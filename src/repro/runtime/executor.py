"""WQ-driven executors: the paper's architecture running real ML work.

TrainExecutor — the supervisor expands a (sweep x step-stream) workflow into
tasks; each scheduler tick claims the next task per worker slice from the
partitioned WQ (one vectorized claim — the wq_claim semantics), executes the
jitted train step with the task's knobs (lr scale, data shard, sweep member),
and commits provenance (loss, grad norm, timing) back to the SAME store the
steering engine queries — the paper's single-database HTAP design, with
training steps in place of Risers simulations.

ServeExecutor — continuous batching: requests are WQ rows; decode slots claim
requests from their partition; per-token progress/results are store updates.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig
from repro.configs.risers_workflow import WorkflowConfig
from repro.core.replication import make_replicator
from repro.core.schema import Status
from repro.core.sharding_router import ShardRouter
from repro.core.steering import SteeringEngine
from repro.core.supervisor import SecondarySupervisor, Supervisor
from repro.core.workqueue import WorkQueue
from repro.data.pipeline import DataConfig, batch_for
from repro.launch.steps import device_bytes_limit, init_train_state, \
    jit_train_step, make_serve_step, plan_train_step
from repro.models.registry import build_model


@dataclasses.dataclass
class TrainTaskSpec:
    """Domain columns of a training task: in0 = lr scale, in1 = data shard,
    in2 = sweep member id. Outputs: out0 = loss, out1 = grad norm,
    out2 = tokens/s (sim)."""
    lr_scale: float
    shard: int
    sweep_id: int


class TrainExecutor:
    def __init__(self, cfg: ModelConfig, *, num_workers: int = 1,
                 base_lr: float = 3e-4, data_cfg: Optional[DataConfig] = None,
                 checkpointer=None, checkpoint_every: int = 50,
                 steer_every: int = 0, seed: int = 0,
                 analyst: str = "snapshot", replicas: int = 1,
                 shards: int = 1, lease_s: Optional[float] = None):
        self.cfg = cfg
        self.num_workers = num_workers
        self.base_lr = base_lr
        self.data_cfg = data_cfg or DataConfig(
            vocab_size=cfg.vocab_size, seq_len=128, batch_size=8)
        # shards > 1: the sharded topology — num_workers partitions split
        # across `shards` full primaries behind a ShardRouter; claims,
        # replication, and compaction run per shard, steering is the
        # router's scatter-gather sweep, and drained shards pull work from
        # rich siblings (cross-shard stealing) each tick.
        if shards < 1:
            raise ValueError("shards must be >= 1")
        if shards > 1 and num_workers % shards:
            raise ValueError(f"num_workers={num_workers} must divide "
                             f"evenly across shards={shards}")
        self.workflow = WorkflowConfig(name="train-sweep",
                                       activities=("train_step",))
        self.router: Optional[ShardRouter] = None
        if shards > 1:
            # checkpointing a sharded run is supported since PR 9: the
            # Checkpointer cuts one store-lock-consistent snapshot per
            # shard plus the version vector into a single atomic manifest
            self.router = ShardRouter(
                shards, num_workers // shards,
                replicate=None if analyst == "snapshot" else analyst,
                replicas=replicas, lease_s=lease_s)
            # per-shard supervision: each Shard gets a Supervisor +
            # SecondarySupervisor so expansion state survives a
            # promote_shard (the single-activity training workflow keeps
            # shard-local id allocation safe)
            self.router.attach_supervision(self.workflow)
            self.wq = self.router.shards[0].wq   # compat: a primary handle
            self.supervisor = self.secondary = None
            self.steering = None
        else:
            self.wq = WorkQueue(num_workers=num_workers, lease_s=lease_s)
        if self.router is None:
            self.supervisor = Supervisor(self.wq, self.workflow)
            self.secondary = SecondarySupervisor(self.supervisor)
            self.steering = SteeringEngine(self.wq)
        # analyst="snapshot": sweeps read COW snapshot views of the LIVE
        # store (share its arrays until the next write). analyst="replica":
        # sweeps read a delta-caught-up REPLICA store fed only by the txn
        # log — the paper's "steering never touches the transactional hot
        # path", made structural: the analyst thread never holds a single
        # live array. analyst="remote": the replica lives in a SEPARATE OS
        # process fed wire-encoded deltas over a transport (pipe, or TCP
        # for another host); sweeps execute in that process and only the
        # result ships back — the paper's distributed topology (analytical
        # node != data node) for real. ``replicas`` > 1 fans the remote
        # mode out to an N-member ReplicaGroup: deltas broadcast to every
        # member, sweeps round-robin across them.
        if analyst not in ("snapshot", "replica", "remote"):
            raise ValueError(f"unknown analyst mode {analyst!r}")
        self.analyst = analyst
        self.replica = None
        if analyst != "snapshot" and self.router is None:
            # all replication policy lives behind the factory: "replica"
            # maps to the in-process delta arm (nothing ships, so the
            # wire-size accounting is skipped), "remote" to a pipelined
            # replica group fed over the wire
            self.replica = make_replicator(
                self.wq, analyst, replicas=replicas,
                account_encoded=False)
        self.checkpointer = checkpointer
        self.checkpoint_every = checkpoint_every
        self.steer_every = steer_every
        # steering sweeps run on an analyst thread against a store snapshot,
        # concurrent with the claim/train/commit loop (HTAP, paper Exp. 7)
        self._steer_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="steering")
        self._steer_future: Optional[concurrent.futures.Future] = None
        self.last_steering: Optional[Dict[str, object]] = None
        # store the forward's residuals when they fit on the device, else
        # recompute them in the backward pass (from shapes, nothing runs)
        self.step_plan = plan_train_step(
            cfg, batch_for(cfg, self.data_cfg, 0), device_bytes_limit())
        self.step_fn = jit_train_step(self.step_plan.cfg)
        self.state = init_train_state(cfg, jax.random.PRNGKey(seed))
        self.step = 0
        self.reaped_total = 0
        self.history: List[Dict[str, float]] = []

    # ------------------------------------------------------------- seeding
    def submit_steps(self, n: int, *, lr_scale: float = 1.0,
                     sweep_id: int = 0) -> np.ndarray:
        dom = np.stack([
            np.full(n, lr_scale),
            np.arange(self.step, self.step + n) % (1 << 20),
            np.full(n, sweep_id),
        ], axis=1)
        if self.router is not None:
            return self.router.add_tasks(0, n, domain_in=dom,
                                         now=time.time())
        return self.wq.add_tasks(0, n, domain_in=dom, now=time.time())

    # ---------------------------------------------------------------- tick
    def tick(self) -> Dict[str, float]:
        """One scheduler tick: claim -> execute -> commit provenance."""
        with tracing.span("wf.tick"):
            return self._tick()

    def _tick(self) -> Dict[str, float]:
        now = time.time()
        if self.router is not None:
            # any drained shard refills from the richest sibling BEFORE
            # claiming — the cross-shard stealing path
            with tracing.span("wf.rebalance"):
                if (self.router.ready_counts()
                        .reshape(self.router.num_shards, -1).sum(1)
                        == 0).any():
                    self.router.rebalance(now=now)
            claims = [(self.router.shards[s].wq, rows)
                      for s, rows in self.router.claim_all(
                          k=1, now=now).values()]
        else:
            claims = [(self.wq, rows)
                      for rows in self.wq.claim_all(k=1, now=now).values()]
        metrics_out: Dict[str, float] = {}
        for wq, rows in claims:
            for row in rows:
                task = int(wq.store.col("task_id")[row])
                with tracing.span("wf.batch", task=task):
                    lr_scale = wq.store.col("in0")[row]
                    shard = int(wq.store.col("in1")[row])
                    batch = batch_for(self.cfg, self.data_cfg, shard)
                    knobs = {"lr": jnp.asarray(self.base_lr * lr_scale,
                                               jnp.float32)}
                t0 = time.time()
                with tracing.span("wf.dispatch", task=task,
                                  residuals=self.step_plan.residuals,
                                  residual_bytes=self.step_plan.residual_bytes):
                    self.state, metrics = self.step_fn(self.state, batch,
                                                       knobs)
                with tracing.span("wf.sync", task=task):
                    loss = float(metrics["loss"])
                    gnorm = float(metrics["grad_norm"])
                dt_s = time.time() - t0
                wq.finish(np.asarray([row]), now=time.time(),
                          domain_out=np.asarray(
                              [[loss, gnorm, dt_s]]))
                self.step += 1
                rec = {"step": self.step, "loss": loss, "grad_norm": gnorm,
                       "s_per_step": dt_s}
                self.history.append(rec)
                metrics_out = rec
        if self.checkpointer and self.checkpoint_every \
                and self.step and self.step % self.checkpoint_every == 0:
            with tracing.span("wf.checkpoint"):
                if self.router is not None:
                    self.router.sync_secondaries()
                    self.checkpointer.save(self.step, self.state,
                                           router=self.router)
                else:
                    self.checkpointer.save(self.step, self.state, self.wq)
            self._maybe_compact_log()
        if self._steer_future is not None and self._steer_future.done():
            with tracing.span("wf.harvest"):
                self.last_steering = self._steer_future.result()
            metrics_out["steering"] = self.last_steering
            self._steer_future = None
        if self.steer_every and self.step % self.steer_every == 0 \
                and self._steer_future is None:
            # the steering tick doubles as the lease sweep: requeue every
            # expired RUNNING claim (data-plane dead-worker recovery) before
            # analyzing, so the sweep sees the recovered backlog — sharded
            # runs reap per shard and the reclaimed rows feed rebalance
            with tracing.span("wf.reap"):
                self.reaped_total += self.reap(now=time.time())
            with tracing.span("wf.steer_submit"):
                self._steer_submit()
        return metrics_out

    def _steer_submit(self) -> None:
        """Hand this tick's sweep to the analyst thread: cut what it reads
        here (snapshot, replica catch-up or version vector), run it there."""
        if self.router is not None:
            # scatter-gather sweep: pin a consistent version vector on
            # THIS thread (at this tick's commits), merge on the
            # analyst thread; "remote" scatters the sweep into the
            # per-shard replica processes instead
            if self.analyst == "remote":
                # pin + ship on THIS (producer) thread — sync_replicas
                # settles every shard's replica exactly at this tick's
                # version vector — then scatter the partial sweeps into
                # the per-shard replica processes from the analyst
                # thread (sync=False: only log-free sweep requests ride
                # the pipes, so the producer keeps claiming meanwhile)
                vec = self.router.sync_replicas()
                self._steer_future = self._steer_pool.submit(
                    tracing.handoff(self.router.remote_sweep, "wf.sweep"),
                    time.time(), versions=vec, sync=False)
            else:
                views = (self.router.replica_vector()
                         if self.analyst == "replica"
                         else self.router.snapshot_vector())
                self._steer_future = self._steer_pool.submit(
                    tracing.handoff(self.router.run_all, "wf.sweep"),
                    time.time(), views)
            return
        if self.replica is not None:
            # catch the replica up to this tick's commits (O(delta)
            # wire ship for "remote", in-process log replay for
            # "replica"); the sync acked the replica's consumer
            # offset, so compaction piggybacks once a durable
            # checkpoint anchors history
            with tracing.span("wf.ship"):
                self.replica.sync()
            self._maybe_compact_log()
        if self.analyst == "remote":
            # run the sweep IN the replica process: the analyst thread
            # only waits on the result pipe — no store array, live or
            # copied, crosses back
            self._steer_future = self._steer_pool.submit(
                tracing.handoff(self.replica.remote_sweep, "wf.sweep"),
                time.time())
        else:
            # replica: sweep the caught-up shadow store — the live
            # arrays are never handed to the analyst thread at all.
            # snapshot: COW view of the live store at this tick's
            # commits, analyzed while the next ticks keep claiming
            view = self.replica.snapshot_view() \
                if self.replica is not None \
                else self.wq.store.snapshot_view()
            self._steer_future = self._steer_pool.submit(
                tracing.handoff(self.steering.run_all, "wf.sweep"),
                time.time(), view)

    def _maybe_compact_log(self) -> None:
        """Compact the txn log only once a DURABLE checkpoint has acked an
        offset: truncation is then 'since last checkpoint' by construction,
        so `SteeringEngine.at_version` keeps its documented degradation path
        (base snapshot = the checkpoint). Without a checkpoint consumer the
        log is left whole — genesis time-travel stays available and memory
        is bounded by the caller's own `wq.compact_log()` policy instead."""
        with tracing.span("wf.compact"):
            if self.router is not None:
                for sh in self.router.shards:
                    if sh.alive and sh.wq.log.has_consumer("checkpointer"):
                        sh.wq.compact_log()
                return
            if self.wq.log.has_consumer("checkpointer"):
                self.wq.compact_log()

    def run(self, max_ticks: int = 10_000) -> List[Dict[str, float]]:
        for _ in range(max_ticks):
            left = (self.router.tasks_left() if self.router is not None
                    else self.steering.q4_tasks_left())
            if left == 0:
                break
            self.tick()
        self._drain_steering()
        return self.history

    def _drain_steering(self) -> None:
        """Harvest an in-flight sweep; record it on the latest history entry
        so short runs still surface their final (paid-for) sweep."""
        if self._steer_future is not None:
            self.last_steering = self._steer_future.result()
            self._steer_future = None
            if self.history:
                self.history[-1].setdefault("steering", self.last_steering)

    def close(self) -> None:
        """Release the steering analyst thread (ticks after close raise)."""
        self._drain_steering()
        self._steer_pool.shutdown(wait=True)
        if self.replica is not None:
            self.replica.close()     # stop pinning the log compaction floor
        if self.router is not None:
            self.router.close()      # per-shard replicators + steal pipe

    def __del__(self):
        try:
            self._steer_pool.shutdown(wait=False)
        except Exception:
            pass

    # -------------------------------------------------------------- fault
    def reap(self, *, now: Optional[float] = None,
             max_trials: int = 3) -> int:
        """Requeue expired-lease RUNNING rows (``WorkQueue.reap_expired``),
        across every shard when sharded. Runs automatically on the steering
        tick; callable directly for tighter recovery cadences."""
        now = time.time() if now is None else now
        if self.router is not None:
            return self.router.reap_expired(now=now, max_trials=max_trials)
        return self.wq.reap_expired(now=now, max_trials=max_trials)

    def fail_worker(self, worker_id: int) -> int:
        """Simulate a node failure: requeue its RUNNING tasks elsewhere
        (sharded: within the shard owning that global worker)."""
        if self.router is not None:
            L = self.router.workers_per_shard
            sh = self.router.shards[worker_id // L]
            return sh.wq.requeue_worker(worker_id % L)
        return self.wq.requeue_worker(worker_id)

    def promote_secondary(self, shard: Optional[int] = None) -> None:
        """Fail the supervisor over to its shadow. Sharded runs promote
        per shard (``shard=None`` promotes every shard's secondary) — each
        promoted supervisor gets a bumped generation and resumes expansion
        exactly via the store's ``expanded`` column."""
        if self.router is not None:
            shards = (range(self.router.num_shards) if shard is None
                      else [shard])
            for s in shards:
                sh = self.router.shards[s]
                if sh.secondary is None:
                    raise ValueError(f"shard {s} has no supervision "
                                     "attached")
                sh.supervisor.crash()
                sh.supervisor = sh.secondary.promote()
                sh.secondary = SecondarySupervisor(sh.supervisor)
            return
        self.supervisor.crash()
        self.supervisor = self.secondary.promote()
        self.secondary = SecondarySupervisor(self.supervisor)

    def fail_shard(self, shard: int) -> None:
        """Kill a shard primary mid-run (sharded executors only)."""
        if self.router is None:
            raise ValueError("fail_shard requires a sharded executor")
        self.router.fail_shard(shard)

    def promote_shard(self, shard: int):
        """Fail a dead shard over onto its most-caught-up replica; the
        compat ``self.wq`` handle tracks shard 0's promoted queue."""
        if self.router is None:
            raise ValueError("promote_shard requires a sharded executor")
        wq = self.router.promote_shard(shard)
        if shard == 0:
            self.wq = wq
        return wq


class ServeExecutor:
    """Continuous batching driven by the store: requests are WQ rows."""

    def __init__(self, cfg: ModelConfig, *, slots: int = 4,
                 max_len: int = 128, seed: int = 0):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.wq = WorkQueue(num_workers=slots)
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self.serve_fn = jax.jit(make_serve_step(cfg))
        self.prefill_fn = jax.jit(
            lambda p, b: self.model.prefill(p, b, self.max_len))
        self.cache = None
        self.slot_row: Dict[int, int] = {}
        self.slot_tokens: Dict[int, List[int]] = {}
        self.slot_budget: Dict[int, int] = {}
        self.rng = jax.random.PRNGKey(seed)

    def submit(self, prompts: np.ndarray, max_new: int = 16) -> np.ndarray:
        n = prompts.shape[0]
        dom = np.stack([np.full(n, max_new), np.zeros(n), np.zeros(n)],
                       axis=1)
        ids = self.wq.add_tasks(0, n, domain_in=dom, now=time.time())
        for tid, p in zip(ids, prompts):
            self.wq.store.blobs[int(tid)] = {"prompt": p}
        return ids

    def _admit(self) -> None:
        """Claim queued requests into free slots (continuous batching)."""
        free = [s for s in range(self.slots) if s not in self.slot_row]
        if not free:
            return
        for s in free:
            rows = self.wq.claim(s, k=1, now=time.time(), allow_steal=True)
            if len(rows) == 0:
                continue
            row = int(rows[0])
            tid = int(self.wq.store.col("task_id")[row])
            prompt = self.wq.store.blobs[tid]["prompt"]
            batch = {"tokens": prompt[None, :].astype(np.int32)}
            logits, cache = self.prefill_fn(self.params, batch)
            nxt = int(jnp.argmax(logits[0, -1]))
            if self.cache is None or s not in self.slot_tokens:
                pass
            self.slot_row[s] = row
            self.slot_tokens[s] = [nxt]
            self.slot_budget[s] = int(self.wq.store.col("in0")[row])
            self._caches = getattr(self, "_caches", {})
            self._caches[s] = cache

    def step_decode(self) -> int:
        """One decode step across active slots; returns #finished."""
        self._admit()
        finished = 0
        for s in list(self.slot_row):
            cache = self._caches[s]
            tok = jnp.asarray([[self.slot_tokens[s][-1]]], jnp.int32)
            self.rng, sub = jax.random.split(self.rng)
            nxt, cache, _ = self.serve_fn(self.params, tok, cache, sub)
            self._caches[s] = cache
            self.slot_tokens[s].append(int(nxt[0, 0]))
            if len(self.slot_tokens[s]) >= self.slot_budget[s] \
                    or int(cache["idx"]) >= self.max_len - 1:
                row = self.slot_row.pop(s)
                toks = self.slot_tokens.pop(s)
                tid = int(self.wq.store.col("task_id")[row])
                self.wq.store.blobs[tid]["output"] = np.asarray(toks)
                self.wq.finish(np.asarray([row]), now=time.time(),
                               domain_out=np.asarray(
                                   [[float(len(toks)), 0.0, 0.0]]))
                finished += 1
        return finished

    def drain(self, max_steps: int = 10_000) -> int:
        total = 0
        for _ in range(max_steps):
            left = SteeringEngine(self.wq).q4_tasks_left()
            if left == 0 and not self.slot_row:
                break
            total += self.step_decode()
        return total
