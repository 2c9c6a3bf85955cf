"""One run of one benchmark cell: set-up, a timed window, the checks.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own under this directory, found by the name
that ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the deployment and its payload's sizes,
  guarantees and correctness limits;
* ``<reference>.py``, the module that the payload's ``"reference"`` names:
  the payload's plain reference, with the functions ``REFERENCE_API``
  lists (``reference.py`` describes them);
* ``traffic/<mix>.json``: the parameters of the one traffic generator below;
* ``metrics/<metric>.py``: a reducer ``reduce(run) -> float | None`` over the
  run's spans, counters and device trace (``None``: nothing to read).

The system under test is built through ``TrainExecutor(...)`` and driven by
its ``tick()``. The benchmark wraps the calls into each layer (claim, step,
commit, the analyst pool, the replication ship) to record spans on the host
clock, written into the profiler trace too when the run is traced.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import statistics
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TRACE_SECONDS = 4.0          # the traced part of the window: its last seconds
SWEEP_WAIT_S = 60.0          # wait for a sweep in flight when the window ends


class BenchError(RuntimeError):
    """The run cannot be made: no chip, a missing file, a malformed entry."""


# ---------------------------------------------------------------- loading
def load_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise BenchError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo: str = REPO) -> Dict[str, Any]:
    return load_json(os.path.join(repo, "BENCHMARK.json"))


def load_config(name: str, base: str = HERE) -> Dict[str, Any]:
    return load_json(os.path.join(base, "configs", f"{name}.json"))


# What the one traffic generator below implements: a closed loop over a
# backlog submitted whole in set-up, with a sweep every ``steer_every``
# ticks (0: none). A mix that asks for anything else is refused.
TRAFFIC_KEYS = {"name", "loop", "backlog", "steer_every", "why"}
TRAFFIC_VALUES = {"loop": {"closed"}, "backlog": {"preloaded"}}


def load_traffic(name: str, base: str = HERE) -> Dict[str, Any]:
    t = load_json(os.path.join(base, "traffic", f"{name}.json"))
    if set(t) != TRAFFIC_KEYS:
        raise BenchError(f"traffic {name}: keys {sorted(t)}, the generator "
                         f"reads {sorted(TRAFFIC_KEYS)}")
    for k, allowed in TRAFFIC_VALUES.items():
        if t[k] not in allowed:
            raise BenchError(f"traffic {name}: {k}={t[k]!r} is not "
                             f"implemented (only {sorted(allowed)})")
    if not isinstance(t["steer_every"], int) or t["steer_every"] < 0:
        raise BenchError(f"traffic {name}: steer_every must be an int >= 0")
    return t


def _load_module(prefix: str, name: str, path: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, base: str = HERE) -> Callable[["RunRecord"],
                                                         Optional[float]]:
    path = os.path.join(base, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"missing metric reader {path}")
    return _load_module("bench_metric_", name, path).reduce


# What a payload's reference module provides, by name.
REFERENCE_API = ("weight_shapes", "weight_leaf", "make_weights", "leaf_norms",
                 "train_readings", "program_overrides", "train_step_flops")


def load_reference(cfg_file: Dict[str, Any], base: str = HERE):
    """The payload's plain reference: the module ``<base>/<name>.py`` that
    the configuration's ``payload["reference"]`` names."""
    name = cfg_file["payload"].get("reference")
    if not isinstance(name, str) or os.path.basename(name) != name:
        raise BenchError(f"configuration {cfg_file.get('name')}: payload "
                         f"reference {name!r} names no module file")
    path = os.path.join(base, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"missing reference module {path}")
    mod = _load_module("bench_reference_", name, path)
    missing = [f for f in REFERENCE_API if not callable(getattr(mod, f, None))]
    if missing:
        raise BenchError(f"reference module {path} lacks {missing}")
    return mod


def resolve_cell(bench: Dict[str, Any], workload: str, base: str = HERE
                 ) -> Dict[str, Any]:
    """The cell's entry, its configuration and traffic files, and the
    metrics it reports (end-to-end with --trace 0, per-layer with 1)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    return {
        "cell": cell,
        "config": load_config(cell["config"], base),
        "traffic": load_traffic(cell["traffic"], base),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "base": base,
    }


def load_peaks(kind: str, base: str = HERE) -> Dict[str, float]:
    table = load_json(os.path.join(base, "peaks.json"))
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


# ------------------------------------------------------------------ seeds
def derive_seeds(seed: int) -> Dict[str, int]:
    """Independent sub-seeds from one --seed of any size."""
    s = np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)
    return {"w_hi": int(s[0]) & 0x7FFFFFFF, "w_lo": int(s[1]) & 0x7FFFFFFF,
            "data": int(s[2]), "program": int(s[3]) & 0x7FFFFFFF}


def weights_key(seeds: Dict[str, int]):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seeds["w_hi"]),
                              seeds["w_lo"])


# ------------------------------------------------------------------ spans
class Spans:
    """Host-clock spans of the calls into each layer. With ``annotate`` on,
    each span is also a ``jax.profiler.TraceAnnotation`` so that the trace
    can charge device idle gaps to the host work that filled them.

    ``rec[name]`` holds each call's ``perf_counter`` bounds and
    ``wall[name]`` its ``time.time()`` bounds, the clock the program stamps
    the relation with; ``open_wall[name]`` is the entry time of a call in
    progress. With ``probe``, ``probes[name]`` holds ``probe()`` read at each
    call's entry and exit."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rec: Dict[str, List[Tuple[float, float]]] = {}
        self.wall: Dict[str, List[Tuple[float, float]]] = {}
        self.open_wall: Dict[str, float] = {}
        self.probes: Dict[str, List[Tuple[Any, Any]]] = {}

    def wrap(self, name: str, fn: Callable, after: Optional[Callable] = None,
             probe: Optional[Callable[[], Any]] = None) -> Callable:
        import jax
        rec = self.rec.setdefault(name, [])
        wall = self.wall.setdefault(name, [])
        probes = self.probes.setdefault(name, [])
        label = "bench." + name
        annotate = self.annotate
        open_wall = self.open_wall

        def inner(*a, **kw):
            p0 = probe() if probe is not None else None
            t0 = time.perf_counter()
            w0 = open_wall[name] = time.time()
            if annotate:
                with jax.profiler.TraceAnnotation(label):
                    out = fn(*a, **kw)
            else:
                out = fn(*a, **kw)
            w1 = time.time()
            t1 = time.perf_counter()
            rec.append((t0, t1))
            wall.append((w0, w1))
            if probe is not None:
                probes.append((p0, probe()))
            if after is not None:
                after(a, kw, out, t0, t1)
            return out
        return inner

    def within(self, name: str, t0: float, t1: float) -> List[float]:
        """Durations (s) of the spans of ``name`` that started in [t0, t1]."""
        return [b - a for a, b in self.rec.get(name, ()) if t0 <= a <= t1]


class SweepPool:
    """Stands in front of the executor's analyst pool: records, for every
    sweep, when it was handed over, when the analyst thread started and
    finished it, its ``now`` argument and its result."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self.sweeps: List[Dict[str, Any]] = []
        self.futures = []
        self._run = spans.wrap("sweep", lambda fn, *a, **kw: fn(*a, **kw))

    def submit(self, fn, *args, **kwargs):
        rec = {"handed": time.perf_counter(), "wall": time.time(),
               "now": float(args[0])}
        self.sweeps.append(rec)

        def job():
            rec["start"] = time.perf_counter()
            try:
                rec["result"] = self._run(fn, *args, **kwargs)
            except BaseException as e:                    # recorded, re-raised
                rec["error"] = repr(e)
                raise
            finally:
                rec["end"] = time.perf_counter()
            return rec["result"]
        fut = self.inner.submit(job)
        self.futures.append(fut)
        return fut

    def wait(self, timeout: float) -> None:
        import concurrent.futures
        concurrent.futures.wait(self.futures, timeout=timeout)

    def shutdown(self, wait: bool = True):
        return self.inner.shutdown(wait=wait)


# ------------------------------------------------------------ the program
_DROP = {"table", "scale", "attn", "mlp"}


def flat_name(path) -> str:
    """Program parameter path -> the reference's flat weight name."""
    keys = [getattr(k, "key", str(k)) for k in path]
    return ".".join(k for k in keys if k not in _DROP)


def to_program_tree(like, flat: Dict[str, Any]):
    """Put the benchmark's weights into the program's parameter tree."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    names = [flat_name(p) for p, _ in leaves]
    if sorted(names) != sorted(flat):
        raise BenchError(f"parameter names differ: program {sorted(names)} "
                         f"vs benchmark {sorted(flat)}")
    for (p, leaf), n in zip(leaves, names):
        if leaf.shape != flat[n].shape or leaf.dtype != flat[n].dtype:
            raise BenchError(f"{n}: program {leaf.shape} {leaf.dtype}, "
                             f"benchmark {flat[n].shape} {flat[n].dtype}")
    return jax.tree_util.tree_unflatten(treedef, [flat[n] for n in names])


def from_program_tree(tree) -> Dict[str, Any]:
    import jax
    return {flat_name(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _laid_over(obj, fields: Dict[str, Any]):
    """The dataclass ``obj`` with ``fields`` laid over it; a dict given for
    a field that holds a dataclass is laid over that field's value."""
    known = {f.name for f in dataclasses.fields(obj)}
    out = {}
    for k, v in fields.items():
        if k not in known:
            raise BenchError(f"{type(obj).__name__} has no field {k!r}")
        if isinstance(v, dict):
            inner = getattr(obj, k)
            if not dataclasses.is_dataclass(inner):
                raise BenchError(f"{type(obj).__name__}.{k} is {inner!r}, "
                                 f"no dataclass to lay {sorted(v)} over")
            v = _laid_over(inner, v)
        out[k] = v
    return dataclasses.replace(obj, **out)


def model_config(payload: Dict[str, Any], overrides: Dict[str, Any]):
    """The program's ModelConfig for the payload the file describes: the
    published configuration of ``arch`` with ``overrides`` (the reference
    module's ``program_overrides(payload)``) laid over it."""
    from repro.configs import get_config
    cfg = _laid_over(get_config(payload["arch"]), overrides)
    if cfg.optimizer != payload["optimizer"]["name"]:
        raise BenchError(f"optimizer {cfg.optimizer} != "
                         f"{payload['optimizer']['name']}")
    return cfg


class Recorder:
    """What the benchmark sees of one run of the program."""

    def __init__(self):
        self.step_metrics: List[Dict[str, Any]] = []
        self.step_inputs: List[Dict[str, np.ndarray]] = []
        self.step_wall: List[float] = []       # wall clock at each step's return
        self.claims: List[Dict[str, Any]] = []
        self.finishes: List[Dict[str, Any]] = []
        self.grad1: Optional[Dict[str, np.ndarray]] = None
        self.delta: Optional[Dict[str, np.ndarray]] = None


def leaf_groups(shapes: Dict[str, Tuple[int, ...]]) -> List[List[str]]:
    """The leaves in groups of at most the largest leaf's size, filled
    largest first: few calls, none holding more than about one leaf."""
    size = {n: math.prod(s) for n, s in shapes.items()}
    groups: List[List[str]] = []
    room: List[int] = []
    for n in sorted(size, key=lambda n: (-size[n], n)):
        i = next((i for i, r in enumerate(room) if size[n] <= r), None)
        if i is None:
            groups.append([])
            room.append(max(size.values()))
            i = len(room) - 1
        groups[i].append(n)
        room[i] -= size[n]
    return groups


def change_call(ref, payload: Dict[str, Any]) -> Callable:
    """Jitted ``(leaves, key) ->`` the reference's ``leaf_norms`` of each
    given leaf's change from its initial value, which is made again from
    the key. Called once per group of ``leaf_groups``, so that no whole tree
    is made beside the program's state."""
    import jax

    def change(xs, key):
        return ref.leaf_norms({n: x - ref.weight_leaf(payload, key, n)
                               for n, x in xs.items()})
    return jax.jit(change)


def build(cfg_file: Dict[str, Any], traffic: Dict[str, Any], seed: int,
          spans: Spans, ref):
    """Set-up, part one: the executor, the seeded weights (made by the
    payload's reference module ``ref``), the backlog and the benchmark's
    wrappers. Returns (executor, recorder, sweep pool)."""
    import jax

    from repro.data.pipeline import DataConfig
    from repro.runtime.executor import TrainExecutor

    payload = cfg_file["payload"]
    cfg = model_config(payload, ref.program_overrides(payload))
    seeds = derive_seeds(seed)
    ex = TrainExecutor(
        cfg, num_workers=int(cfg_file["workers"]),
        data_cfg=DataConfig(vocab_size=int(payload["vocab_size"]),
                            seq_len=int(payload["seq_len"]),
                            batch_size=int(payload["batch_size"]),
                            seed=seeds["data"]),
        base_lr=float(payload["optimizer"]["lr"]),
        steer_every=int(traffic["steer_every"]), seed=seeds["program"],
        analyst=cfg_file["analyst"], replicas=max(1, int(cfg_file["replicas"])),
        shards=int(cfg_file["shards"]), lease_s=float(cfg_file["lease_s"]))
    key = weights_key(seeds)
    # the executor's own initial parameters are freed before the benchmark's
    # are made, so that the two trees never share the chip
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        ex.state["params"])
    ex.state["params"] = None
    ex.state["params"] = to_program_tree(like, ref.make_weights(payload, key))
    rec = Recorder()
    b1 = float(payload["optimizer"]["beta1"])
    norms = jax.jit(ref.leaf_norms)
    groups = leaf_groups(ref.weight_shapes(payload))
    change = change_call(ref, payload)

    def step_after(a, kw, out, t0, t1):
        state, metrics = out
        rec.step_metrics.append(metrics)
        n = len(rec.step_metrics)
        if n <= 3:
            rec.step_inputs.append({k: np.asarray(v) for k, v in a[1].items()})
        if n == 1:      # the first gradient as the optimizer got it
            m = from_program_tree(state["opt"]["inner"]["m"])
            rec.grad1 = {k: np.asarray(v) / (1.0 - b1)
                         for k, v in norms(m).items()}
        if n == 3:      # the parameters' change over the first three steps
            params = from_program_tree(state["params"])
            rec.delta = {k: np.asarray(v) for g in groups for k, v in
                         change({k: params[k] for k in g}, key).items()}
        rec.step_wall.append(time.time())
    ex.step_fn = spans.wrap("step", ex.step_fn, step_after)

    def finish_after(shard):
        def after(a, kw, out, t0, t1):
            # the program has read these to the host before its commit; keep
            # the host values, not the step's device buffers
            m = rec.step_metrics[-1]
            rec.step_metrics[-1] = {k: float(m[k]) for k in
                                    ("loss", "grad_norm")}
            rec.finishes.append({
                "shard": shard, "rows": np.asarray(a[0]).copy(),
                "out": np.asarray(kw["domain_out"]).copy(), "t": t1,
                "now": float(kw["now"]), "wall": spans.wall["commit"][-1]})
        return after

    def claim_after(a, kw, out, t0, t1):
        for v in out.values():
            shard, rows = v if ex.router is not None else (0, v)
            for r in np.asarray(rows):
                rec.claims.append({
                    "shard": shard, "row": int(r), "now": float(kw["now"]),
                    "tick_wall": spans.open_wall["tick"],
                    "wall": spans.wall["claim"][-1]})
    if ex.router is not None:
        for s, sh in enumerate(ex.router.shards):
            sh.wq.finish = spans.wrap("commit", sh.wq.finish,
                                      finish_after(s))
        ex.router.claim_all = spans.wrap("claim", ex.router.claim_all,
                                         claim_after)
        ex.router.sync_replicas = spans.wrap("ship", ex.router.sync_replicas)
    else:
        ex.wq.finish = spans.wrap("commit", ex.wq.finish, finish_after(0))
        ex.wq.claim_all = spans.wrap("claim", ex.wq.claim_all, claim_after)
    ex.tick = spans.wrap("tick", ex.tick, probe=host_probe())
    pool = SweepPool(ex._steer_pool, spans)
    ex._steer_pool = pool
    ex.submit_steps(int(cfg_file["tasks"]))
    return ex, rec, pool


_GC = {"pause_s": 0.0, "full": 0, "t": 0.0}


def host_probe() -> Callable[[], Tuple[float, ...]]:
    """What the host did besides the calls the spans cover, read at a
    tick's entry and exit: this thread's CPU seconds, seconds paused in
    Python's garbage collector, full collections, major and minor page
    faults, voluntary and involuntary context switches."""
    import resource
    if _gc_listen not in gc.callbacks:
        gc.callbacks.append(_gc_listen)

    def probe():
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (time.thread_time(), _GC["pause_s"], _GC["full"],
                ru.ru_majflt, ru.ru_minflt, ru.ru_nvcsw, ru.ru_nivcsw)
    return probe


def _gc_listen(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        _GC["t"] = time.perf_counter()
    else:
        _GC["pause_s"] += time.perf_counter() - _GC["t"]
        _GC["full"] += info["generation"] == 2


PROBE_FIELDS = ("cpu_s", "gc_s", "gc_full", "majflt", "minflt", "vcsw",
                "ivcsw")


def slow_ticks(spans: Spans, window: Tuple[float, float], n: int = 2
               ) -> List[str]:
    """The ``n`` longest ticks of the window: how long, when, the host's
    probe over each, and the time of each layer's calls inside it."""
    t0, t1 = window
    ticks = [(b - a, a, i) for i, (a, b) in enumerate(spans.rec["tick"])
             if t0 <= a <= t1]
    if not ticks:
        return []
    med = statistics.median(d for d, _, _ in ticks)
    first, last = spans.probes["tick"][ticks[0][2]][0], \
        spans.probes["tick"][ticks[-1][2]][1]
    host = " ".join(f"{k} {y - x:.6g}" for k, x, y in
                    zip(PROBE_FIELDS, first, last))
    lines = [f"window_ticks {len(ticks)} median_s {med:.6f} over_2x_median "
             f"{sum(d > 2 * med for d, _, _ in ticks)} {host}"]
    for d, a, i in sorted(ticks, reverse=True)[:n]:
        p0, p1 = spans.probes["tick"][i]
        host = " ".join(f"{k} {y - x:.6g}" for k, x, y in
                        zip(PROBE_FIELDS, p0, p1))
        inner = " ".join(
            f"{name}_s {sum(e - s for s, e in rec if a <= s <= a + d):.6f}"
            for name, rec in sorted(spans.rec.items()) if name != "tick")
        # from a step's return to its commit: the wait for the device
        sync = sum(c[0] - s[1] for s, c in zip(spans.rec.get("step", ()),
                                                spans.rec.get("commit", ()))
                   if a <= s[0] <= a + d)
        lines.append(f"slow_tick {d:.6f} at_s {a - t0:.3f} {host} {inner} "
                     f"sync_s {sync:.6f}")
    return lines


def warm_up(ex, rec: Recorder, pool: SweepPool, traffic, spans: Spans,
            max_ticks: int = 64) -> int:
    """Set-up, part two: tick until every path the window takes has run
    once (the train step three times, a sweep harvested, a ship made), so
    that nothing compiles or spawns inside the window."""
    steer = int(traffic["steer_every"]) > 0
    ticks = 0
    while ticks < max_ticks:
        done = (len(rec.step_metrics) >= 3 and ticks >= 2
                and (not steer or ex.last_steering is not None)
                and (not steer or ex.router is None
                     or spans.rec.get("ship")))
        if done:
            return ticks
        ex.tick()
        ticks += 1
    raise BenchError(f"warm-up did not reach every path in {max_ticks} ticks")


# ------------------------------------------------------------------ checks
def relations_of(ex) -> List[Dict[str, np.ndarray]]:
    """Each primary's relation, columns cut to the used rows."""
    wqs = ([sh.wq for sh in ex.router.shards] if ex.router is not None
           else [ex.wq])
    out = []
    for wq in wqs:
        snap = wq.store.snapshot()
        out.append(snap["cols"])
    return out


def check_commits(rels, rec: Recorder, tasks: int, cfg_file,
                  data_seed: int) -> Dict[str, float]:
    """Claims and commits: every task id present once across the primaries,
    every task committed once, each committed row's provenance equal to what
    the step returned, and the first steps fed the generator's batches."""
    from datagen import shard_batch
    from steering_ref import FINISHED
    ids = np.concatenate([r["task_id"] for r in rels])
    lost = int(tasks - np.unique(ids).size) + int(ids.size - np.unique(ids).size)
    pairs = [(f["shard"], int(r)) for f in rec.finishes for r in f["rows"]]
    twice = len(pairs) - len(set(pairs))
    finished = sum(int((r["status"] == FINISHED).sum()) for r in rels)
    twice += abs(finished - len(pairs))
    bad = 0
    if len(rec.finishes) != len(rec.step_metrics):
        bad += abs(len(rec.finishes) - len(rec.step_metrics))
    for f, m in zip(rec.finishes, rec.step_metrics):
        r = rels[f["shard"]]
        row = int(f["rows"][0])
        want = (float(m["loss"]), float(m["grad_norm"]))
        got = (float(r["out0"][row]), float(r["out1"][row]))
        if f["out"][0, 0] != want[0] or f["out"][0, 1] != want[1] \
                or got != want or r["status"][row] != FINISHED \
                or not r["end_time"][row] >= r["start_time"][row]:
            bad += 1
    p = cfg_file["payload"]
    inputs_bad = 0
    for f, given in zip(rec.finishes[:3], rec.step_inputs):
        shard = int(rels[f["shard"]]["in1"][int(f["rows"][0])])
        want = shard_batch(data_seed, shard, int(p["batch_size"]),
                           int(p["seq_len"]), int(p["vocab_size"]))
        if not all(np.array_equal(given[k], want[k]) for k in want):
            inputs_bad += 1
    return {"ids_lost_or_doubled": lost, "commits_doubled": twice,
            "provenance_mismatches": bad, "input_mismatches": inputs_bad}


def check_stamps(rels, rec: Recorder, pool: SweepPool) -> Dict[str, float]:
    """The claim and commit times in the relation, and the time each sweep
    was cut at, against the benchmark's own wall clock around the calls:
    a row's ``start_time`` is the ``now`` its claim was given, read in the
    tick that made the claim before the claim returned; its ``end_time`` the
    ``now`` its commit was given, read after its step returned and before
    the commit did; a sweep's ``now`` was read after the last commit before
    it and before it was handed over. The steering reference reads the
    relation at those times, so it rests on these clocks, not on the
    program's stamps alone."""
    import bisect
    bad = 0
    for c in rec.claims:
        r = rels[c["shard"]]
        if not (r["start_time"][c["row"]] == c["now"]
                and c["tick_wall"] <= c["now"] <= c["wall"][1]):
            bad += 1
    for f, w in zip(rec.finishes, rec.step_wall):
        r = rels[f["shard"]]
        if not (all(r["end_time"][int(x)] == f["now"] for x in f["rows"])
                and w <= f["now"] <= f["wall"][1]):
            bad += 1
    ends = [f["t"] for f in rec.finishes]
    for s in pool.sweeps:
        i = bisect.bisect_right(ends, s["handed"])
        low = rec.finishes[i - 1]["wall"][1] if i else -math.inf
        if not low <= s["now"] <= s["wall"]:
            bad += 1
    return {"time_stamp_mismatches": bad}


def check_sweeps(rels, pool: SweepPool, cfg_file) -> Dict[str, float]:
    """Every sweep handed to the analyst against the reference sweep over
    the relation as it stood when the sweep was cut. A sweep that raised or
    never answered counts as a mismatch."""
    import steering_ref
    per_shard = int(cfg_file["workers"]) // int(cfg_file["shards"])
    compared = mism = 0
    for s in pool.sweeps:
        compared += 1
        if "result" not in s or not steering_ref.equal(
                s["result"], steering_ref.sweep(rels, per_shard, s["now"]),
                rels):
            mism += 1
    return {"sweeps_compared": compared, "sweep_mismatches": mism}


def check_replicas(ex) -> Dict[str, float]:
    """Each shard's replica processes against the primary, column by
    column, at the primary's version."""
    bad = 0
    members = 0
    for sh in ex.router.shards:
        rep = sh.replicator
        rep.sync()
        rep.flush()
        prim = sh.wq.store.snapshot()
        for m in rep.members:
            got = m.fetch_remote_state()["snapshot"]
            members += 1
            if got["version"] != prim["version"] or \
                    got["n_rows"] != prim["n_rows"]:
                bad += 1
                continue
            for c, a in prim["cols"].items():
                b = got["cols"][c]
                if a.dtype.kind == "f":
                    same = np.array_equal(a, b, equal_nan=True)
                else:
                    same = np.array_equal(a, b)
                if not same:
                    bad += 1
    return {"replicas_compared": members, "replica_mismatches": bad}


def train_numbers(prog: Dict[str, Any], refr: Dict[str, Any]
                  ) -> Dict[str, float]:
    """The numbers compared for the train step (see PERF.md section 2)."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], refr["loss"]))
    gn_gap = max(abs(a - b) / b for a, b in
                 zip(prog["grad_norm"], refr["grad_norm"]))

    def worst(p, r, keep=None):
        flat_r = np.concatenate([np.ravel(r[n]) for n in sorted(r)])
        med = float(np.median(flat_r))
        gap = 0.0
        for n in sorted(r):
            pr, rr = np.ravel(p[n]), np.ravel(r[n])
            k = np.ones(rr.shape, bool) if keep is None else np.ravel(keep[n])
            if k.any():
                gap = max(gap, float(np.max(
                    np.abs(pr - rr)[k] / np.maximum(rr[k], med))))
        return gap
    g1 = refr["grad1"]
    gmed = float(np.median(np.concatenate([np.ravel(g1[n]) for n in g1])))
    moved = {n: np.ravel(g1[n]) >= 1e-3 * gmed for n in g1}
    return {"loss_gap": loss_gap, "grad_norm_gap": gn_gap,
            "grad_leaf_gap": worst(prog["grad1"], g1),
            "delta_leaf_gap": worst(prog["delta"], refr["delta"], moved)}


def program_readings(rec: Recorder) -> Dict[str, Any]:
    return {"loss": [float(m["loss"]) for m in rec.step_metrics[:3]],
            "grad_norm": [float(m["grad_norm"])
                          for m in rec.step_metrics[:3]],
            "grad1": rec.grad1, "delta": rec.delta}


def reference_readings(cfg_file, ref, rec: Recorder, relations, seed: int,
                       quant: Optional[str] = None, rows: Optional[int] = None
                       ) -> Dict[str, Any]:
    """The plain reference ``ref`` over the first three steps' batches
    (their first ``rows`` rows only, where given: a planted fault)."""
    from datagen import shard_batch
    p = dict(cfg_file["payload"])
    seeds = derive_seeds(seed)
    batches = []
    for f in rec.finishes[:3]:
        shard = int(relations[f["shard"]]["in1"][int(f["rows"][0])])
        bt = shard_batch(seeds["data"], shard, int(p["batch_size"]),
                         int(p["seq_len"]), int(p["vocab_size"]))
        batches.append({k: v[:rows] for k, v in bt.items()})
    key = weights_key(seeds)
    return ref.train_readings(ref.make_weights(p, key), batches, p,
                              lambda: ref.make_weights(p, key), quant)


# ----------------------------------------------------------------- records
@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader gets."""
    spans: Spans
    window: Tuple[float, float]            # perf_counter bounds
    traced: Optional[Tuple[float, float]]  # perf_counter bounds, traced part
    trace: Any                             # trace.TraceData or None
    tasks: int                             # committed in the window
    tasks_traced: int                      # committed in the traced part
    s_per_step: List[float]                # program counter, window steps
    sweeps: List[Dict[str, Any]]           # submitted in the window
    flops_per_task: float
    peaks: Dict[str, float]
    chips: int


def quantile(xs: Sequence[float], q: float) -> float:
    return float(np.quantile(np.asarray(xs, np.float64), q))


def end_to_end(rec: Recorder, pool: SweepPool, relations, window,
               setup_s: float) -> Dict[str, float]:
    t0, t1 = window
    inwin = [f for f in rec.finishes if t0 <= f["t"] <= t1]
    durs = []
    for f in inwin:
        r = relations[f["shard"]]
        row = int(f["rows"][0])
        durs.append(r["end_time"][row] - r["start_time"][row])
    out = {"setup_s": setup_s,
           "tasks_per_s": len(inwin) / (t1 - t0),
           "task_p95_ms": 1e3 * quantile(durs, 0.95) if durs else None}
    sw = [s for s in pool.sweeps if t0 <= s["handed"] <= t1 and "end" in s]
    out["sweep_p90_ms"] = (1e3 * quantile([s["end"] - s["handed"] for s in sw],
                                          0.90) if sw else None)
    return out


# --------------------------------------------------------------- one run
def run_cell(resolved: Dict[str, Any], seed: int, seconds: float,
             trace: bool, t_start: float, *,
             require_platform: Optional[str] = "tpu") -> Dict[str, Any]:
    """Run one cell once and return the result object (the last line)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    chips = int(resolved["cell"]["chips"])
    if require_platform is not None and d0.platform != require_platform:
        raise BenchError(f"no {require_platform.upper()} found: JAX sees "
                         f"{d0.platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks = load_peaks(d0.device_kind) if require_platform else {
        "bf16_flops_per_s": float("nan")}
    lowerings = _count_lowerings()

    cfg_file, traffic = resolved["config"], resolved["traffic"]
    ref = load_reference(cfg_file, resolved["base"])
    spans = Spans(annotate=trace)
    ex, rec, pool = build(cfg_file, traffic, seed, spans, ref)
    try:
        warm_up(ex, rec, pool, traffic, spans)
        jax.block_until_ready(ex.state)
        gc.collect()            # set-up's garbage is collected in set-up
        setup_s = time.perf_counter() - t_start
        n_low = lowerings[0]
        tick = ex.tick
        t0 = time.perf_counter()
        deadline = t0 + seconds
        traced = None
        tdir = None
        if trace:
            import tempfile
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            while time.perf_counter() < deadline - TRACE_SECONDS:
                tick()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while time.perf_counter() < deadline:
                    tick()
            traced = (ta, time.perf_counter())
            t1 = traced[1]
            jax.profiler.stop_trace()
        else:
            while time.perf_counter() < deadline:
                tick()
            t1 = time.perf_counter()
        window_compiles = lowerings[0] - n_low
        print(f"window_compilations {window_compiles}", file=sys.stderr)
        for line in slow_ticks(spans, (t0, t1)):
            print(line, file=sys.stderr)
        pool.wait(SWEEP_WAIT_S)
        mem = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                  for d in devices[:chips]) if d0.platform != "cpu" else 0
        relations = relations_of(ex)
        tasks = int(cfg_file["tasks"])
        seeds = derive_seeds(seed)
        checks: Dict[str, Dict[str, float]] = {}

        def put(vals, limits):
            for k, v in vals.items():
                checks[k] = {"value": v, "limit": limits.get(k)}
        put(check_commits(relations, rec, tasks, cfg_file, seeds["data"]),
            {"ids_lost_or_doubled": 0, "commits_doubled": 0,
             "provenance_mismatches": 0, "input_mismatches": 0})
        put(check_stamps(relations, rec, pool), {"time_stamp_mismatches": 0})
        if int(traffic["steer_every"]):
            put(check_sweeps(relations, pool, cfg_file),
                {"sweep_mismatches": 0, "sweeps_compared": 1})
        if ex.router is not None and cfg_file["analyst"] == "remote":
            put(check_replicas(ex),
                {"replica_mismatches": 0, "replicas_compared": 1})
        e2e = end_to_end(rec, pool, relations, (t0, t1), setup_s)
        wsteps = [i for i, f in enumerate(rec.finishes) if t0 <= f["t"] <= t1]
        s_per_step = [ex.history[i]["s_per_step"] for i in wsteps]
        sweeps_win = [s for s in pool.sweeps if t0 <= s["handed"] <= t1]
        failed = sum("error" in s for s in sweeps_win)
        attempted = len(wsteps) + len(sweeps_win)
        prog = program_readings(rec)
    finally:
        ex.close()
    ex.state = None
    del ex
    gc.collect()
    refr = reference_readings(cfg_file, ref, rec, relations, seed)
    put(train_numbers(prog, refr), cfg_file["limits"])
    trace_data = None
    if trace:
        import shutil
        import devtrace as trace_mod
        trace_data = trace_mod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    run = RunRecord(
        spans=spans, window=(t0, t1), traced=traced, trace=trace_data,
        tasks=len(wsteps),
        tasks_traced=(sum(traced[0] <= rec.finishes[i]["t"] <= traced[1]
                          for i in wsteps) if traced else 0),
        s_per_step=s_per_step, sweeps=sweeps_win,
        flops_per_task=float(ref.train_step_flops(cfg_file["payload"])),
        peaks=peaks, chips=chips)
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in resolved["per_layer"]:
            v = load_metric(m["name"], resolved["base"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in resolved["end_to_end"]:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = window_compiles == 0 and all(
        c["limit"] is not None and _passes(k, c) for k, c in checks.items())
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": mem}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": attempted, "failed": failed,
                              "metrics": metrics, "device": device}
    if trace:
        import devtrace as trace_mod
        device["busy_s"], device["window_s"] = trace_mod.busy_and_window(
            trace_data)
        result["breakdown"] = trace_mod.breakdown(trace_data)
    checks["window_compilations"] = {"value": window_compiles, "limit": 0}
    result["checks"] = checks
    return result


def check_lines(result: Dict[str, Any]) -> List[str]:
    """One line per number compared, with its limit and its verdict."""
    return [f"check {k} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if _passes(k, c) else 'FAIL'}"
            for k, c in result["checks"].items()]


_LOWERINGS: List[int] = []


def _count_lowerings() -> List[int]:
    """A one-element counter of the programs JAX lowers from now on (each
    new compilation, whether or not the persistent cache then has it)."""
    if not _LOWERINGS:
        from jax import monitoring
        _LOWERINGS.append(0)

        def listen(name, secs, **kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                _LOWERINGS[0] += 1
        monitoring.register_event_duration_secs_listener(listen)
    return _LOWERINGS


_AT_LEAST = {"sweeps_compared", "replicas_compared"}


def _passes(name: str, c: Dict[str, Any]) -> bool:
    v, lim = c["value"], c["limit"]
    if lim is None or v is None or (isinstance(v, float) and math.isnan(v)):
        return False
    return v >= lim if name in _AT_LEAST else v <= lim
