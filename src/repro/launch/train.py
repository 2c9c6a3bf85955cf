"""Training entrypoint: WQ-driven trainer for any --arch.

Runs the SchalaDB ``TrainExecutor`` on the default device: training-step
tasks are claimed from the partitioned work queue, executed by the jitted
train step, and committed back with loss/grad-norm provenance. The whole
train state lives on one device (no mesh). ``--smoke`` swaps in the reduced
config for CPU runs; the default batch (8 x 128 tokens) is the executor's and
fits one TPU v5e at the full qwen2-0.5b config.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 8
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.train \
      --arch qwen2-0.5b --smoke --steps 50
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.data.pipeline import DataConfig
from repro.runtime.executor import TrainExecutor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    ex = TrainExecutor(cfg, num_workers=args.workers, base_lr=args.lr,
                       checkpointer=ck, checkpoint_every=50,
                       data_cfg=DataConfig(vocab_size=cfg.vocab_size,
                                           seq_len=args.seq_len,
                                           batch_size=args.batch))
    if args.resume and ck and ck.latest_step() is not None:
        step, state, wq = ck.restore(jax.device_get(ex.state))
        ex.state, ex.step = state, step
        if wq is not None:
            ex.wq = wq
        print(f"resumed from step {step}")
    ex.submit_steps(args.steps)
    hist = ex.run()
    if hist:
        print(f"trained {len(hist)} steps on {jax.devices()[0].device_kind}; "
              f"loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    if ck:
        ck.save(ex.step, ex.state, ex.wq)
        ck.wait()
    ex.close()


if __name__ == "__main__":
    main()
