"""End-to-end LM training driver: train a ~100M-parameter qwen3-family model
for a few hundred steps on the synthetic pipeline, with checkpoints and the
fault-tolerance rig.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse

import jax
import numpy as np

from repro.common.compile_cache import place_compile_cache
from repro.configs.base import ModelConfig
from repro.data.pipeline import BatchSpec, make_source


def config_100m() -> ModelConfig:
    """~100M params: a scaled qwen3 family member."""
    return ModelConfig(
        name="qwen3-100m", family="dense",
        n_layers=8, d_model=512, vocab=32000,
        n_heads=8, n_kv_heads=4, head_dim=64, qk_norm=True,
        d_ff=1536, ffn_act="silu", dtype="float32",
    )


def main():
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = config_100m()
    print(f"model: {cfg.name} ~{cfg.n_params()/1e6:.0f}M params")

    from repro.train.step import TrainPlan, init_state, make_train_step
    from repro.checkpoint import ckpt as ckpt_lib
    from repro.distributed.fault_tolerance import StepWatchdog

    plan = TrainPlan(microbatches=2, lr=6e-4, warmup=30,
                     total_steps=args.steps, state_dtype="int8")
    params, opt = init_state(jax.random.PRNGKey(0), cfg, plan)
    step_fn = jax.jit(make_train_step(cfg, plan))
    src = make_source("synthetic", BatchSpec(8, 256, cfg.vocab), seed=0)
    wd = StepWatchdog()

    import time
    losses = []
    for step in range(args.steps):
        b = src.batch_at(step)
        t0 = time.perf_counter()
        params, opt, m = step_fn(params, opt,
                                 {"tokens": b["tokens"], "labels": b["labels"]})
        wd.record(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        if step % 25 == 0:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"p50 {wd.p50()*1e3:.0f}ms")
        if (step + 1) % 100 == 0:
            ckpt_lib.save(args.ckpt, step + 1, {"params": params, "opt": opt})
    print(f"done: loss {np.mean(losses[:20]):.3f} -> {np.mean(losses[-20:]):.3f}"
          f" (ckpts in {args.ckpt})")
    assert np.mean(losses[-20:]) < np.mean(losses[:20])


if __name__ == "__main__":
    main()
