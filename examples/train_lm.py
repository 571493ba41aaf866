"""End-to-end training driver: train a ~100M-param qwen2-style model for a
few hundred steps on the synthetic bigram corpus, with async checkpointing
and crash-resume.

  PYTHONPATH=src python examples/train_lm.py --steps 300
(defaults to a scaled-down config so it finishes on CPU; pass --d-model 768
--layers 12 for the true ~100M config on real hardware)
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.checkpoint import Checkpointer, latest_step, restore
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import init_params
from repro.training import (AdamWConfig, TrainState, TrainStepConfig,
                            adamw_init, build_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b", smoke=True),
        num_layers=args.layers, d_model=args.d_model,
        num_heads=max(4, args.d_model // 64), num_kv_heads=2,
        d_ff=args.d_model * 4, vocab_size=2048, q_chunk=64)
    n = cfg.num_params_estimate()
    print(f"model: {cfg.num_layers}L d={cfg.d_model} params≈{n/1e6:.1f}M")

    opt_cfg = AdamWConfig(lr_peak=3e-3, warmup_steps=20,
                          total_steps=args.steps)
    data = SyntheticLM(DataConfig(global_batch=args.batch, seq_len=args.seq,
                                  vocab_size=cfg.vocab_size))
    step_fn = jax.jit(build_train_step(cfg, opt_cfg, TrainStepConfig()),
                      donate_argnums=(0,))

    params = init_params(jax.random.key(0), cfg)
    state = TrainState.create(params, adamw_init(opt_cfg, params),
                              jax.random.key(0))
    start = 0
    ckpt = Checkpointer(args.ckpt_dir, keep_last_k=2)
    last = latest_step(args.ckpt_dir)
    if last is not None and last < args.steps:
        state = restore(args.ckpt_dir, last, jax.eval_shape(lambda: state))
        start = last
        print(f"resumed from checkpoint step {last}")

    t0 = time.time()
    for step in range(start, args.steps):
        state, metrics = step_fn(state, data.batch_at(step))
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step={step:4d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"({time.time()-t0:.1f}s)", flush=True)
        if (step + 1) % 100 == 0:
            ckpt.save_async(step + 1, state)
    ckpt.wait()
    ckpt.save_async(args.steps, state)
    ckpt.wait()
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
