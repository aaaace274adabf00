"""GPT-3 1.3B single-chip fit recipe (BASELINE config 5, single-chip leg).

The recipe (VERDICT round-2 #2): bf16 params + bf16 optimizer moments
(`AdamW(multi_precision=False)`) + per-block dots-policy remat + fused
(sequence-chunked) head+CE + donated buffers.  Expected HBM at b1 s1024:
  params 2.6GB + moments 5.2GB -> 2.6GB (bf16) + grads 2.6GB (donated)
  + remat activations ~0.1GB  ==>  ~8GB, inside a 16GB v5e chip.

Two modes:
  --compile-only   AOT lower+compile and print XLA compile time and the
                   compiled memory analysis (works on the CPU backend;
                   bounds XLA time before chip time is spent).
  (default)        run `--steps` training steps and print tokens/s.

Usage:
  JAX_PLATFORMS=cpu python tools/exp/_exp_13b.py --compile-only \
      --batch 1 --seq 256          # CPU rehearsal (small seq)
  python tools/exp/_exp_13b.py --batch 1 --seq 1024 --steps 10   # on TPU
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

import numpy as np


def build(args):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPTModel
    from paddle_tpu.parallel.train_step import TrainStep

    paddle.seed(0)
    model = GPTModel.from_config(
        "gpt3-1.3b", dropout=args.dropout, fused_loss=True,
        scan_layers=args.scan,
        use_recompute=not args.no_remat,
        recompute_policy=(None if args.policy == "full" else args.policy)
        if not args.no_remat else None)
    model.to(dtype="bfloat16")
    opt = optimizer.AdamW(
        learning_rate=1e-4, weight_decay=0.01,
        parameters=model.parameters(),
        multi_precision=not args.bf16_moments)
    step = TrainStep(model, opt, loss_fn=None, donate=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 50304, (args.batch, args.seq + 1)).astype(np.int32)
    return step, ids[:, :-1], ids[:, 1:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--scan", action="store_true",
                    help="scan-over-layers form (one compiled block "
                         "body; see GPTScanBlocks)")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--policy", default="dots",
                    choices=["full", "dots", "nothing", "everything"])
    ap.add_argument("--bf16-moments", action="store_true", default=True)
    ap.add_argument("--f32-moments", dest="bf16_moments",
                    action="store_false")
    args = ap.parse_args()

    import jax
    step, x, y = build(args)
    out = {"config": vars(args), "backend": jax.default_backend()}

    if args.compile_only:
        t_lower, t_compile, compiled = step.aot_compile([x, y])
        out["lower_s"] = round(t_lower, 1)
        out["compile_s"] = round(t_compile, 1)
        try:
            ma = compiled.memory_analysis()
            out["memory_analysis"] = {
                "argument_size_gb": round(
                    ma.argument_size_in_bytes / 2 ** 30, 2),
                "output_size_gb": round(
                    ma.output_size_in_bytes / 2 ** 30, 2),
                "temp_size_gb": round(
                    ma.temp_size_in_bytes / 2 ** 30, 2),
                "peak_gb_est": round(
                    (max(ma.argument_size_in_bytes,
                         ma.output_size_in_bytes)
                     + ma.temp_size_in_bytes) / 2 ** 30, 2),
            }
        except Exception as e:  # backend without memory analysis
            out["memory_analysis"] = f"unavailable: {e!r}"
        print(json.dumps(out), flush=True)
        return

    t0 = time.perf_counter()
    loss = step.step([x, y])
    loss.numpy()
    out["first_step_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = step.step([x, y])
    lv = float(loss.numpy())
    dt = time.perf_counter() - t0
    out["loss"] = round(lv, 3)
    out["tokens_per_s"] = round(args.batch * args.seq * args.steps / dt, 1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
