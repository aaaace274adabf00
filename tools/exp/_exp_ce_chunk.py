"""Fused-CE chunk-size sweep on hardware (round-3 MFU push).

The chunked head+CE scan is ~18% of the GPT-2 345M step (round-3
breakdown).  Chunk size trades scan iterations (per-iteration
dW-accumulate traffic over the [H, V] head grad) against live logits
HBM ([B, chunk, V] f32).  Sweeps chunk at b8 s1024 and prints tokens/s
per setting; also the first data for the dynamic_slice scan rewrite
(chunks sliced from [B, S, H] in-body instead of a pre-transposed scan
input).

Usage: python tools/exp/_exp_ce_chunk.py [--steps 20]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--chunks", default="128,256,512,1024")
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPTModel
    from paddle_tpu.parallel.train_step import TrainStep

    on_tpu = jax.default_backend() != "cpu"
    batch, seq, cfg = (8, 1024, "gpt2-medium") if on_tpu else \
        (2, 128, "tiny")
    rng = np.random.RandomState(0)
    vocab = 50304 if cfg != "tiny" else 128
    ids = rng.randint(0, vocab, (batch, seq + 1)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]

    out = {"backend": jax.default_backend(), "batch": batch, "seq": seq}
    for chunk in [int(c) for c in args.chunks.split(",")]:
        paddle.seed(0)
        model = GPTModel.from_config(cfg, dropout=0.1, fused_loss=True,
                                     fused_loss_chunk=chunk)
        if on_tpu:
            model.to(dtype="bfloat16")
        opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                              parameters=model.parameters())
        step = TrainStep(model, opt, loss_fn=None)
        loss = step.step([x, y])
        loss.numpy()  # compile + sync
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss = step.step([x, y])
        loss.numpy()
        dt = time.perf_counter() - t0
        rate = round(batch * seq * args.steps / dt, 1)
        out[f"chunk{chunk}"] = {"tokens_per_s": rate,
                                "loss": round(float(loss.numpy()), 4)}
        print(json.dumps({f"chunk{chunk}": out[f"chunk{chunk}"]}),
              flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
