"""Packed vs padded GPT throughput at realistic document skew (TPU).

Compares real-token throughput of (a) bucketed padded-dense batches vs
(b) token-budget packed batches with segment-id flash masking, on the
BASELINE round-3 lognormal corpus. The packed path should win by
roughly the padding-waste ratio (~17% at this skew) at long budgets
where flash engages.

Usage: python tools/exp/_exp_packed.py [--budget 4096] [--steps 12]
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=4096)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--docs", type=int, default=2048)
    # --leg runs one leg per process; --ladder pow2 needs 8 compiles
    # instead of the x1.5 ladder's 13
    ap.add_argument("--leg", choices=("both", "packed", "padded"),
                    default="both")
    ap.add_argument("--ladder", choices=("x15", "pow2"), default="x15")
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.io.bucketing import (POW2_BUCKETS,
                                         TokenBudgetBatchSampler,
                                         bucket_for, DEFAULT_BUCKETS)
    from paddle_tpu.models import GPTModel
    from paddle_tpu.parallel.train_step import TrainStep
    from _exp_ragged import make_corpus

    on_tpu = jax.default_backend() != "cpu"
    cfg = "gpt2-medium" if on_tpu else "tiny"
    budget = args.budget if on_tpu else 128
    docs, lengths = make_corpus(args.docs, max_len=budget)
    out = {"backend": jax.default_backend(), "budget": budget}

    MAX_ROWS = 64          # docs per packed row (doc_lens width)
    ROWS_PER_STEP = 8      # packed rows per step == padded batch rows:
    #                        both legs then move ~8 x budget tokens/step,
    #                        isolating packing from batch-size/MFU effects

    class PackedGPT(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.gpt = GPTModel.from_config(
                cfg, dropout=0.1, max_position=budget, fused_loss=True,
                # 8 rows x budget-4096 = 32k tokens/step: activations
                # (24 x 256MB MLP intermediates alone) exceed HBM
                # without remat — same recipe any long-seq run uses
                use_recompute=budget >= 2048)

        def forward(self, ids, doc_lens, labels):
            return self.gpt(ids, labels=labels, doc_lens=doc_lens)

    def run_packed():
        paddle.seed(0)
        model = PackedGPT()
        if on_tpu:
            model.to(dtype="bfloat16")
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        step = TrainStep(model, opt, loss_fn=None)

        class DS:
            def __getitem__(self, i):
                return (docs[i],)

            def __len__(self):
                return len(docs)

        sampler = TokenBudgetBatchSampler(
            DS(), token_budget=budget, max_batch_size=MAX_ROWS,
            length_fn=lambda i: int(lengths[i]), shuffle=True)
        rows = list(sampler)
        feeds = []
        for s0 in range(0, len(rows) - ROWS_PER_STEP + 1,
                        ROWS_PER_STEP):
            ids = np.zeros((ROWS_PER_STEP, budget), np.int32)
            dl = np.zeros((ROWS_PER_STEP, MAX_ROWS), np.int32)
            real = 0
            for r, b in enumerate(rows[s0:s0 + ROWS_PER_STEP]):
                off = 0
                for j, i in enumerate(b):
                    d = docs[i][:int(lengths[i])]  # corpus stores len+1
                    ids[r, off:off + len(d)] = d
                    dl[r, j] = len(d)
                    off += len(d)
                real += off
            labels = np.concatenate(
                [ids[:, 1:], np.zeros((ROWS_PER_STEP, 1), np.int32)],
                axis=1).astype(np.int64)
            feeds.append((ids, dl, labels, real))
            if len(feeds) >= args.steps + 1:
                break
        step.step(list(feeds[0][:3])).numpy()  # compile + SYNC
        t0 = time.perf_counter()
        real = 0
        for f in feeds[1:args.steps + 1]:
            loss = step.step(list(f[:3]))
            real += f[3]
        loss.numpy()
        dt = time.perf_counter() - t0
        return round(real / dt, 1)

    def run_padded():
        paddle.seed(0)
        model = GPTModel.from_config(cfg, dropout=0.1, fused_loss=True,
                                     max_position=budget,
                                     use_recompute=budget >= 2048)
        if on_tpu:
            model.to(dtype="bfloat16")
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        step = TrainStep(model, opt, loss_fn=None)
        base = POW2_BUCKETS if args.ladder == "pow2" else DEFAULT_BUCKETS
        ladder = tuple(b for b in base if b <= budget)
        if budget not in ladder:
            ladder = ladder + (budget,)
        # SAME corpus, SAME shuffle-everything sampling as the packed
        # leg (sorting would benchmark only the tail and hide the
        # population's padding waste)
        rs = np.random.RandomState(0)
        order = rs.permutation(len(docs))
        batches = []
        for s0 in range(0, len(order) - ROWS_PER_STEP + 1,
                        ROWS_PER_STEP):
            idx = order[s0:s0 + ROWS_PER_STEP]
            L = bucket_for(int(max(lengths[i] for i in idx)), ladder)
            x = np.zeros((ROWS_PER_STEP, L), np.int32)
            y = np.zeros((ROWS_PER_STEP, L), np.int64)
            real = 0
            for r, i in enumerate(idx):
                d = docs[i]
                x[r, :len(d) - 1] = d[:-1]
                y[r, :len(d) - 1] = d[1:]
                real += len(d) - 1
            batches.append((x, y, real))
        # pre-compile EVERY bucket shape outside the timed window (a
        # 20-40s TPU compile inside it would deflate the denominator)
        seen = set()
        # only the TIMED batches' buckets need pre-compiling (the
        # whole corpus's ladder is 13 compiles)
        for x, y, _ in batches[:args.steps]:
            if x.shape[1] not in seen:
                seen.add(x.shape[1])
                print(json.dumps({"padded_compile_L": x.shape[1]}),
                      file=sys.stderr, flush=True)
                step.step([x, y]).numpy()
        t0 = time.perf_counter()
        real = 0
        for x, y, r in batches[:args.steps]:
            loss = step.step([x, y])
            real += r
        loss.numpy()
        dt = time.perf_counter() - t0
        return round(real / dt, 1)

    # flush per leg: a device crash in one leg must not lose the other
    # (observed: TPU worker fault in the padded leg after packed passed)
    if args.leg in ("both", "packed"):
        out["packed_real_tokens_per_s"] = run_packed()
        print(json.dumps({"packed_real_tokens_per_s":
                          out["packed_real_tokens_per_s"]}), flush=True)
    if args.leg in ("both", "padded"):
        out["padded_real_tokens_per_s"] = run_padded()
        print(json.dumps({"padded_real_tokens_per_s":
                          out["padded_real_tokens_per_s"]}), flush=True)
    if args.leg == "both":
        out["packed_vs_padded"] = round(
            out["packed_real_tokens_per_s"]
            / max(out["padded_real_tokens_per_s"], 1e-9), 3)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
