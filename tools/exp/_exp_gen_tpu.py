"""Compiled-generation throughput on hardware (VERDICT round-2 #8).

Measures GPT-2 345M prefill tokens/s and decode tokens/s at b1 and b8
through `GPTModel.generate(compiled=True)` (one jitted donated-buffer
decode step), plus an eager-vs-compiled greedy token-parity assert on a
small config.  Round 2 recorded 13-22x eager on the CPU backend only;
this records the TPU numbers.

Usage: python tools/exp/_exp_gen_tpu.py  [--config gpt2-medium]
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


def measure(model, batch, prompt_len, new_tokens, vocab, mode=True):
    """mode=True: per-token jitted step.  mode="fused": whole decode =
    one lax.scan jit (one dispatch, one sync — the remote-device mode)."""
    import paddle_tpu as paddle
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, vocab, (batch, prompt_len)).astype(np.int32))
    # warmup compiles prefill + decode step/scan for BOTH measured token
    # counts (fused: the scan length is part of the program)
    model.generate(ids, max_new_tokens=1, compiled=mode)
    model.generate(ids, max_new_tokens=new_tokens, compiled=mode)
    # prefill: a generate that decodes ONE token — dominated by the
    # prompt pass at these lengths
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=1, compiled=mode).numpy()
    t_prefill = time.perf_counter() - t0
    # decode: long continuation minus the measured 1-token call — both
    # share the same prefill program, so the difference is pure decode
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new_tokens, compiled=mode)
    np.asarray(out.numpy())
    t_total = time.perf_counter() - t0
    # under timing noise the 1-token call can measure SLOWER than
    # the full call — the subtraction is then meaningless: report null
    # and let end_to_end_s (the robust number) speak
    t_decode = t_total - t_prefill
    return {
        "batch": batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_tokens_per_s": round(batch * prompt_len / t_prefill, 1),
        "decode_tokens_per_s": round(
            batch * (new_tokens - 1) / t_decode, 1)
        if t_decode > 1e-3 else None,
        "end_to_end_s": round(t_total, 3),
        "new_tokens_per_s_e2e": round(
            batch * new_tokens / t_total, 1),
    }


def parity_check():
    """Greedy eager == compiled token-for-token on a small config."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTModel
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (2, 8)).astype(np.int32))
    eager = m.generate(ids, max_new_tokens=12, compiled=False).numpy()
    comp = m.generate(ids, max_new_tokens=12, compiled=True).numpy()
    return bool(np.array_equal(eager, comp))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2-medium")
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=128)
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTModel, GPT_CONFIGS

    out = {"backend": jax.default_backend(), "config": args.config,
           "greedy_parity": parity_check()}
    paddle.seed(0)
    model = GPTModel.from_config(args.config, dropout=0.0)
    if jax.default_backend() != "cpu":
        model.to(dtype="bfloat16")
    model.eval()
    vocab = GPT_CONFIGS[args.config]["vocab_size"]
    for batch in (1, 8):
        out[f"b{batch}"] = measure(model, batch, args.prompt_len,
                                   args.new_tokens, vocab)
        print(json.dumps({f"b{batch}": out[f"b{batch}"]}), flush=True)
        out[f"b{batch}_fused"] = measure(model, batch, args.prompt_len,
                                         args.new_tokens, vocab,
                                         mode="fused")
        print(json.dumps({f"b{batch}_fused": out[f"b{batch}_fused"]}),
              flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
