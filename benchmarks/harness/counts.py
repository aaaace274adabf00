"""The table of peaks, and the work an ideal chip must do.

Every function here works from a configuration's sizes alone, so a CPU
test can check it against hand-worked numbers.  Only necessary work is
counted: a byte that is gathered and thrown away, or an operation that
is recomputed, is the program's cost and not the algorithm's.
"""
from __future__ import annotations

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    """The peaks of ``device_kind``; an unknown device is an error, never
    a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            "row to benchmarks/harness/counts.py PEAKS with its source"
        ) from None


_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def dtype_bytes(name):
    return _BYTES[name]


def block_matrix_params(dims):
    """Parameters of one block that sit in matrix multiplications: the
    fused QKV, the output projection and the two feed-forward
    matrices."""
    d, f = dims["hidden_size"], dims["ffn_hidden_size"]
    return d * 3 * d + d * d + d * f + f * d


def matmul_params(dims):
    """Parameters every token is multiplied by: the blocks' matrices and
    the LM head.  The embedding tables are looked up, not multiplied."""
    return (dims["num_layers"] * block_matrix_params(dims)
            + dims["hidden_size"] * dims["vocab_size"])


def step_weight_bytes(dims, dtype="bfloat16"):
    """Bytes of weights one decode step must read once: the blocks
    (matrices, biases, layer norms), the final norm and the LM head."""
    d, f = dims["hidden_size"], dims["ffn_hidden_size"]
    per_block = block_matrix_params(dims) + (3 * d + d + f + d) + 4 * d
    total = (dims["num_layers"] * per_block + 2 * d
             + d * dims["vocab_size"])
    return total * dtype_bytes(dtype)


def kv_bytes_per_position(dims, dtype="bfloat16"):
    """K and V of one cached position over all layers."""
    return (2 * dims["num_layers"] * dims["hidden_size"]
            * dtype_bytes(dtype))


def serve_least_seconds(dims, peaks, *, tokens_emitted, live_positions,
                        prefill_tokens, num_slots, dtype="bfloat16"):
    """Least time the chip could take for a window of serving.

    ``tokens_emitted`` tokens need at least ``tokens_emitted / num_slots``
    decode steps, each reading the weights once; every emitted token
    reads the K and V of its ``live`` cached positions (their sum is
    ``live_positions``); every uncached prompt token costs two
    operations per multiplied parameter.  Returns (seconds, bound):
    the larger of the two sides and which one it is."""
    steps = tokens_emitted / float(num_slots)
    byts = (steps * step_weight_bytes(dims, dtype)
            + live_positions * kv_bytes_per_position(dims, dtype))
    flops = 2.0 * matmul_params(dims) * (prefill_tokens + tokens_emitted)
    t_mem = byts / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["bf16_flops"]
    return ((t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute"))


def train_flops_per_token(dims, seq_len):
    """Forward and backward: 6 per multiplied parameter, plus attention's
    two sequence-long products at 12 x layers x width x sequence (the
    convention of PaLM's model-FLOPs utilisation: the full square, no
    recomputation)."""
    return (6 * matmul_params(dims)
            + 12 * dims["num_layers"] * dims["hidden_size"] * seq_len)


def train_step_flops(dims, batch, seq_len):
    return train_flops_per_token(dims, seq_len) * batch * seq_len
