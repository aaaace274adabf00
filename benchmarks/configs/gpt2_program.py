"""The program's side of the GPT-2 family's configurations (GPT-2, GPT-3
dense): how the benchmark builds the program's model, which leaves it
seeds, and the work an ideal chip must do for it.  The one file of the
benchmark that imports a model class.

A configuration names this file (``"program": "gpt2_program"``) and the
harness finds it beside the configuration, as it finds the reference.
What the harness calls:

``build(cfg, seed)``    the program's model, holding the seeded weights
``leaf_specs(dims)``    ``[(name, shape, kind)]`` under the program's
                        parameter names, in the order that fixes each
                        leaf's values (``harness/weights.py``)
``<name>(cfg, peaks, work)``   the least seconds an ideal chip needs
                        for the work of a profiled interval; a metric
                        file names the function (``serve_least_seconds``,
                        ``decode_least_seconds``, ``train_least_seconds``)
``train_flops_per_token(dims, seq_len)``   behind ``mfu.train``

Every count works from the configuration's sizes alone, so a CPU test
can check it against hand-worked numbers.  Only necessary work is
counted: a byte that is gathered and thrown away, or an operation that
is recomputed, is the program's cost and not the algorithm's.
"""
from __future__ import annotations

from harness import weights
from harness.counts import dtype_bytes


def build(cfg, seed):
    """``GPTModel`` at the configuration's sizes, holding the benchmark's
    seeded weights in the type it is served or trained in."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTModel
    dims = cfg["dims"]
    if dims["ffn_hidden_size"] != 4 * dims["hidden_size"]:
        raise ValueError("GPTModel's feed-forward is 4 x hidden")
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = GPTModel(num_layers=dims["num_layers"],
                     hidden_size=dims["hidden_size"],
                     num_heads=dims["num_heads"],
                     vocab_size=dims["vocab_size"],
                     max_position=dims["max_position"],
                     **cfg.get("model_options", {}))
    model.to(dtype=cfg["dtype"])
    return weights.fill_model(model, seed, leaf_specs(dims), cfg["dtype"])


def leaf_specs(dims):
    """[(name, shape, kind)] in a fixed order; ``kind`` is ``normal`` or
    ``gain`` (1 + normal).  Values follow GPT-2's initialisation and also
    fill what GPT-2 starts at zero or one (biases, layer-norm gains), so
    that a dropped bias or gain shows in the comparison."""
    d, f = dims["hidden_size"], dims["ffn_hidden_size"]
    out = [("embeddings.word_embeddings.weight",
            (dims["vocab_size"], d), "normal"),
           ("embeddings.position_embeddings.weight",
            (dims["max_position"], d), "normal")]
    for i in range(dims["num_layers"]):
        p = f"blocks.{i}."
        out += [(p + "ln1.weight", (d,), "gain"),
                (p + "ln1.bias", (d,), "normal"),
                (p + "attn.qkv_proj.weight", (d, 3 * d), "normal"),
                (p + "attn.qkv_proj.bias", (3 * d,), "normal"),
                (p + "attn.out_proj.weight", (d, d), "normal"),
                (p + "attn.out_proj.bias", (d,), "normal"),
                (p + "ln2.weight", (d,), "gain"),
                (p + "ln2.bias", (d,), "normal"),
                (p + "mlp.fc1.weight", (d, f), "normal"),
                (p + "mlp.fc1.bias", (f,), "normal"),
                (p + "mlp.fc2.weight", (f, d), "normal"),
                (p + "mlp.fc2.bias", (d,), "normal")]
    out += [("head.ln_f.weight", (d,), "gain"),
            ("head.ln_f.bias", (d,), "normal"),
            ("head.lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


# -- the work an ideal chip must do ----------------------------------------

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


def decode_least_seconds(cfg, peaks, work):
    """The memory side of a profiled interval of serving, which is all a
    decode step has: ``tokens_emitted`` tokens need at least
    ``tokens_emitted / num_slots`` decode steps, each reading the
    weights once; every emitted token reads the K and V of its live
    cached positions (their sum is ``live_positions``)."""
    dims, dtype = cfg["dims"], cfg["dtype"]
    steps = work["tokens_emitted"] / float(work["num_slots"])
    byts = (steps * step_weight_bytes(dims, dtype)
            + work["live_positions"] * kv_bytes_per_position(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def serve_least_seconds(cfg, peaks, work):
    """Least time the chip could take for a profiled interval of
    serving: the memory side above against two operations per
    multiplied parameter for every uncached prompt token and every
    emitted one.  ``work`` holds ``tokens_emitted``, ``live_positions``,
    ``prefill_tokens``, ``num_slots`` and ``counters`` (the program's
    counters' increase over the interval, unused here).  Returns
    (seconds, bound): the larger of the two sides and which it is."""
    t_mem = decode_least_seconds(cfg, peaks, work)
    flops = 2.0 * matmul_params(cfg["dims"]) * (work["prefill_tokens"]
                                                + work["tokens_emitted"])
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


def train_least_seconds(cfg, peaks, work):
    """Least time for the ``steps`` training steps of a profiled
    interval, each over ``batch`` x ``seq_len`` tokens: their operations
    at the chip's peak."""
    return work["steps"] * train_step_flops(
        cfg["dims"], work["batch"], work["seq_len"]) / peaks["bf16_flops"]
