"""The program's side of the block-diffusion / routed-experts
configurations (the SDAR-MoE family: SDAR-30B-A3B-Chat): how the
benchmark builds the program's model, which leaves it seeds, and the
work an ideal chip must do for it.  The harness finds this file beside
the configuration (``"program": "sdar_moe_program"``) and calls what
``gpt2_program.py``'s docstring lists: ``build``, ``leaf_specs``,
``decode_least_seconds``, ``serve_least_seconds``, and
``gmm_least_seconds`` for the grouped product's kernel.

Every count works from the configuration's sizes alone (``dims``: the
published ``config.json`` keys as run, and under ``generation`` how a
block is made), so a CPU test can check it against hand-worked numbers.

**The counts are of necessary work, whatever implements it.**  A block
of B tokens needs T = ``denoising_steps`` passes of B rows: the pass
that writes a finished block's K/V can ride on the next block's first
pass (ROADMAP Queue 1), so it is not counted.  An emitted token so
stands for ``T / B`` passes of its lane, ``T`` rows through the
matrices, and ``T / B`` readings of its live cached positions.  The
program's commit pass is therefore overhead in ``roofline_share.decode``
and ``.serve``, and a change that folds it away moves those shares
without the yardstick going stale.
"""
from __future__ import annotations

from harness import weights
from harness.counts import dtype_bytes


def build(cfg, seed):
    """``SDARMoEModel`` at the configuration's sizes, generating as
    ``dims["generation"]`` says, holding the seeded leaves in the
    served dtype and nothing else: the parameters are declared under
    ``LazyGuard`` (no initial values), and the leaves are made and
    handed over a layer at a time, so that no second copy of the
    weights is alive."""
    from paddle_tpu import nn
    from paddle_tpu.models.sdar_moe import SDARMoEModel
    dims, dtype = cfg["dims"], cfg["dtype"]
    with nn.LazyGuard():
        model = SDARMoEModel(dims, **dims["generation"])
    model.to(dtype=dtype)
    params = dict(model.named_parameters())
    specs = leaf_specs(dims)
    if set(params) != {n for n, _, _ in specs}:
        raise RuntimeError(
            "the program's parameters and the benchmark's weights "
            f"differ: {sorted(set(params) ^ {n for n, _, _ in specs})[:6]}")
    groups = {}
    for name, _, _ in specs:
        head = name.split(".")
        groups.setdefault(".".join(head[:2]) if head[0] == "blocks"
                          else name, []).append(name)
    for names in groups.values():
        made = weights.make_weights(seed, specs, dtype,
                                    names=frozenset(names))
        for name in names:
            scale = leaf_scale(dims, name)
            leaf = made.pop(name)
            params[name].set_value(leaf if scale == 1.0 else leaf * scale)
    return model


def leaf_scale(dims, name):
    """What a drawn leaf is multiplied by before the model holds it:
    ``dims["seeded"]["q_norm_scale"]`` for the query heads' norm gains
    (a power of two, so the product is exact in the served dtype and
    the reference, which scales the same leaves, sees the same
    numbers), 1 for every other leaf.  Why: the configuration's
    ``assumed.weights``."""
    if name.endswith("attn.q_norm.weight"):
        return float(dims.get("seeded", {}).get("q_norm_scale", 1.0))
    return 1.0


def leaf_specs(dims):
    """[(name, shape, kind)] under the program's parameter names, in a
    fixed order; ``normal`` is std 0.02, ``gain`` 1 + normal (the
    harness's two kinds; ``leaf_scale`` is applied on top)."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    E, F = dims["num_experts"], dims["moe_intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), "normal")]
    for i in range(dims["num_hidden_layers"]):
        p = f"blocks.{i}."
        out += [(p + "input_norm.weight", (d,), "gain"),
                (p + "attn.q_proj.weight", (d, H * hd), "normal"),
                (p + "attn.k_proj.weight", (d, K * hd), "normal"),
                (p + "attn.v_proj.weight", (d, K * hd), "normal"),
                (p + "attn.q_norm.weight", (hd,), "gain"),
                (p + "attn.k_norm.weight", (hd,), "gain"),
                (p + "attn.o_proj.weight", (H * hd, d), "normal"),
                (p + "post_norm.weight", (d,), "gain"),
                (p + "ffn.gate_weight", (d, E), "normal"),
                (p + "ffn.experts_in", (E, d, 2 * F), "normal"),
                (p + "ffn.experts_out", (E, F, d), "normal")]
    out += [("norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


# -- the work an ideal chip must do ----------------------------------------

def _n(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def attention_params(dims):
    """W_q, W_k, W_v and W_o of one layer."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    return 2 * d * H * hd + 2 * d * K * hd


def expert_params(dims):
    """One routed expert: W1, W3 and W2."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def expert_bytes(dims, dtype="bfloat16"):
    return expert_params(dims) * dtype_bytes(dtype)


def layer_params(dims):
    """One layer whole: attention, router, every expert, the four
    norms' gains."""
    prefix = "blocks.0."
    return sum(_n(shape) for name, shape, _ in leaf_specs(dims)
               if name.startswith(prefix))


def total_params(dims):
    return sum(_n(shape) for _, shape, _ in leaf_specs(dims))


def fixed_step_params(dims):
    """Parameters every pass multiplies by whatever the routing:
    attention and router of every layer, and the head.  The embedding
    is looked up, the norms are not matrices."""
    d = dims["hidden_size"]
    return (dims["num_hidden_layers"]
            * (attention_params(dims) + d * dims["num_experts"])
            + d * dims["vocab_size"])


def active_params(dims):
    """Parameters one row is multiplied by: the fixed part and its
    ``num_experts_per_tok`` experts in every layer."""
    return (fixed_step_params(dims) + dims["num_hidden_layers"]
            * dims["num_experts_per_tok"] * expert_params(dims))


def row_bytes_per_position(dims, dtype="bfloat16"):
    """K and V of one cached position over all layers."""
    return (dims["num_hidden_layers"] * 2 * dims["num_key_value_heads"]
            * dims["head_dim"] * dtype_bytes(dtype))


def attention_flops_per_pair(dims):
    """Operations for one (query row, cached position) pair in one
    layer: scores and context over hd for every query head."""
    return 4 * dims["num_attention_heads"] * dims["head_dim"]


def passes_per_token(dims):
    """Necessary passes of a lane for each token it emits: T / B."""
    gen = dims["generation"]
    return gen["denoising_steps"] / float(gen["block_length"])


def _necessary_share(work):
    """Of the step program's lane-passes, the share that is necessary:
    the denoise passes (the commit can ride on the next block's first
    pass).  1 where the program counted none."""
    c = work["counters"]
    den = c.get("serving.denoise_passes", 0)
    both = den + c.get("serving.commit_passes", 0)
    return den / float(both) if both else 1.0


def _expert_hits(dims, work, decode_only):
    """Expert weight sets the interval's necessary passes had to read.
    The program's own count (``serving.moe_experts_hit``) sums the step
    and chunk programs' runs; every chunk run is taken to have hit
    every expert of every layer (a chunk of 64 tokens does), and what
    is left, the step program's, counts in the share of its passes
    that are necessary."""
    c = work["counters"]
    chunk = (c.get("serving.prefill_chunks", 0)
             * dims["num_hidden_layers"] * dims["num_experts"])
    hits = c.get("serving.moe_experts_hit", 0)
    step = max(hits - chunk, 0) * _necessary_share(work)
    return step if decode_only else step + min(chunk, hits)


def _memory_seconds(cfg, peaks, work, decode_only):
    dims, dtype = cfg["dims"], cfg["dtype"]
    per = passes_per_token(dims)
    steps = work["tokens_emitted"] * per / float(work["num_slots"])
    byts = (steps * fixed_step_params(dims) * dtype_bytes(dtype)
            + _expert_hits(dims, work, decode_only)
            * expert_bytes(dims, dtype)
            + work["live_positions"] * per
            * row_bytes_per_position(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def gmm_least_seconds(cfg, peaks, work):
    """Least time for the grouped expert products the kernel was given
    in a profiled interval (the megablox kernel alone, step and chunk
    runs alike, every pass): every expert hit read once, against two
    operations for each of an expert's parameters and each routed
    pair; the larger side."""
    dims = cfg["dims"]
    c = work["counters"]
    t_mem = (c.get("serving.moe_experts_hit", 0)
             * expert_bytes(dims, cfg["dtype"]) / peaks["hbm_bytes_per_s"])
    t_flop = (2.0 * expert_params(dims)
              * c.get("serving.moe_routed_pairs", 0) / peaks["bf16_flops"])
    return max(t_mem, t_flop)


def decode_least_seconds(cfg, peaks, work):
    """The memory side of a profiled interval's necessary passes:
    ``tokens_emitted x T / B / num_slots`` steps at least, each reading
    the fixed weights once; every expert the necessary passes hit, once
    a hit; every emitted token's live cached positions, ``T / B``
    times."""
    return _memory_seconds(cfg, peaks, work, decode_only=True)


def serve_least_seconds(cfg, peaks, work):
    """Least time for a profiled interval of serving: the memory side
    (with the chunk programs' expert reads) against two operations per
    active parameter for every uncached prompt token and for the T
    rows of every emitted one, plus those rows' attention over their
    live positions.  Returns (seconds, bound)."""
    dims = cfg["dims"]
    T = dims["generation"]["denoising_steps"]
    t_mem = _memory_seconds(cfg, peaks, work, decode_only=False)
    flops = (2.0 * active_params(dims)
             * (work["prefill_tokens"] + T * work["tokens_emitted"])
             + dims["num_hidden_layers"] * attention_flops_per_pair(dims)
             * T * work["live_positions"])
    t_flop = flops / peaks["bf16_flops"]
    return ((t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute"))
