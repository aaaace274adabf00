"""The program's side of ``lfm2-8b-a1b-serve`` (the LFM2-MoE family:
gated short-convolution layers whose state lives in the tails of the
paged cache's blocks beside the grouped-query layers' rows,
sigmoid-routed experts with no shared one): how the benchmark builds
the program's model, which leaves it seeds, and the work an ideal chip
must do for it.  The harness finds this file beside the configuration
(``"program": "lfm2_moe_program"``) and calls what ``gpt2_program.py``'s
docstring lists: ``build``, ``leaf_specs``, ``decode_least_seconds``,
``serve_least_seconds``, and ``gmm_least_seconds`` for the grouped
product's kernel.

Every count works from the configuration's sizes alone (``dims``: the
published ``config.json`` keys as run), so a CPU test can check it
against hand-worked numbers.  **Only necessary work is counted, whatever
implements it**: an expert's weights once a hit and two operations a
parameter a pair; of the cache the rows a query SEES in the attention
layers (``serving.attn_rows_seen`` / ``_chunk``); and of the state one
tail read and one written a conv layer for every position a decode lane
computes (``serving.conv_positions``: ``tail_bytes`` each way) and one
of each a layer and chunk program.  (A lane that is still prefilling
takes a discarded decode step in every tick beside the others; its rows,
pairs and tails are in the counts, as the program ran them.)
"""
from __future__ import annotations

from harness import common, weights
from harness.counts import dtype_bytes

_base = common.load_program({"program": "mla_moe_program"})
_ffn_leaves, _n = _base._ffn_leaves, _base._n
CONV = "conv"


def build(cfg, seed):
    """``Lfm2MoeModel`` at the configuration's sizes, holding the
    seeded leaves in the served dtype and nothing else: the parameters
    are declared under ``LazyGuard`` (no initial values), and the leaves
    are made and handed over a layer at a time, so that no second copy
    of the weights is alive."""
    from paddle_tpu import nn
    from paddle_tpu.models.lfm2_moe import Lfm2MoeModel
    dims, dtype = cfg["dims"], cfg["dtype"]
    with nn.LazyGuard():
        model = Lfm2MoeModel(dims)
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
    ``dims["seeded"]["conv_weight_scale"]`` for the convolutions' taps
    and ``["q_norm_scale"]`` for the query heads' norm gains (powers of
    two, so the product is exact in the served dtype and the reference,
    which scales the same leaves, sees the same numbers), 1 for every
    other leaf.  Why: the configuration's
    ``assumed.weights``."""
    seeded = dims.get("seeded", {})
    if name.endswith("conv.conv_weight"):
        return float(seeded.get("conv_weight_scale", 1.0))
    if name.endswith("attn.q_norm.weight"):
        return float(seeded.get("q_norm_scale", 1.0))
    return 1.0


def head_dim(dims):
    return dims["hidden_size"] // dims["num_attention_heads"]


def leaf_specs(dims):
    """[(name, shape, kind)] under the program's parameter names, in a
    fixed order; ``normal`` is std 0.02, ``gain`` 1 + normal (the
    harness's two kinds; ``leaf_scale`` is applied on top).  The
    router's selection bias is seeded too (it starts at zero in a fresh
    model), so that dropping it shows in the comparison.  The head is
    the embedding: no leaf of its own."""
    d, hd = dims["hidden_size"], head_dim(dims)
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    E, F = dims["num_experts"], dims["moe_intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), "normal")]
    for i, kind in enumerate(dims["layer_types"]):
        p = f"blocks.{i}."
        out += [(p + "operator_norm.weight", (d,), "gain")]
        if kind == CONV:
            out += [(p + "conv.in_proj.weight", (d, 3 * d), "normal"),
                    (p + "conv.conv_weight", (d, dims["conv_L_cache"]),
                     "normal"),
                    (p + "conv.out_proj.weight", (d, d), "normal")]
        else:
            out += [(p + "attn.q_proj.weight", (d, H * hd), "normal"),
                    (p + "attn.k_proj.weight", (d, K * hd), "normal"),
                    (p + "attn.v_proj.weight", (d, K * hd), "normal"),
                    (p + "attn.q_norm.weight", (hd,), "gain"),
                    (p + "attn.k_norm.weight", (hd,), "gain"),
                    (p + "attn.o_proj.weight", (H * hd, d), "normal")]
        out += [(p + "ffn_norm.weight", (d,), "gain")]
        if i < dims["num_dense_layers"]:
            out += _ffn_leaves(p + "ffn.", d, dims["intermediate_size"])
        else:
            out += [(p + "ffn.gate_weight", (d, E), "normal"),
                    (p + "ffn.gate_bias", (E,), "normal"),
                    (p + "ffn.experts_in", (E, d, 2 * F), "normal"),
                    (p + "ffn.experts_out", (E, F, d), "normal")]
    out += [("norm.weight", (d,), "gain")]
    return out


# -- the work an ideal chip must do ----------------------------------------

def routed_layers(dims):
    return dims["num_hidden_layers"] - dims["num_dense_layers"]


def layers_of(dims, kind):
    return sum(1 for k in dims["layer_types"] if k == kind)


def attention_layers(dims):
    return dims["num_hidden_layers"] - layers_of(dims, CONV)


def conv_params(dims):
    """W_in, W_out and the taps of one conv operator."""
    d = dims["hidden_size"]
    return 4 * d * d + d * dims["conv_L_cache"]


def attention_params(dims):
    """W_q, W_k, W_v, W_o and the two head norms of one layer."""
    d, hd = dims["hidden_size"], head_dim(dims)
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    return 2 * d * H * hd + 2 * d * K * hd + 2 * hd


def expert_params(dims):
    """One routed expert: W1, W3 and W2."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def expert_bytes(dims, dtype="bfloat16"):
    return expert_params(dims) * dtype_bytes(dtype)


def total_params(dims):
    return sum(_n(shape) for _, shape, _ in leaf_specs(dims))


def fixed_step_params(dims):
    """Parameters every decode step multiplies by whatever the routing:
    the conv operators' and the attention layers' matrices, the dense
    layers' feed-forward, the routers, and the tied head (the
    embedding's rows as the head's columns; looked up at the input,
    multiplied at the output).  Norm gains and taps are not matrices."""
    d = dims["hidden_size"]
    return (layers_of(dims, CONV) * 4 * d * d
            + attention_layers(dims) * (attention_params(dims)
                                        - 2 * head_dim(dims))
            + dims["num_dense_layers"] * 3 * d * dims["intermediate_size"]
            + routed_layers(dims) * d * dims["num_experts"]
            + d * dims["vocab_size"])


def row_bytes(dims, dtype="bfloat16"):
    """K and V of one cached position in ONE attention layer."""
    return (2 * dims["num_key_value_heads"] * head_dim(dims)
            * dtype_bytes(dtype))


def tail_bytes(dims, dtype="bfloat16"):
    """One conv layer's state: the tail of one block."""
    return ((dims["conv_L_cache"] - 1) * dims["hidden_size"]
            * dtype_bytes(dtype))


def block_bytes(dims, block_size, dtype="bfloat16"):
    """One block of the pool: its rows in the attention layers and its
    tails in the conv layers."""
    return (block_size * attention_layers(dims) * row_bytes(dims, dtype)
            + layers_of(dims, CONV) * tail_bytes(dims, dtype))


def attention_flops_per_pair(dims):
    """Operations for one (query, seen row) pair in one layer: scores
    and context over hd for every query head."""
    return 4 * dims["num_attention_heads"] * head_dim(dims)


def _expert_hits(dims, work, decode_only):
    """Expert weight sets the interval's programs had to read: the
    program's own count (``serving.moe_experts_hit``, summed over the
    decode and chunk programs' runs).  The decode program's share of it
    is what is left after every chunk run is taken to have hit every
    expert of every routed layer (a chunk of 256 tokens brings an expert
    32 pairs at the mean; a short one, a resent turn's hundred tokens,
    misses some, which makes this an undercount, the safe side)."""
    hits = work["counters"].get("serving.moe_experts_hit", 0)
    if decode_only:
        hits -= (work["counters"].get("serving.prefill_chunks", 0)
                 * routed_layers(dims) * dims["num_experts"])
    return max(hits, 0)


def _memory_seconds(cfg, peaks, work, decode_only):
    dims, dtype = cfg["dims"], cfg["dtype"]
    c = work["counters"]
    steps = work["tokens_emitted"] / float(work["num_slots"])
    rows = c.get("serving.attn_rows_seen", 0)
    # a tail read and a tail written for every position a decode lane
    # computes in a conv layer; a chunk program reads one and writes at
    # least one a layer
    tails = 2 * (c.get("serving.conv_positions", 0)
                 - layers_of(dims, CONV) * work["prefill_tokens"])
    if not decode_only:
        rows += c.get("serving.attn_rows_seen_chunk", 0)
        tails += 2 * layers_of(dims, CONV) * (
            c.get("serving.conv_starts_from_tail", 0)
            + c.get("serving.conv_starts_from_zero", 0))
    byts = (steps * fixed_step_params(dims) * dtype_bytes(dtype)
            + _expert_hits(dims, work, decode_only)
            * expert_bytes(dims, dtype)
            + rows * row_bytes(dims, dtype)
            + max(tails, 0) * tail_bytes(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def gmm_least_seconds(cfg, peaks, work):
    """Least time for the grouped expert products of a profiled
    interval (the megablox kernel alone, decode and chunk runs alike):
    every expert hit read once, against two operations for each of an
    expert's parameters and each pair; the larger side."""
    dims = cfg["dims"]
    c = work["counters"]
    t_mem = (c.get("serving.moe_experts_hit", 0)
             * expert_bytes(dims, cfg["dtype"]) / peaks["hbm_bytes_per_s"])
    t_flop = (2.0 * expert_params(dims)
              * c.get("serving.moe_routed_pairs", 0) / peaks["bf16_flops"])
    return max(t_mem, t_flop)


def decode_least_seconds(cfg, peaks, work):
    """The memory side of a profiled interval's decode steps:
    ``tokens_emitted / num_slots`` steps at least, each reading the
    fixed weights once; every expert the decode program hit, once a
    hit; for every live lane the rows its query sees in each attention
    layer and a tail each way in each conv layer."""
    return _memory_seconds(cfg, peaks, work, decode_only=True)


def serve_least_seconds(cfg, peaks, work):
    """Least time for a profiled interval of serving: the memory side
    (with the chunk programs' expert, row and tail traffic) against two
    operations a fixed parameter for every uncached prompt token and
    every emitted one, two a parameter of an expert for every pair, and
    attention's for every (decode query, seen row) pair and at least one
    query for every row a chunk's queries see.  The convolution itself
    is ``2 L`` operations a channel and position: nothing beside a
    product.  Returns (seconds, bound)."""
    dims = cfg["dims"]
    c = work["counters"]
    t_mem = _memory_seconds(cfg, peaks, work, decode_only=False)
    flops = (2.0 * fixed_step_params(dims)
             * (work["prefill_tokens"] + work["tokens_emitted"])
             + 2.0 * expert_params(dims)
             * c.get("serving.moe_routed_pairs", 0)
             + attention_flops_per_pair(dims)
             * (c.get("serving.attn_rows_seen", 0)
                + c.get("serving.attn_rows_seen_chunk", 0)))
    t_flop = flops / peaks["bf16_flops"]
    return ((t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute"))
