"""The program's side of ``trinity-large-preview-serve`` (the AFMoE
family: sliding-window and full-attention layers in one model, gated
attention under four norms a block, sigmoid-routed experts of which
this chip holds a share): how the benchmark builds the program's model,
which leaves it seeds, and the work an ideal chip must do for it.  The
harness finds this file beside the configuration (``"program":
"afmoe_program"``) and calls what ``gpt2_program.py``'s docstring
lists: ``build``, ``leaf_specs``, ``decode_least_seconds``,
``serve_least_seconds``, and ``gmm_least_seconds`` for the grouped
product's kernel.

Every count works from the configuration's sizes alone (``dims``: the
published ``config.json`` keys as run, and under ``share`` which of the
router's experts are held), so a CPU test can check it against
hand-worked numbers.  **Only necessary work is counted, whatever
implements it**: an expert's weights once a hit HERE and two operations
a parameter for the pairs that fell on held experts only (the others
are another chip's), and of the cache the rows a query can SEE:
``min(p + 1, sliding_window)`` in a sliding layer, ``p + 1`` in a full
one.  A sum of positions cannot give that ``min``, so the rows come
from the program's own counters (``serving.attn_rows_seen`` for the
decode lanes, ``serving.attn_rows_seen_chunk`` for the chunk
program's queries: ``models/afmoe.py`` ``AFMOE_COUNTERS``), which count
what the queries see and not what a walk fetched.  (A lane that is
still prefilling takes a discarded decode step in every tick beside the
others, at its next chunk's first row; its rows are in the count.  In
this traffic that is about one lane-step in a hundred.)
"""
from __future__ import annotations

from harness import common, weights
from harness.counts import dtype_bytes

_base = common.load_program({"program": "mla_moe_program"})
_ffn_leaves, _n = _base._ffn_leaves, _base._n
SLIDING = "sliding_attention"


def build(cfg, seed):
    """``AfmoeModel`` at the configuration's sizes and share, holding
    the seeded leaves in the served dtype and nothing else: the
    parameters are declared under ``LazyGuard`` (no initial values), and
    the leaves are made and handed over a layer at a time, so that no
    second copy of the weights is alive."""
    from paddle_tpu import nn
    from paddle_tpu.models.afmoe import AfmoeModel
    dims, dtype = cfg["dims"], cfg["dtype"]
    with nn.LazyGuard():
        model = AfmoeModel(dims, **dims.get("share", {}))
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
            params[name].set_value(made.pop(name))
    return model


def router_width(dims):
    """Experts the router scores: the published count, of which
    ``dims["num_experts"]`` are held here."""
    return dims.get("share", {}).get("experts_of", dims["num_experts"])


def leaf_specs(dims):
    """[(name, shape, kind)] under the program's parameter names, in a
    fixed order; ``normal`` is std 0.02, ``gain`` 1 + normal.  The
    router's selection bias is seeded too (it starts at zero in a fresh
    model), so that dropping it shows in the comparison."""
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
                (p + "attn.gate_proj.weight", (d, H * hd), "normal"),
                (p + "post_attn_norm.weight", (d,), "gain"),
                (p + "pre_mlp_norm.weight", (d,), "gain"),
                (p + "post_mlp_norm.weight", (d,), "gain")]
        if i < dims["num_dense_layers"]:
            out += _ffn_leaves(p + "ffn.", d, dims["intermediate_size"])
        else:
            out += [(p + "ffn.gate_weight", (d, router_width(dims)),
                     "normal"),
                    (p + "ffn.gate_bias", (router_width(dims),), "normal"),
                    (p + "ffn.experts_in", (E, d, 2 * F), "normal"),
                    (p + "ffn.experts_out", (E, F, d), "normal")]
            out += _ffn_leaves(p + "ffn.shared.", d,
                               dims["num_shared_experts"] * F)
    out += [("norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


# -- the work an ideal chip must do ----------------------------------------

def routed_layers(dims):
    return dims["num_hidden_layers"] - dims["num_dense_layers"]


def layers_of(dims, kind):
    return sum(1 for k in dims["layer_types"] if k == kind)


def attention_params(dims):
    """W_q, W_k, W_v, W_o and the gate's W_g of one layer."""
    d, hd = dims["hidden_size"], dims["head_dim"]
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    return 3 * d * H * hd + 2 * d * K * hd


def expert_params(dims):
    """One routed expert: W1, W3 and W2."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def expert_bytes(dims, dtype="bfloat16"):
    return expert_params(dims) * dtype_bytes(dtype)


def total_params(dims):
    return sum(_n(shape) for _, shape, _ in leaf_specs(dims))


def fixed_step_params(dims):
    """Parameters every decode step multiplies by whatever the routing:
    attention of every layer, the dense layers' feed-forward, the
    shared experts, the routers (every column: the router is whole on
    every chip) and the head over this chip's slice of the vocabulary.
    The embedding is looked up, the norms are not matrices."""
    d = dims["hidden_size"]
    return (dims["num_hidden_layers"] * attention_params(dims)
            + dims["num_dense_layers"] * 3 * d * dims["intermediate_size"]
            + routed_layers(dims) * (
                dims["num_shared_experts"] * expert_params(dims)
                + d * router_width(dims))
            + d * dims["vocab_size"])


def row_bytes(dims, dtype="bfloat16"):
    """K and V of one cached position in ONE layer."""
    return (2 * dims["num_key_value_heads"] * dims["head_dim"]
            * dtype_bytes(dtype))


def rows_seen(dims, position):
    """Cached rows a decode query at ``position`` sees, summed over the
    layers, its own row among them: what ``serving.attn_rows_seen``
    adds up a live lane."""
    end = position + 1
    return (layers_of(dims, SLIDING) * min(end, dims["sliding_window"])
            + (dims["num_hidden_layers"] - layers_of(dims, SLIDING)) * end)


def attention_flops_per_pair(dims):
    """Operations for one (query, seen row) pair in one layer: scores
    and context over hd for every query head."""
    return 4 * dims["num_attention_heads"] * dims["head_dim"]


def _expert_hits(dims, work, decode_only):
    """Held expert weight sets the interval's programs had to read: the
    program's own count (``serving.moe_experts_hit``, of held experts
    only, summed over the decode and chunk programs' runs).  The decode
    program's share of it is what is left after every chunk run is
    taken to have hit every held expert of every routed layer (a chunk
    of 256 tokens brings a held expert 4 pairs at the mean and misses
    it one time in 55; a shorter one makes this an undercount, the safe
    side)."""
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
    if not decode_only:
        rows += c.get("serving.attn_rows_seen_chunk", 0)
    byts = (steps * fixed_step_params(dims) * dtype_bytes(dtype)
            + _expert_hits(dims, work, decode_only)
            * expert_bytes(dims, dtype)
            + rows * row_bytes(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def gmm_least_seconds(cfg, peaks, work):
    """Least time for the grouped expert products of a profiled
    interval (the megablox kernel alone, decode and chunk runs alike):
    every held expert hit read once, against two operations for each of
    an expert's parameters and each pair that fell on a held expert;
    the larger side."""
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
    fixed weights once; every held expert the decode program hit, once
    a hit; for every live lane the rows its query sees in each layer
    (``rows_seen``, by the program's counter)."""
    return _memory_seconds(cfg, peaks, work, decode_only=True)


def serve_least_seconds(cfg, peaks, work):
    """Least time for a profiled interval of serving: the memory side
    (with the chunk programs' expert and row reads) against two
    operations a fixed parameter for every uncached prompt token and
    every emitted one, two a parameter of a held expert for every pair
    computed here, and attention's for every (decode query, seen row)
    pair and at least one query for every row a chunk's queries see.
    Returns (seconds, bound)."""
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
