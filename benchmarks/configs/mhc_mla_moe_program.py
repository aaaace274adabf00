"""The program's side of ``xing4-29b-a4b-serve`` (the DeepSeek-V3 layer
family of ``mla_moe_program.py`` with a compressed query, YaRN
positions and a residual of ``hc_mult`` streams whose three mappings
have leaves of their own in every sub-layer): how the benchmark builds
the program's model, which leaves it seeds, and the work an ideal chip
must do for it.  The harness finds this file beside the configuration
(``"program": "mhc_mla_moe_program"``) and calls what
``gpt2_program.py``'s docstring lists.

What the two families count alike (an expert's three matrices, the
latent row, the attention's pairs, the grouped product's least time) is
``mla_moe_program.py``'s; what differs is counted here: two leading
dense layers, the low-rank query, the mappings' leaves.  Every count
works from ``dims`` alone; only necessary work is counted.
"""
from __future__ import annotations

from harness import common, weights
from harness.counts import dtype_bytes

_base = common.load_program({"program": "mla_moe_program"})
routed_layers = _base.routed_layers
expert_params = _base.expert_params
expert_bytes = _base.expert_bytes
row_bytes_per_position = _base.row_bytes_per_position
attention_flops_per_pair = _base.attention_flops_per_pair
gmm_least_seconds = _base.gmm_least_seconds


def build(cfg, seed):
    """``MLAMoEModel`` at the configuration's sizes holding the seeded
    leaves in the served dtype and nothing else (declared under
    ``LazyGuard``, filled a layer at a time: no second copy of the
    weights is alive)."""
    from paddle_tpu import nn
    from paddle_tpu.models.mla_moe import MLAMoEModel
    dims, dtype = cfg["dims"], cfg["dtype"]
    with nn.LazyGuard():
        model = MLAMoEModel(dims)
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


def mapping_leaves(prefix, dims):
    """The leaves of one sub-layer's three mappings: the stream norm's
    gain, ``phi`` (rows: pre n, post n, res n x n), the three scalars
    ``alpha`` and the biases ``beta``.  ``alpha`` is seeded as a gain
    (1 + normal): with ``phi`` normal at std 0.02 over n d inputs of
    unit mean square the mappings' raw values have a standard deviation
    of 0.02 sqrt(n d) (2.4 at 4 x 3,584), so H_res is neither the
    identity nor uniform (the configuration's ``assumed.weights``)."""
    n, d = dims["hc_mult"], dims["hidden_size"]
    return [(prefix + "norm.weight", (n * d,), "gain"),
            (prefix + "phi", (n * (n + 2), n * d), "normal"),
            (prefix + "alpha", (3,), "gain"),
            (prefix + "beta", (n * (n + 2),), "normal")]


def leaf_specs(dims):
    """[(name, shape, kind)] under the program's parameter names, in a
    fixed order; ``normal`` is std 0.02, ``gain`` 1 + normal.  The
    router's correction bias is seeded too (it starts at zero in a
    fresh model), so that dropping it shows in the comparison."""
    d, H = dims["hidden_size"], dims["num_attention_heads"]
    r, dr = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    rq = dims["q_lora_rank"]
    E, F = dims["n_routed_experts"], dims["moe_intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), "normal")]
    for i in range(dims["num_hidden_layers"]):
        p = f"blocks.{i}."
        out += [(p + "input_norm.weight", (d,), "gain"),
                (p + "attn.q_a_proj.weight", (d, rq), "normal"),
                (p + "attn.q_a_norm.weight", (rq,), "gain"),
                (p + "attn.q_b_proj.weight", (rq, H * (dn + dr)),
                 "normal"),
                (p + "attn.kv_a_proj.weight", (d, r + dr), "normal"),
                (p + "attn.kv_norm.weight", (r,), "gain"),
                (p + "attn.kv_b", (r, H * (dn + dv)), "normal"),
                (p + "attn.o_proj.weight", (H * dv, d), "normal"),
                (p + "post_norm.weight", (d,), "gain")]
        out += mapping_leaves(p + "attn_hc.", dims)
        out += mapping_leaves(p + "ffn_hc.", dims)
        if i < dims["first_k_dense_replace"]:
            out += _base._ffn_leaves(p + "ffn.", d,
                                     dims["intermediate_size"])
        else:
            out += [(p + "ffn.gate_weight", (d, E), "normal"),
                    (p + "ffn.gate_bias", (E,), "normal"),
                    (p + "ffn.experts_in", (E, d, 2 * F), "normal"),
                    (p + "ffn.experts_out", (E, F, d), "normal")]
            out += _base._ffn_leaves(p + "ffn.shared.", d,
                                     dims["n_shared_experts"] * F)
    out += [("norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


# -- the work an ideal chip must do ----------------------------------------

def attention_params(dims):
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer (the matrices;
    its two norms' gains, r_q + r numbers, are not)."""
    d, H = dims["hidden_size"], dims["num_attention_heads"]
    r, dr = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    rq = dims["q_lora_rank"]
    return (d * rq + rq * H * (dn + dr) + d * (r + dr)
            + r * H * (dn + dv) + H * dv * d)


def mapping_params(dims):
    """The leaves of one sub-layer's mappings, all of which a step
    reads."""
    return sum(_base._n(shape) for _, shape, _ in mapping_leaves("", dims))


def total_params(dims):
    return sum(_base._n(shape) for _, shape, _ in leaf_specs(dims))


def fixed_step_params(dims):
    """Parameters every decode step reads whatever the routing:
    attention of every layer, the dense layers' feed-forward, the
    shared experts, the routers, both sub-layers' mappings of every
    layer and the head.  The embedding is looked up, the other norms
    are not matrices."""
    d = dims["hidden_size"]
    routed = routed_layers(dims)
    return (dims["num_hidden_layers"]
            * (attention_params(dims) + 2 * mapping_params(dims))
            + dims["first_k_dense_replace"] * 3 * d
            * dims["intermediate_size"]
            + routed * (dims["n_shared_experts"] * expert_params(dims)
                        + d * dims["n_routed_experts"])
            + d * dims["vocab_size"])


def active_params(dims):
    """Parameters one token is multiplied by: the fixed part and its
    ``num_experts_per_tok`` experts in every routed layer."""
    return (fixed_step_params(dims) + routed_layers(dims)
            * dims["num_experts_per_tok"] * expert_params(dims))


def _memory_seconds(cfg, peaks, work, decode_only):
    dims, dtype = cfg["dims"], cfg["dtype"]
    steps = work["tokens_emitted"] / float(work["num_slots"])
    byts = (steps * fixed_step_params(dims) * dtype_bytes(dtype)
            + _base._expert_hits(dims, work, decode_only)
            * expert_bytes(dims, dtype)
            + work["live_positions"] * row_bytes_per_position(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def decode_least_seconds(cfg, peaks, work):
    """The memory side of a profiled interval's decode steps:
    ``tokens_emitted / num_slots`` steps at least, each reading the
    fixed weights once; every expert the decode program hit, once a
    hit; every emitted token the latent rows of its live cached
    positions."""
    return _memory_seconds(cfg, peaks, work, decode_only=True)


def serve_least_seconds(cfg, peaks, work):
    """Least time for a profiled interval of serving: the memory side
    (with the chunk programs' expert reads) against two operations per
    active parameter for every uncached prompt token and every emitted
    one, plus attention of the emitted tokens over their live
    positions.  Returns (seconds, bound)."""
    dims = cfg["dims"]
    t_mem = _memory_seconds(cfg, peaks, work, decode_only=False)
    flops = (2.0 * active_params(dims)
             * (work["prefill_tokens"] + work["tokens_emitted"])
             + dims["num_hidden_layers"] * attention_flops_per_pair(dims)
             * work["live_positions"])
    t_flop = flops / peaks["bf16_flops"]
    return ((t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute"))
