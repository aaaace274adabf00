"""The program's side of the latent-attention / routed-experts
configurations (the DeepSeek-V3 layer family: Kimi-VL-A3B's decoder):
how the benchmark builds the program's model, which leaves it seeds, and
the work an ideal chip must do for it.  The harness finds this file
beside the configuration (``"program": "mla_moe_program"``) and calls
what ``gpt2_program.py``'s docstring lists: ``build``, ``leaf_specs``,
``decode_least_seconds``, ``serve_least_seconds``, and
``gmm_least_seconds`` for the grouped product's kernel.

Every count works from the configuration's sizes alone (``dims``: the
published ``config.json`` keys as run), so a CPU test can check it
against hand-worked numbers.  Only necessary work is counted.
"""
from __future__ import annotations

from harness import weights
from harness.counts import dtype_bytes


def build(cfg, seed):
    """``MLAMoEModel`` at the configuration's sizes holding the seeded
    leaves in the served dtype and nothing else: the parameters are
    declared under ``LazyGuard`` (no initial values), and the leaves
    are made and handed over a layer at a time, so that no second copy
    of the weights is alive."""
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


def _ffn_leaves(prefix, d, width):
    return [(prefix + "gate_up_proj.weight", (d, 2 * width), "normal"),
            (prefix + "down_proj.weight", (width, d), "normal")]


def leaf_specs(dims):
    """[(name, shape, kind)] under the program's parameter names, in a
    fixed order; ``normal`` is std 0.02, ``gain`` 1 + normal.  The
    router's correction bias is seeded too (it starts at zero in a
    fresh model), so that dropping it shows in the comparison."""
    d, H = dims["hidden_size"], dims["num_attention_heads"]
    r, dr = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    E, F = dims["n_routed_experts"], dims["moe_intermediate_size"]
    out = [("embed", (dims["vocab_size"], d), "normal")]
    for i in range(dims["num_hidden_layers"]):
        p = f"blocks.{i}."
        out += [(p + "input_norm.weight", (d,), "gain"),
                (p + "attn.q_proj.weight", (d, H * (dn + dr)), "normal"),
                (p + "attn.kv_a_proj.weight", (d, r + dr), "normal"),
                (p + "attn.kv_norm.weight", (r,), "gain"),
                (p + "attn.kv_b", (r, H * (dn + dv)), "normal"),
                (p + "attn.o_proj.weight", (H * dv, d), "normal"),
                (p + "post_norm.weight", (d,), "gain")]
        if i < dims["first_k_dense_replace"]:
            out += _ffn_leaves(p + "ffn.", d, dims["intermediate_size"])
        else:
            out += [(p + "ffn.gate_weight", (d, E), "normal"),
                    (p + "ffn.gate_bias", (E,), "normal"),
                    (p + "ffn.experts_in", (E, d, 2 * F), "normal"),
                    (p + "ffn.experts_out", (E, F, d), "normal")]
            out += _ffn_leaves(p + "ffn.shared.", d,
                               dims["n_shared_experts"] * F)
    out += [("norm.weight", (d,), "gain"),
            ("lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


# -- the work an ideal chip must do ----------------------------------------

def routed_layers(dims):
    return dims["num_hidden_layers"] - dims["first_k_dense_replace"]


def attention_params(dims):
    """W_q, W_kva, W_kvb and W_o of one layer."""
    d, H = dims["hidden_size"], dims["num_attention_heads"]
    r, dr = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    dn, dv = dims["qk_nope_head_dim"], dims["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(dims):
    """One routed expert: W1, W3 and W2."""
    return 3 * dims["hidden_size"] * dims["moe_intermediate_size"]


def expert_bytes(dims, dtype="bfloat16"):
    return expert_params(dims) * dtype_bytes(dtype)


def total_params(dims):
    return sum(_n(shape) for _, shape, _ in leaf_specs(dims))


def _n(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def fixed_step_params(dims):
    """Parameters every decode step multiplies by whatever the routing:
    attention of every layer, the dense layers' feed-forward, the
    shared experts, the routers and the head.  The embedding is looked
    up, the norms are not matrices."""
    d = dims["hidden_size"]
    routed = routed_layers(dims)
    return (dims["num_hidden_layers"] * attention_params(dims)
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


def row_bytes_per_position(dims, dtype="bfloat16"):
    """The latent row [c; k_r] of one cached position over all
    layers."""
    return (dims["num_hidden_layers"]
            * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"])
            * dtype_bytes(dtype))


def attention_flops_per_pair(dims):
    """Operations for one (query, cached position) pair in one layer,
    in the cheaper (expanded) form: scores over d_n + d_r, context over
    d_v, for every head."""
    return 2 * dims["num_attention_heads"] * (
        dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
        + dims["v_head_dim"])


def _expert_hits(dims, work, decode_only):
    """Expert weight sets the interval's programs had to read: the
    program's own count (``serving.moe_experts_hit``, summed over the
    decode and chunk programs' runs).  The decode program's share of
    it is what is left after every chunk run is taken to have hit
    every expert of every routed layer (a chunk of 64 tokens does; a
    shorter one makes this an undercount, the safe side)."""
    hits = work["counters"].get("serving.moe_experts_hit", 0)
    if decode_only:
        hits -= (work["counters"].get("serving.prefill_chunks", 0)
                 * routed_layers(dims) * dims["n_routed_experts"])
    return max(hits, 0)


def _memory_seconds(cfg, peaks, work, decode_only):
    dims, dtype = cfg["dims"], cfg["dtype"]
    steps = work["tokens_emitted"] / float(work["num_slots"])
    byts = (steps * fixed_step_params(dims) * dtype_bytes(dtype)
            + _expert_hits(dims, work, decode_only)
            * expert_bytes(dims, dtype)
            + work["live_positions"] * row_bytes_per_position(dims, dtype))
    return byts / peaks["hbm_bytes_per_s"]


def gmm_least_seconds(cfg, peaks, work):
    """Least time for the grouped expert products of a profiled
    interval (the megablox kernel alone, decode and chunk runs alike):
    every expert hit read once, against two operations for each of an
    expert's parameters and each routed pair; the larger side."""
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
