"""Plain reference for the latent-attention / routed-experts decoder
with a multi-stream residual (Xing4.0-29B-A4B: the DeepSeek-V3 layer
family with a compressed query, YaRN positions and manifold-constrained
hyper-connections): the forward pass in straightforward ``jax.numpy``,
float32 with matrix products at ``highest`` precision.  Expanded
attention a block of queries at a time, a loop over experts in which
every expert sees every row, no cache, no kernels; it imports nothing
of the program.

Architecture as the published ``config.json`` declares it
(``model_type`` ``xing4_0``), with the departures the configuration's
file lists under ``assumed``:

* attention: ``q = RMSNorm(h W_qa) W_qb`` split per head into a
  no-position part (``qk_nope_head_dim``) and a rotary part
  (``qk_rope_head_dim``); ``h W_kva`` split into the latent
  (``kv_lora_rank``), which is RMS-normed, and ONE rotary key shared by
  all heads; the latent expanded by ``W_kvb`` into each head's
  no-position key and value; scores ``(q_n . k_n + q_r . k_r) *
  softmax_scale``, causal softmax;
* positions (``DeepseekV3YarnRotaryEmbedding``): ``f_i = theta^(-2i /
  d_r)``; ``corr(b) = d_r ln(L0 / (2 pi b)) / (2 ln theta)``; ``low =
  floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, clipped to
  ``[0, d_r - 1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq_i = f_i (1 - ramp_i) + f_i / factor ramp_i``; cos and sin
  times ``m(factor, mscale) / m(factor, mscale_all_dim)`` with ``m(s,
  a) = 0.1 a ln s + 1``; ``softmax_scale = (d_n + d_r)^(-1/2) m(factor,
  mscale_all_dim)^2``.  Rotary pairs: dimension i with i + d_r/2 in
  stored order (the published code de-interleaves first: a fixed
  permutation of columns);
* the residual (``hc_mult`` n streams; manifold-constrained
  hyper-connections, arXiv:2512.24880): ``X_0`` every stream the
  embedding; around each sub-layer F with its own leaves ``x' =
  RMSNorm(vec(X))`` over all n d numbers (epsilon ``rms_norm_eps``),
  ``[Hpre~; Hpost~; Hres~] = a * (Phi x') + b``, ``H_pre =
  sigmoid(Hpre~)``, ``H_post = 2 sigmoid(Hpost~)``, ``H_res`` =
  ``exp(clip(Hres~, clamp_min, clamp_max))`` with ``hc_sinkhorn_iters``
  times its columns and then its rows divided by their sums +
  ``hc_eps``; ``u = H_pre X``; ``X <- H_res X + H_post^T F(u)``; the
  streams' sum goes to the final norm.  The mappings are float32 in
  every ``precision``, as the router's scores are;
* the first ``first_k_dense_replace`` layers: ``(silu(h W1) * (h W3))
  W2``; the others: ``s = sigmoid(h W_g)`` in float32, the experts of a
  token are the top ``num_experts_per_tok`` of ``s + b``, their weights
  ``routed_scaling_factor * s_e / sum_selected s``, plus the shared
  expert on every token; gate | up of a feed-forward sit side by side
  in one leaf.

``precision``: ``"highest"`` is the reference; ``"bf16"`` rounds both
operands of every matrix product to bfloat16, ``"fp8"`` to float8_e4m3
under a per-tensor scale.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512        # queries a step of the attention loop
HEAD_BLOCK = 512     # positions a step of the head loop
HIGHEST = jax.lax.Precision.HIGHEST


def _divisor(n, cap):
    """The largest block size up to ``cap`` that divides ``n``."""
    return next(b for b in range(min(cap, n), 0, -1) if n % b == 0)


def _round(a, precision):
    if precision == "fp8":
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return a


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(dims):
    """(inv_freq float32 [d_r / 2], the factor on cos and sin, the
    softmax scale) of the configuration's positions."""
    d, theta = dims["qk_rope_head_dim"], float(dims["rope_theta"])
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scale = 1.0 / math.sqrt(dims["qk_nope_head_dim"] + d)
    sc = dims.get("rope_scaling")
    if sc is None:
        return freq.astype(np.float32), 1.0, scale
    L0 = sc["original_max_position_embeddings"]

    def corr(turns):
        return d * math.log(L0 / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv = freq * (1 - ramp) + freq / sc["factor"] * ramp
    on_cos = (_mscale(sc["factor"], sc.get("mscale", 1))
              / _mscale(sc["factor"], sc.get("mscale_all_dim", 0)))
    if sc.get("mscale_all_dim"):
        scale *= _mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), on_cos, scale


def _rope(x, inv_freq, on_cos):
    """x [T, ..., d] at positions 0..T-1."""
    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * on_cos, jnp.sin(ang) * on_cos
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, w_in, w_out, precision):
    a = _mm(h, w_in, precision)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(a[:, :f]) * a[:, f:], w_out, precision)


def attention(w, h, dims, precision):
    """h [T, D] of one sequence -> [T, D]."""
    T = h.shape[0]
    H, r = dims["num_attention_heads"], dims["kv_lora_rank"]
    dn, dr = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    dv, eps = dims["v_head_dim"], dims["rms_norm_eps"]
    inv_freq, on_cos, scale = rotary(dims)
    q = _mm(_rms(_mm(h, w["attn.q_a_proj.weight"], precision),
                 w["attn.q_a_norm.weight"], eps),
            w["attn.q_b_proj.weight"], precision).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], inv_freq, on_cos)
    ckr = _mm(h, w["attn.kv_a_proj.weight"], precision)
    c = _rms(ckr[:, :r], w["attn.kv_norm.weight"], eps)
    k_r = _rope(ckr[:, r:], inv_freq, on_cos)
    kv = _mm(c, w["attn.kv_b"], precision).reshape(T, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    qb = _divisor(T, Q_BLOCK)

    def some_queries(i):
        qn = jax.lax.dynamic_slice_in_dim(q_n, i * qb, qb)
        qr = jax.lax.dynamic_slice_in_dim(q_r, i * qb, qb)
        s = (jnp.einsum("qhd,khd->hqk", _round(qn, precision),
                        _round(k_n, precision), precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", _round(qr, precision),
                          _round(k_r, precision),
                          precision=HIGHEST)) * scale
        at = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(T)[None, None, :] <= at[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqk,khd->qhd", _round(p, precision),
                          _round(v, precision), precision=HIGHEST)
    o = jax.lax.map(some_queries, jnp.arange(T // qb)).reshape(T, H * dv)
    return _mm(o, w["attn.o_proj.weight"], precision)


def routed(w, h, dims, precision):
    """The routed layer's feed-forward over h [T, D]: every expert over
    every row, weighted by the gate (0 where not selected), plus the
    shared expert."""
    k, E = dims["num_experts_per_tok"], dims["n_routed_experts"]
    s = jax.nn.sigmoid(jnp.matmul(h, w["ffn.gate_weight"],
                                  precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + w["ffn.gate_bias"][None, :], k)
    picked = jnp.take_along_axis(s, chosen, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        if dims.get("norm_topk_prob", True) and k > 1 else picked
    gate = jnp.zeros_like(s).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(
        picked * dims["routed_scaling_factor"])           # [T, E]

    def one_expert(y, e):
        out = _swiglu(h, w["ffn.experts_in"][e], w["ffn.experts_out"][e],
                      precision)
        return y + gate[:, e][:, None] * out, None
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(E))
    return y + _swiglu(h, w["ffn.shared.gate_up_proj.weight"],
                       w["ffn.shared.down_proj.weight"], precision)


def mappings(w, pre, X, dims):
    """The three mappings of the sub-layer whose leaves start with
    ``pre`` for the streams X [T, n, d]: (H_pre [T, n], H_post [T, n],
    H_res [T, n, n]), float32 at ``highest`` whatever the run's
    precision."""
    T, n, d = X.shape
    xn = _rms(X.reshape(T, n * d), w[pre + "norm.weight"],
              dims["rms_norm_eps"])
    raw = jnp.matmul(xn, w[pre + "phi"].T, precision=HIGHEST)
    a, b = w[pre + "alpha"], w[pre + "beta"]
    h_pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        (a[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(T, n, n),
        dims["mhc_h_res_clamp_min"], dims["mhc_h_res_clamp_max"]))
    for _ in range(dims["hc_sinkhorn_iters"]):
        m = m / (m.sum(1, keepdims=True) + dims["hc_eps"])    # columns
        m = m / (m.sum(2, keepdims=True) + dims["hc_eps"])    # rows
    return h_pre, h_post, m


def residual(w, pre, X, dims, sublayer):
    """``X <- H_res X + H_post^T F(H_pre X)`` for X [T, n, d]."""
    h_pre, h_post, h_res = mappings(w, pre, X, dims)
    y = sublayer((h_pre[:, :, None] * X).sum(1))
    return ((h_res[:, :, :, None] * X[:, None, :, :]).sum(2)
            + h_post[:, :, None] * y[:, None, :])


def block(w, x, dims, is_routed, precision="highest"):
    """One layer over the streams x [B, T, n, d], a sequence at a
    time."""
    eps = dims["rms_norm_eps"]

    def feed_forward(u):
        h = _rms(u, w["post_norm.weight"], eps)
        if is_routed:
            return routed(w, h, dims, precision)
        return _swiglu(h, w["ffn.gate_up_proj.weight"],
                       w["ffn.down_proj.weight"], precision)

    def one(X):
        X = residual(w, "attn_hc.", X, dims, lambda u: attention(
            w, _rms(u, w["input_norm.weight"], eps), dims, precision))
        return residual(w, "ffn_hc.", X, dims, feed_forward)
    return jax.lax.map(one, x)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _frozen(dims):
    """``dims`` as a hashable static argument (its nested groups
    too)."""
    return json.dumps(dims, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_jit(w, x, dims, is_routed, precision):
    return block(_f32(w), x, json.loads(dims), is_routed, precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _regret(w, x, served, eps, precision):
    """Per row and position: the reference's best logit minus its logit
    for the served token (``served`` holds at t the token that followed
    position t, -1 where none was served), ``HEAD_BLOCK`` positions at
    a time so that the [positions, vocabulary] logits stay small.  x
    [B, T, D] is the streams' sum."""
    w = _f32(w)
    B, T, D = x.shape
    hb = _divisor(T, HEAD_BLOCK)

    def some(args):
        xs, sv = args                                   # [B, hb, D]
        logits = _mm(_rms(xs, w["norm.weight"], eps),
                     w["lm_head.weight"], precision)
        got = jnp.take_along_axis(
            logits, jnp.maximum(sv, 0)[..., None], -1)[..., 0]
        return logits.max(-1) - got, logits.argmax(-1)
    xs = x.reshape(B, T // hb, hb, D).transpose(1, 0, 2, 3)
    sv = served.reshape(B, T // hb, hb).transpose(1, 0, 2)
    reg, top = jax.lax.map(some, (xs, sv))
    reg = reg.transpose(1, 0, 2).reshape(B, T)
    top = top.transpose(1, 0, 2).reshape(B, T)
    valid = served >= 0
    return jnp.where(valid, reg, 0.0), valid, top


def layer_leaves(dims, i):
    """Names (without the ``blocks.<i>.`` prefix) of layer i's
    leaves."""
    names = ["input_norm.weight", "attn.q_a_proj.weight",
             "attn.q_a_norm.weight", "attn.q_b_proj.weight",
             "attn.kv_a_proj.weight", "attn.kv_norm.weight", "attn.kv_b",
             "attn.o_proj.weight", "post_norm.weight"]
    names += [hc + leaf for hc in ("attn_hc.", "ffn_hc.")
              for leaf in ("norm.weight", "phi", "alpha", "beta")]
    if i < dims["first_k_dense_replace"]:
        return names + ["ffn.gate_up_proj.weight", "ffn.down_proj.weight"]
    return names + ["ffn.gate_weight", "ffn.gate_bias", "ffn.experts_in",
                    "ffn.experts_out", "ffn.shared.gate_up_proj.weight",
                    "ffn.shared.down_proj.weight"]


def served_regret(get_weights, dims, ids, served, precision="highest",
                  rows_per_block=1):
    """Teacher-forced regret of served tokens.

    ``get_weights(names)`` returns the named leaves (any float type);
    ``ids`` [B, T] are prompt + served tokens, right-padded; ``served``
    [B, T] is -1 except where position t's next token was served.  The
    layers are streamed: each layer's weights are made once, upcast,
    applied to the rows in blocks of ``rows_per_block`` and dropped,
    and the two layer programs (dense, routed) compile once each.
    Returns (regret [B, T], valid [B, T], argmax [B, T]) as numpy."""
    ids = np.asarray(ids, np.int32)
    served = np.asarray(served, np.int32)
    blocks = [slice(lo, lo + rows_per_block)
              for lo in range(0, ids.shape[0], rows_per_block)]
    xs = _after_layers(get_weights, dims, [ids[b] for b in blocks],
                       precision)
    w = get_weights(("norm.weight", "lm_head.weight"))
    out = [[np.asarray(a) for a in _regret(
        w, x.sum(2), jnp.asarray(served[b]), float(dims["rms_norm_eps"]),
        precision)] for x, b in zip(xs, blocks)]
    return tuple(np.concatenate([o[k] for o in out]) for k in range(3))


def _after_layers(get_weights, dims, id_blocks, precision):
    """The streams [b, T, n, d] after every layer for each block of
    ids [b, T]; the layers are streamed, each layer's weights made
    once, applied to every block and dropped."""
    emb = get_weights(("embed",))["embed"]
    xs = [emb[jnp.asarray(ids)].astype(jnp.float32) for ids in id_blocks]
    del emb
    xs = [jnp.broadcast_to(x[:, :, None, :], x.shape[:2]
                           + (dims["hc_mult"], x.shape[-1])) for x in xs]
    frozen = _frozen(dims)
    for i in range(dims["num_hidden_layers"]):
        pre = f"blocks.{i}."
        names = layer_leaves(dims, i)
        w = get_weights(tuple(pre + n for n in names))
        w = {n: w[pre + n] for n in names}
        is_routed = i >= dims["first_k_dense_replace"]
        xs = [_block_jit(w, x, frozen, is_routed, precision) for x in xs]
        del w
    return xs


def logits(get_weights, dims, ids, precision="highest"):
    """[B, T, V] logits of whole sequences (small sizes: the tests)."""
    x, = _after_layers(get_weights, dims, [np.asarray(ids, np.int32)],
                       precision)
    w = _f32(get_weights(("norm.weight", "lm_head.weight")))
    return _mm(_rms(x.sum(2), w["norm.weight"], dims["rms_norm_eps"]),
               w["lm_head.weight"], precision)
