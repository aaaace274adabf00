"""Plain reference for the latent-attention / routed-experts decoder
(the DeepSeek-V3 layer family; Kimi-VL-A3B's language decoder): the
forward pass in straightforward ``jax.numpy``, float32 with matrix
products at ``highest`` precision.  Expanded attention, a loop over
experts in which every expert sees every row, no cache, no kernels; it
imports nothing of the program.

Architecture as the published code computes it (``modeling_deepseek``
as Kimi-VL ships it), with the departures the configuration's file
lists under ``assumed``:

* pre-norm blocks ``x += attn(rms(x)); x += ffn(rms(x))``, RMSNorm with
  epsilon ``rms_norm_eps``, a final norm and an untied head;
* attention: ``q = h W_q`` split per head into a no-position part
  (``qk_nope_head_dim``) and a rotary part (``qk_rope_head_dim``);
  ``h W_kva`` split into the latent (``kv_lora_rank``), which is
  RMS-normed, and ONE rotary key shared by all heads; the latent
  expanded by ``W_kvb`` into each head's no-position key and value;
  scores ``(q_n . k_n + q_r . k_r) / sqrt(d_n + d_r)``, causal softmax;
* rotary pairs: dimension i with i + d_r/2 in stored order (the
  published code de-interleaves first: a fixed permutation of columns);
  angle ``pos * theta^(-2i/d_r)``;
* the first ``first_k_dense_replace`` layers: ``(silu(h W1) * (h W3))
  W2``; the others: ``s = sigmoid(h W_g)`` in float32, the experts of a
  token are the top ``num_experts_per_tok`` of ``s + b``, their weights
  ``routed_scaling_factor * s_e / sum_selected s``, plus the shared
  expert (width ``n_shared_experts`` x the expert width) on every
  token; gate | up of a feed-forward sit side by side in one leaf.

``precision``: ``"highest"`` is the reference; ``"fp8"`` rounds both
operands of every matrix product to float8_e4m3 under a per-tensor
scale (the nearest precision below bfloat16); ``"bf16"`` rounds them to
bfloat16.  The router's scores stay float32 in all three.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512        # queries a step of the attention loop
HEAD_BLOCK = 512     # positions a step of the head loop


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
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, ..., d] at positions 0..T-1."""
    T, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _swiglu(h, w_in, w_out, precision):
    a = _mm(h, w_in, precision)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(a[:, :f]) * a[:, f:], w_out, precision)


def attention(w, h, dims, precision):
    """h [T, D] of one sequence -> [T, D]."""
    T = h.shape[0]
    H, r = dims["num_attention_heads"], dims["kv_lora_rank"]
    dn, dr = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    dv, theta = dims["v_head_dim"], float(dims["rope_theta"])
    q = _mm(h, w["attn.q_proj.weight"], precision).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], theta)
    ckr = _mm(h, w["attn.kv_a_proj.weight"], precision)
    c = _rms(ckr[:, :r], w["attn.kv_norm.weight"], dims["rms_norm_eps"])
    k_r = _rope(ckr[:, r:], theta)
    kv = _mm(c, w["attn.kv_b"], precision).reshape(T, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + dr)
    qb = _divisor(T, Q_BLOCK)

    def some_queries(i):
        qn = jax.lax.dynamic_slice_in_dim(q_n, i * qb, qb)
        qr = jax.lax.dynamic_slice_in_dim(q_r, i * qb, qb)
        s = (jnp.einsum("qhd,khd->hqk", _round(qn, precision),
                        _round(k_n, precision),
                        precision=jax.lax.Precision.HIGHEST)
             + jnp.einsum("qhd,kd->hqk", _round(qr, precision),
                          _round(k_r, precision),
                          precision=jax.lax.Precision.HIGHEST)) * scale
        at = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(T)[None, None, :] <= at[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqk,khd->qhd", _round(p, precision),
                          _round(v, precision),
                          precision=jax.lax.Precision.HIGHEST)
    o = jax.lax.map(some_queries, jnp.arange(T // qb)).reshape(T, H * dv)
    return _mm(o, w["attn.o_proj.weight"], precision)


def routed(w, h, dims, precision):
    """The routed layer's feed-forward over h [T, D]: every expert over
    every row, weighted by the gate (0 where not selected), plus the
    shared expert."""
    k, E = dims["num_experts_per_tok"], dims["n_routed_experts"]
    s = jax.nn.sigmoid(jnp.matmul(h, w["ffn.gate_weight"],
                                  precision=jax.lax.Precision.HIGHEST))
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


def block(w, x, dims, is_routed, precision="highest"):
    """One layer over x [B, T, D], a sequence at a time."""
    eps = dims["rms_norm_eps"]

    def one(x):
        x = x + attention(w, _rms(x, w["input_norm.weight"], eps), dims,
                          precision)
        h = _rms(x, w["post_norm.weight"], eps)
        if is_routed:
            return x + routed(w, h, dims, precision)
        return x + _swiglu(h, w["ffn.gate_up_proj.weight"],
                           w["ffn.down_proj.weight"], precision)
    return jax.lax.map(one, x)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _frozen(dims):
    return tuple(sorted((k, v) for k, v in dims.items()
                        if isinstance(v, (int, float, bool))))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_jit(w, x, dims, is_routed, precision):
    return block(_f32(w), x, dict(dims), is_routed, precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _regret(w, x, served, eps, precision):
    """Per row and position: the reference's best logit minus its logit
    for the served token (``served`` holds at t the token that followed
    position t, -1 where none was served), ``HEAD_BLOCK`` positions at
    a time so that the [positions, vocabulary] logits stay small."""
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
    names = ["input_norm.weight", "attn.q_proj.weight",
             "attn.kv_a_proj.weight", "attn.kv_norm.weight", "attn.kv_b",
             "attn.o_proj.weight", "post_norm.weight"]
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
    import numpy as np
    ids = np.asarray(ids, np.int32)
    served = np.asarray(served, np.int32)
    blocks = [slice(lo, lo + rows_per_block)
              for lo in range(0, ids.shape[0], rows_per_block)]
    emb = get_weights(("embed",))["embed"]
    xs = [emb[jnp.asarray(ids[b])].astype(jnp.float32) for b in blocks]
    del emb
    frozen = _frozen(dims)
    for i in range(dims["num_hidden_layers"]):
        pre = f"blocks.{i}."
        names = layer_leaves(dims, i)
        w = get_weights(tuple(pre + n for n in names))
        w = {n: w[pre + n] for n in names}
        is_routed = i >= dims["first_k_dense_replace"]
        xs = [_block_jit(w, x, frozen, is_routed, precision) for x in xs]
        del w
    w = get_weights(("norm.weight", "lm_head.weight"))
    out = [[np.asarray(a) for a in _regret(
        w, x, jnp.asarray(served[b]), float(dims["rms_norm_eps"]),
        precision)] for x, b in zip(xs, blocks)]
    return tuple(np.concatenate([o[k] for o in out]) for k in range(3))


def logits(get_weights, dims, ids, precision="highest"):
    """[B, T, V] logits of whole sequences (small sizes: the tests)."""
    ids = jnp.asarray(ids, jnp.int32)
    x = _f32(get_weights(("embed",)))["embed"][ids]
    frozen = _frozen(dims)
    for i in range(dims["num_hidden_layers"]):
        pre = f"blocks.{i}."
        names = layer_leaves(dims, i)
        w = get_weights(tuple(pre + n for n in names))
        x = _block_jit({n: w[pre + n] for n in names}, x, frozen,
                       i >= dims["first_k_dense_replace"], precision)
    w = _f32(get_weights(("norm.weight", "lm_head.weight")))
    return _mm(_rms(x, w["norm.weight"], dims["rms_norm_eps"]),
               w["lm_head.weight"], precision)
