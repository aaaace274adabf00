"""Plain reference for the decoder of gated short-convolution and
grouped-query attention layers with sigmoid-routed experts and no
shared one (LFM2-8B-A1B, ``model_type`` ``lfm2_moe``): the forward pass
in straightforward ``jax.numpy``, float32 with matrix products at
``highest`` precision.  The convolution over the whole sequence as
shifted multiply-adds from a zero start, attention a block of queries
at a time over every key under the causal mask, a loop over the experts
in which every expert sees every row; no cache, no state, no kernel, no
work list; it imports nothing of the program.

Architecture as the published ``config.json`` declares it, with what it
has no key for as the configuration's file lists under ``assumed`` (d
``hidden_size``, H / K query and key/value heads of ``hd = d / H``, L
``conv_L_cache`` taps, eps ``norm_eps``, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * gain``, no bias anywhere):

* ``x_0 = Emb[id]``; ``logits = RMSNorm(x_n) Emb^T`` (the head is the
  embedding, tied);
* block l: ``x <- x + Op(RMSNorm_op(x))``, then ``x <- x +
  FF(RMSNorm_ffn(x))``;
* ``Op`` of a ``conv`` layer: ``[b; c; u] = a W_in`` (three chunks of d
  in that order), ``s_t = b_t * u_t``, ``v_t = sum_j w[:, j] * s_{t - (L
  - 1) + j}`` (``w`` [d, L] a channel's own taps, the last one the
  position's own; s before position 0 is 0), ``Op = (c_t * v_t) W_out``;
* ``Op`` of a ``full_attention`` layer: ``q = a W_q`` [H, hd], ``k = a
  W_k``, ``v = a W_v`` [K, hd]; q and k RMS-normalised a head with a
  gain of hd each, then rotated (theta ``rope_theta``, dimension i
  pairs with i + hd/2, no scaling); position i sees every ``j <= i``;
  query head h reads K/V head ``h // (H / K)``; ``Op = softmax(q . k /
  sqrt(hd)) v W_o``;
* ``FF``: below ``num_dense_layers`` ``(silu(h W_1) * (h W_3)) W_2`` at
  ``intermediate_size``; from there on ``s = sigmoid(h W_r)`` in
  float32, the ``num_experts_per_tok`` experts of a token are the top of
  ``s + expert_bias``, ``w = s[choice] / (sum + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``, ``FF(h) = sum_j
  w_j E_{c_j}(h)``; gate | up of a feed-forward sit side by side in one
  leaf.

``dims["seeded"]`` says what drawn leaves are multiplied by before
anyone uses them (1 where the configuration says nothing):
``conv_weight_scale`` the taps, ``q_norm_scale`` the query heads' norm
gains.  The weights are random, and the program's ``build`` scales the
same leaves (powers of two, so exact in every dtype).

``precision``: ``"highest"`` is the reference; ``"bf16"`` rounds both
operands of every matrix product, and the convolution's inputs, to
bfloat16, ``"fp8"`` to float8_e4m3 under a per-tensor scale (the
router's scores stay float32).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256        # queries a step of the attention loop
HEAD_BLOCK = 512     # positions a step of the head loop
HIGHEST = jax.lax.Precision.HIGHEST
CONV = "conv"


def _divisor(n, cap):
    """The largest block size up to ``cap`` that divides ``n``."""
    return next(b for b in range(min(cap, n), 0, -1) if n % b == 0)


def _round(a, precision):
    a = a.astype(jnp.float32)
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
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1: dimension i pairs with
    i + hd/2, angle ``t * theta^(-2i/hd)``."""
    T, hd = x.shape[0], x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, w_in, w_out, precision):
    a = _mm(h, w_in, precision)
    f = w_out.shape[0]
    return _mm(jax.nn.silu(a[:, :f]) * a[:, f:], w_out, precision)


def _seeded(dims, key):
    return float(dims.get("seeded", {}).get(key, 1.0))


def short_conv(w, a, dims, precision):
    """a [T, D] of one sequence (already normed) -> [T, D]: the gated
    convolution from a zero start."""
    T, d = a.shape
    taps = dims["conv_L_cache"]
    bcu = _mm(a, w["conv.in_proj.weight"], precision)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    s = _round(b * u, precision)
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), s.dtype), s])
    taps_w = w["conv.conv_weight"].astype(jnp.float32) \
        * _seeded(dims, "conv_weight_scale")
    v = sum(taps_w[:, j] * padded[j:j + T] for j in range(taps))
    return _mm(c * v, w["conv.out_proj.weight"], precision)


def attention(w, a, dims, precision):
    """a [T, D] of one sequence (already normed) -> [T, D]."""
    T = a.shape[0]
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd, eps = dims["hidden_size"] // H, dims["norm_eps"]
    theta = float(dims["rope_theta"])
    q = _rope(_rms(
        _mm(a, w["attn.q_proj.weight"], precision).reshape(T, H, hd),
        w["attn.q_norm.weight"] * _seeded(dims, "q_norm_scale"), eps),
        theta)
    k = _rope(_rms(
        _mm(a, w["attn.k_proj.weight"], precision).reshape(T, K, hd),
        w["attn.k_norm.weight"], eps), theta)
    v = _mm(a, w["attn.v_proj.weight"], precision).reshape(T, K, hd)
    q = q.reshape(T, K, H // K, hd)
    qb = _divisor(T, Q_BLOCK)
    keys = jnp.arange(T)[None, :]

    def some_queries(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = jnp.einsum("qkgd,nkd->kgqn", _round(qs, precision),
                       _round(k, precision),
                       precision=HIGHEST) / math.sqrt(hd)
        sees = keys <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgqn,nkd->qkgd", _round(p, precision),
                          _round(v, precision), precision=HIGHEST)
    o = jax.lax.map(some_queries, jnp.arange(T // qb)).reshape(T, H * hd)
    return _mm(o, w["attn.o_proj.weight"], precision)


def routed(w, h, dims, precision):
    """The expert layer's feed-forward over h [T, D]: the router, then
    every expert over every row weighted by the gate (0 where it was
    not chosen)."""
    k, E = dims["num_experts_per_tok"], dims["num_experts"]
    s = jax.nn.sigmoid(jnp.matmul(
        h, w["ffn.gate_weight"].astype(jnp.float32), precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        s + w["ffn.gate_bias"].astype(jnp.float32)[None, :], k)
    picked = jnp.take_along_axis(s, chosen, -1)
    if dims.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    gate = jnp.zeros_like(s).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(
        picked * dims.get("routed_scaling_factor", 1.0))      # [T, E]

    def one_expert(y, e):
        out = _swiglu(h, w["ffn.experts_in"][e], w["ffn.experts_out"][e],
                      precision)
        return y + gate[:, e][:, None] * out, None
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(E))
    return y


def block(w, x, dims, kind, is_routed, precision="highest"):
    """One layer over x [B, T, D], a sequence at a time."""
    eps = dims["norm_eps"]

    def one(x):
        a = _rms(x, w["operator_norm.weight"], eps)
        x = x + (short_conv(w, a, dims, precision) if kind == CONV
                 else attention(w, a, dims, precision))
        h = _rms(x, w["ffn_norm.weight"], eps)
        return x + (routed(w, h, dims, precision) if is_routed
                    else _swiglu(h, w["ffn.gate_up_proj.weight"],
                                 w["ffn.down_proj.weight"], precision))
    return jax.lax.map(one, x)


def _frozen(dims):
    """``dims`` as a hashable static argument (its nested groups
    too)."""
    return json.dumps(dims, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _block_jit(w, x, dims, kind, is_routed, precision):
    return block(w, x, json.loads(dims), kind, is_routed, precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _regret(w, x, served, eps, precision):
    """Per row and position: the reference's best logit minus its logit
    for the served token (``served`` holds at t the token that followed
    position t, -1 where none was served), ``HEAD_BLOCK`` positions at
    a time so that the [positions, vocabulary] logits stay small."""
    B, T, D = x.shape
    hb = _divisor(T, HEAD_BLOCK)

    def some(args):
        xs, sv = args                                   # [B, hb, D]
        logits = _mm(_rms(xs, w["norm.weight"], eps), w["embed"].T,
                     precision)
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
    names = ["operator_norm.weight", "ffn_norm.weight"]
    if dims["layer_types"][i] == CONV:
        names += ["conv.in_proj.weight", "conv.conv_weight",
                  "conv.out_proj.weight"]
    else:
        names += ["attn.q_proj.weight", "attn.k_proj.weight",
                  "attn.v_proj.weight", "attn.q_norm.weight",
                  "attn.k_norm.weight", "attn.o_proj.weight"]
    if i < dims["num_dense_layers"]:
        return names + ["ffn.gate_up_proj.weight", "ffn.down_proj.weight"]
    return names + ["ffn.gate_weight", "ffn.gate_bias", "ffn.experts_in",
                    "ffn.experts_out"]


def _after_layers(get_weights, dims, id_blocks, precision):
    """The residual [b, T, d] after every layer for each block of ids
    [b, T]; the layers are streamed, each layer's leaves fetched once,
    applied to every block and dropped (an expert layer's are 0.7 GB in
    bfloat16 and 1.4 GB in float32: the whole model's would not fit a
    chip)."""
    emb = get_weights(("embed",))["embed"]
    xs = [emb[jnp.asarray(ids)].astype(jnp.float32) for ids in id_blocks]
    del emb
    frozen = _frozen(dims)
    for i, kind in enumerate(dims["layer_types"]):
        pre = f"blocks.{i}."
        names = layer_leaves(dims, i)
        w = get_weights(tuple(pre + n for n in names))
        w = {n: w[pre + n] for n in names}
        xs = [_block_jit(w, x, frozen, kind,
                         i >= dims["num_dense_layers"], precision)
              for x in xs]
        del w
    return xs


def served_regret(get_weights, dims, ids, served, precision="highest",
                  rows_per_block=1):
    """Teacher-forced regret of served tokens.

    ``get_weights(names)`` returns the named leaves (any float type);
    ``ids`` [B, T] are prompt + served tokens, right-padded; ``served``
    [B, T] is -1 except where position t's next token was served.
    Returns (regret [B, T], valid [B, T], argmax [B, T]) as numpy."""
    ids = np.asarray(ids, np.int32)
    served = np.asarray(served, np.int32)
    blocks = [slice(lo, lo + rows_per_block)
              for lo in range(0, ids.shape[0], rows_per_block)]
    xs = _after_layers(get_weights, dims, [ids[b] for b in blocks],
                       precision)
    w = get_weights(("norm.weight", "embed"))
    out = [[np.asarray(a) for a in _regret(
        w, x, jnp.asarray(served[b]), float(dims["norm_eps"]),
        precision)] for x, b in zip(xs, blocks)]
    return tuple(np.concatenate([o[k] for o in out]) for k in range(3))


def logits(get_weights, dims, ids, precision="highest"):
    """[B, T, V] logits of whole sequences (small sizes: the tests)."""
    x, = _after_layers(get_weights, dims, [np.asarray(ids, np.int32)],
                       precision)
    w = get_weights(("norm.weight", "embed"))
    return _mm(_rms(x, w["norm.weight"], dims["norm_eps"]), w["embed"].T,
               precision)
