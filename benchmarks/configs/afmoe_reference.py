"""Plain reference for the decoder of sliding-window and full-attention
layers with gated attention, sandwich norms and sigmoid-routed experts
(Trinity-Large-Preview, ``model_type`` ``afmoe``): the forward pass in
straightforward ``jax.numpy``, float32 with matrix products at
``highest`` precision.  Attention a block of queries at a time over
every key with the causal mask and the window as masks over whole score
blocks, a loop over the held experts in which every expert sees every
row, no cache, no kernel, no work list; it imports nothing of the
program.

Architecture as the published ``config.json`` declares it, with what it
has no key for as the configuration's file lists under ``assumed``
(d ``hidden_size``, H / K query and key/value heads of ``head_dim`` hd,
W ``sliding_window``, eps ``rms_norm_eps``, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * gain``):

* ``x_0 = Emb[id] * sqrt(d)`` (``mup_enabled``); ``logits =
  RMSNorm(x_L) W_head``;
* block l: ``x <- x + RMSNorm_post_attn(Attn(RMSNorm_in(x)))``, then
  ``x <- x + RMSNorm_post_mlp(FF(RMSNorm_pre_mlp(x)))``;
* ``Attn(a)``: ``q = a W_q`` [H, hd], ``k = a W_k``, ``v = a W_v``
  [K, hd], ``g = a W_g`` [H hd]; q and k RMS-normalised a head with a
  gain of hd each; in a ``sliding_attention`` layer q and k are rotated
  (theta ``rope_theta``, dimension i pairs with i + hd/2, no scaling)
  and position i sees j iff ``j <= i`` and ``i - j < W``; in a
  ``full_attention`` layer nothing is rotated and i sees every ``j <=
  i``; query head h reads K/V head ``h // (H / K)``; ``o = softmax(q .
  k / sqrt(hd)) v``; ``Attn = (o * sigmoid(g)) W_o``;
* ``FF``: below ``num_dense_layers`` ``(silu(b W_1) * (b W_3)) W_2`` at
  ``intermediate_size``; from there on ``s = sigmoid(b W_r)`` in
  float32 over ALL the router's experts (the width of ``W_r``), the
  ``num_experts_per_tok`` experts of a token are the top of ``s +
  expert_bias``, ``w = s[choice] / (sum + 1e-20)`` (``route_norm``)
  times ``route_scale``, ``FF(b) = Shared(b) + sum_j w_j E_{c_j}(b)``;
  gate | up of a feed-forward sit side by side in one leaf.

**The share.**  ``dims["num_experts"]`` experts are held, from
``dims["share"]["experts_first"]`` (0 where ``dims`` has no ``share``)
of the router's: the stacks ``ffn.experts_in`` / ``_out`` hold those
and the sum over a token's choices runs over the ones that fall on
them; the shared expert is whole.  A sliced vocabulary is a smaller
vocabulary.

``precision``: ``"highest"`` is the reference; ``"bf16"`` rounds both
operands of every matrix product to bfloat16, ``"fp8"`` to float8_e4m3
under a per-tensor scale (the router's scores stay float32).
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
SLIDING = "sliding_attention"


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


def attention(w, a, dims, kind, precision):
    """a [T, D] of one sequence (already normed) -> [T, D]."""
    T = a.shape[0]
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd, eps = dims["head_dim"], dims["rms_norm_eps"]
    q = _rms(_mm(a, w["attn.q_proj.weight"], precision).reshape(T, H, hd),
             w["attn.q_norm.weight"], eps)
    k = _rms(_mm(a, w["attn.k_proj.weight"], precision).reshape(T, K, hd),
             w["attn.k_norm.weight"], eps)
    v = _mm(a, w["attn.v_proj.weight"], precision).reshape(T, K, hd)
    if kind == SLIDING:
        q = _rope(q, float(dims["rope_theta"]))
        k = _rope(k, float(dims["rope_theta"]))
    q = q.reshape(T, K, H // K, hd)
    qb = _divisor(T, Q_BLOCK)
    keys = jnp.arange(T)[None, :]

    def some_queries(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = jnp.einsum("qkgd,nkd->kgqn", _round(qs, precision),
                       _round(k, precision),
                       precision=HIGHEST) / math.sqrt(hd)
        at = (i * qb + jnp.arange(qb))[:, None]
        sees = keys <= at
        if kind == SLIDING:
            sees = sees & (at - keys < dims["sliding_window"])
        p = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgqn,nkd->qkgd", _round(p, precision),
                          _round(v, precision), precision=HIGHEST)
    o = jax.lax.map(some_queries, jnp.arange(T // qb)).reshape(T, H * hd)
    gate = jax.nn.sigmoid(_mm(a, w["attn.gate_proj.weight"], precision))
    return _mm(o * gate, w["attn.o_proj.weight"], precision)


def routed(w, b, dims, precision):
    """The expert layer's feed-forward over b [T, D]: the router over
    all its experts, every HELD expert over every row weighted by the
    gate (0 where it was not chosen), plus the shared expert."""
    k, held = dims["num_experts_per_tok"], dims["num_experts"]
    first = dims.get("share", {}).get("experts_first", 0)
    s = jax.nn.sigmoid(jnp.matmul(
        b, w["ffn.gate_weight"].astype(jnp.float32), precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        s + w["ffn.gate_bias"].astype(jnp.float32)[None, :], k)
    picked = jnp.take_along_axis(s, chosen, -1)
    if dims.get("route_norm", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    gate = jnp.zeros_like(s).at[
        jnp.arange(b.shape[0])[:, None], chosen].set(
        picked * dims["route_scale"])                   # [T, E_router]

    def one_expert(y, e):
        out = _swiglu(b, w["ffn.experts_in"][e], w["ffn.experts_out"][e],
                      precision)
        return y + gate[:, first + e][:, None] * out, None
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(b), jnp.arange(held))
    return y + _swiglu(b, w["ffn.shared.gate_up_proj.weight"],
                       w["ffn.shared.down_proj.weight"], precision)


def block(w, x, dims, kind, is_routed, precision="highest"):
    """One layer over x [B, T, D], a sequence at a time."""
    eps = dims["rms_norm_eps"]

    def one(x):
        x = x + _rms(attention(
            w, _rms(x, w["input_norm.weight"], eps), dims, kind,
            precision), w["post_attn_norm.weight"], eps)
        b = _rms(x, w["pre_mlp_norm.weight"], eps)
        y = (routed(w, b, dims, precision) if is_routed else _swiglu(
            b, w["ffn.gate_up_proj.weight"], w["ffn.down_proj.weight"],
            precision))
        return x + _rms(y, w["post_mlp_norm.weight"], eps)
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
             "attn.k_proj.weight", "attn.v_proj.weight",
             "attn.q_norm.weight", "attn.k_norm.weight",
             "attn.o_proj.weight", "attn.gate_proj.weight",
             "post_attn_norm.weight", "pre_mlp_norm.weight",
             "post_mlp_norm.weight"]
    if i < dims["num_dense_layers"]:
        return names + ["ffn.gate_up_proj.weight", "ffn.down_proj.weight"]
    return names + ["ffn.gate_weight", "ffn.gate_bias", "ffn.experts_in",
                    "ffn.experts_out", "ffn.shared.gate_up_proj.weight",
                    "ffn.shared.down_proj.weight"]


def _after_layers(get_weights, dims, id_blocks, precision):
    """The residual [b, T, d] after every layer for each block of ids
    [b, T]; the layers are streamed, each layer's leaves fetched once,
    applied to every block and dropped (an expert layer's are 2 GB in
    bfloat16: the whole model's in float32 would not fit a chip)."""
    emb = get_weights(("embed",))["embed"]
    scale = math.sqrt(dims["hidden_size"]) \
        if dims.get("mup_enabled") else 1.0
    xs = [emb[jnp.asarray(ids)].astype(jnp.float32) * scale
          for ids in id_blocks]
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
    w = get_weights(("norm.weight", "lm_head.weight"))
    out = [[np.asarray(a) for a in _regret(
        w, x, jnp.asarray(served[b]), float(dims["rms_norm_eps"]),
        precision)] for x, b in zip(xs, blocks)]
    return tuple(np.concatenate([o[k] for o in out]) for k in range(3))


def logits(get_weights, dims, ids, precision="highest"):
    """[B, T, V] logits of whole sequences (small sizes: the tests)."""
    x, = _after_layers(get_weights, dims, [np.asarray(ids, np.int32)],
                       precision)
    w = get_weights(("norm.weight", "lm_head.weight"))
    return _mm(_rms(x, w["norm.weight"], dims["rms_norm_eps"]),
               w["lm_head.weight"], precision)
