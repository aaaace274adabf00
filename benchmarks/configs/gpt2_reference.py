"""Plain reference for the GPT-2 family (GPT-2, GPT-3 dense): the
forward pass, the loss and one AdamW step in straightforward
``jax.numpy``, float32 with matrix products at ``highest`` precision.
No cache, no batching tricks, no kernels; it imports nothing of the
program.

Architecture as published (Radford et al. 2019; Brown et al. 2020
keep it): learned position embeddings; pre-norm blocks
``x += attn(ln1(x)); x += mlp(ln2(x))``; one fused QKV product whose
columns are ordered (q|k|v, head, head_dim); causal softmax attention
scaled by 1/sqrt(head_dim); GELU in its tanh form; a final layer norm
and an LM head that is a matrix of its own (the configurations here do
not tie it to the embedding).  Layer norm epsilon 1e-5.

``precision`` selects the arithmetic of the matrix products:
``"highest"`` is the reference; ``"fp8"`` rounds both operands of every
product to float8_e4m3 under a per-tensor scale and is the control of a
bfloat16 configuration (the nearest precision below it); ``"bf16"``
rounds them to bfloat16 and is the control of a float32 one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-5


def _through(a, rounded):
    """``rounded`` forward, identity backward: the rounding is applied to
    the operands that the forward and the backward products use, and the
    cotangent itself stays float32."""
    return a + jax.lax.stop_gradient(rounded - a)


def _fp8(a):
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    s = jax.lax.stop_gradient(s)
    return _through(
        a, (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s)


def _mm(a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "bf16":
        a, b = (_through(t, t.astype(jnp.bfloat16).astype(jnp.float32))
                for t in (a, b))
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _ln(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(w, x, num_heads, precision="highest"):
    """One block on x [B, T, d]; ``w`` holds this block's leaves under
    their short names (``ln1.weight`` ...), float32."""
    B, T, d = x.shape
    hd = d // num_heads
    h = _ln(x, w["ln1.weight"], w["ln1.bias"])
    qkv = _mm(h, w["attn.qkv_proj.weight"], precision) \
        + w["attn.qkv_proj.bias"]
    qkv = qkv.reshape(B, T, 3, num_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.float32(hd))
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, T, d)
    x = x + _mm(a, w["attn.out_proj.weight"], precision) \
        + w["attn.out_proj.bias"]
    h = _ln(x, w["ln2.weight"], w["ln2.bias"])
    h = _gelu(_mm(h, w["mlp.fc1.weight"], precision) + w["mlp.fc1.bias"])
    return x + _mm(h, w["mlp.fc2.weight"], precision) + w["mlp.fc2.bias"]


BLOCK_LEAVES = ("ln1.weight", "ln1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln2.weight", "ln2.bias",
                "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
                "mlp.fc2.bias")
WTE = "embeddings.word_embeddings.weight"
WPE = "embeddings.position_embeddings.weight"
LNF_W, LNF_B, HEAD = ("head.ln_f.weight", "head.ln_f.bias",
                      "head.lm_head.weight")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# -- serving: logits of whole sequences, layer by layer ------------------

@jax.jit
def _embed(w, ids):
    w = _f32(w)
    T = ids.shape[1]
    return w[WTE][ids] + w[WPE][jnp.arange(T)]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_jit(w, x, num_heads, precision):
    return block(_f32(w), x, num_heads, precision)


@functools.partial(jax.jit, static_argnums=(3,))
def _regret(w, x, served, precision):
    """Per row and position: the reference's best logit minus its logit
    for the served token.  ``served`` [B, T] holds at index t the token
    that followed position t, and -1 where none was served."""
    w = _f32(w)
    h = _ln(x, w[LNF_W], w[LNF_B])
    logits = _mm(h, w[HEAD], precision)
    best = logits.max(-1)
    got = jnp.take_along_axis(
        logits, jnp.maximum(served, 0)[..., None], -1)[..., 0]
    valid = served >= 0
    return jnp.where(valid, best - got, 0.0), valid, logits.argmax(-1)


def served_regret(get_weights, dims, ids, served, precision="highest",
                  rows_per_block=4):
    """Teacher-forced regret of served tokens.

    ``get_weights(names)`` returns the named leaves (any float type);
    ``ids`` [B, T] are prompt + served tokens, right-padded; ``served``
    [B, T] is -1 except where position t's next token was served.  Each
    layer's weights are made once and applied to the rows in blocks of
    ``rows_per_block``, so that scores and logits of a few rows at a
    time are all that is alive.
    Returns (regret [B, T], valid [B, T], argmax [B, T]) as numpy."""
    import numpy as np
    ids = np.asarray(ids, np.int32)
    served = np.asarray(served, np.int32)
    blocks = [slice(lo, lo + rows_per_block)
              for lo in range(0, ids.shape[0], rows_per_block)]
    w = get_weights((WTE, WPE))
    xs = [_embed(w, jnp.asarray(ids[b])) for b in blocks]
    for i in range(dims["num_layers"]):
        pre = f"blocks.{i}."
        w = get_weights(tuple(pre + n for n in BLOCK_LEAVES))
        w = {n: w[pre + n] for n in BLOCK_LEAVES}
        xs = [_block_jit(w, x, dims["num_heads"], precision) for x in xs]
    w = get_weights((LNF_W, LNF_B, HEAD))
    out = [[np.asarray(a) for a in _regret(
        w, x, jnp.asarray(served[b]), precision)]
        for x, b in zip(xs, blocks)]
    return tuple(np.concatenate([o[k] for o in out]) for k in range(3))


# -- training: loss, gradients and AdamW, rows in blocks -----------------

def stack_params(w, dims):
    """The flat leaves as the pytree the training reference scans over:
    block leaves stacked on a leading layer axis, all float32."""
    L = dims["num_layers"]
    out = {n: jnp.stack([w[f"blocks.{i}.{n}"] for i in range(L)])
           .astype(jnp.float32) for n in BLOCK_LEAVES}
    for n in (WTE, WPE, LNF_W, LNF_B, HEAD):
        out[n] = w[n].astype(jnp.float32)
    return out


def unstack_names(dims):
    """(flat name, stacked key, layer or None) for every leaf."""
    out = [(n, n, None) for n in (WTE, WPE, LNF_W, LNF_B, HEAD)]
    for i in range(dims["num_layers"]):
        out += [(f"blocks.{i}.{n}", n, i) for n in BLOCK_LEAVES]
    return out


def loss_fn(p, x, y, num_heads, precision="highest"):
    """Mean next-token cross entropy over every position of x [B, T]."""
    T = x.shape[1]
    h = p[WTE][x] + p[WPE][jnp.arange(T)]

    @jax.checkpoint
    def body(h, w):
        return block(w, h, num_heads, precision), None
    h, _ = jax.lax.scan(body, h, {n: p[n] for n in BLOCK_LEAVES})
    logits = _mm(_ln(h, p[LNF_W], p[LNF_B]), p[HEAD], precision)
    logz = jax.nn.logsumexp(logits, -1)
    got = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    return (logz - got).mean()


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(5,))
def _grad_block(p, x, y, num_heads, precision, acc):
    loss, g = jax.value_and_grad(loss_fn)(p, x, y, num_heads, precision)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, hyper, t):
    lr, b1, b2, eps, wd = hyper
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

    def upd(p, m, v):
        mh = m / (1 - b1 ** t)
        vh = v / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    return jax.tree_util.tree_map(upd, p, m, v), m, v


def train_steps(p, batches, dims, opt, rows_per_block=2,
                precision="highest", store=None):
    """Follow ``batches`` [(x, y)] with AdamW (decoupled decay on every
    leaf, bias-corrected moments).  Gradients of the batch mean are the
    mean of row-block gradients, since every row has as many targets.
    ``store`` names the type the configuration keeps its parameters in:
    after each update they are rounded to it (the arithmetic stays
    float32), as a deployment without master weights does.
    Returns (losses, first gradient pytree, final params)."""
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"],
             opt["epsilon"], opt["weight_decay"])
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, p)
    m, v = zeros(), zeros()
    losses, first_grad = [], None
    for t, (x, y) in enumerate(batches, 1):
        n = x.shape[0]
        if n % rows_per_block:
            raise ValueError("rows_per_block must divide the batch")
        k = n // rows_per_block
        acc, tot = zeros(), 0.0
        for b in range(k):
            sl = slice(b * rows_per_block, (b + 1) * rows_per_block)
            loss, acc = _grad_block(p, jnp.asarray(x[sl]),
                                    jnp.asarray(y[sl]),
                                    dims["num_heads"], precision, acc)
            tot += float(loss)
        g = jax.tree_util.tree_map(lambda a: a / k, acc)
        losses.append(tot / k)
        if first_grad is None:
            first_grad = g
        p, m, v = _adamw(p, g, m, v, hyper, jnp.float32(t))
        if store is not None:
            p = jax.tree_util.tree_map(
                lambda a: a.astype(store).astype(jnp.float32), p)
    return losses, first_grad, p
