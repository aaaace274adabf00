"""Plain reference for ``gptmoe_program``'s model: GPT-2 blocks, every
``moe_every``-th with routed experts in place of its feed-forward.
float32, every expert computed for every token and the routed ones
picked afterwards; it imports nothing of the program.

An expert block: router probabilities softmax(h @ gate); the TOP_K best
experts of each token, weighted by their probabilities as they are (not
renormalised); each expert ``gelu(h @ w1 + b1) @ w2 + b2``.  No token is
dropped: the program's capacity, 2 x TOP_K x tokens / experts, is never
reached with four experts.  The loss adds, for every expert block,
0.01 x experts x sum_e(share of tokens whose best expert is e x mean
probability of e), taken over the whole batch, so ``rows_per_block`` has
to be the batch.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_gpt2_reference_for_moe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "gpt2_reference.py"))
g2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(g2)

TOP_K, AUX_WEIGHT = 2, 0.01


def _is_moe(dims, i):
    return (i + 1) % dims["moe_every"] == 0


def _attn(w, x, num_heads, precision):
    B, T, d = x.shape
    hd = d // num_heads
    h = g2._ln(x, w["ln1.weight"], w["ln1.bias"])
    qkv = (g2._mm(h, w["attn.qkv_proj.weight"], precision)
           + w["attn.qkv_proj.bias"]).reshape(B, T, 3, num_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                       jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, T, d)
    return x + g2._mm(a, w["attn.out_proj.weight"], precision) \
        + w["attn.out_proj.bias"]


def _experts(w, h, precision):
    """(output [B, T, d], this block's load-balancing term)."""
    B, T, d = h.shape
    t = h.reshape(B * T, d)
    probs = jax.nn.softmax(g2._mm(t, w["mlp.gate"], precision), axis=-1)
    e = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1), e), axis=0)
    aux = e * jnp.sum(share * jnp.mean(probs, axis=0))
    gates, choice = jax.lax.top_k(probs, TOP_K)
    ys = jnp.stack([
        g2._mm(g2._gelu(g2._mm(t, w["mlp.experts.w1"][i], precision)
                        + w["mlp.experts.b1"][i]),
               w["mlp.experts.w2"][i], precision) + w["mlp.experts.b2"][i]
        for i in range(e)], axis=1)                       # [tokens, E, d]
    picked = jnp.take_along_axis(ys, choice[:, :, None], axis=1)
    return (picked * gates[:, :, None]).sum(1).reshape(B, T, d), aux


def loss_fn(p, x, y, dims, precision="highest"):
    T = x.shape[1]
    h = p[g2.WTE][x] + p[g2.WPE][jnp.arange(T)]
    aux = 0.0
    for i in range(dims["num_layers"]):
        pre = f"blocks.{i}."
        w = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = _attn(w, h, dims["num_heads"], precision)
        n = g2._ln(h, w["ln2.weight"], w["ln2.bias"])
        if _is_moe(dims, i):
            out, a = _experts(w, n, precision)
            h, aux = h + out, aux + AUX_WEIGHT * a
        else:
            n = g2._gelu(g2._mm(n, w["mlp.fc1.weight"], precision)
                         + w["mlp.fc1.bias"])
            h = h + g2._mm(n, w["mlp.fc2.weight"], precision) \
                + w["mlp.fc2.bias"]
    logits = g2._mm(g2._ln(h, p[g2.LNF_W], p[g2.LNF_B]), p[g2.HEAD],
                    precision)
    got = jnp.take_along_axis(logits, y[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, -1) - got).mean() + aux


def stack_params(w, dims):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def unstack_names(dims):
    """(flat name, key, None) for every leaf: nothing is stacked."""
    names = [g2.WTE, g2.WPE, g2.LNF_W, g2.LNF_B, g2.HEAD]
    for i in range(dims["num_layers"]):
        mlp = (("mlp.gate",) + tuple("mlp.experts." + n for n in
                                     ("w1", "b1", "w2", "b2"))
               if _is_moe(dims, i) else g2.BLOCK_LEAVES[8:])
        names += [f"blocks.{i}.{n}" for n in g2.BLOCK_LEAVES[:8] + mlp]
    return [(n, n, None) for n in names]


def train_steps(p, batches, dims, opt, rows_per_block=2,
                precision="highest", store=None):
    """As ``gpt2_reference.train_steps``, the whole batch at once."""
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"],
             opt["epsilon"], opt["weight_decay"])
    frozen = tuple(sorted(dims.items()))
    grad = jax.jit(lambda p, x, y: jax.value_and_grad(loss_fn)(
        p, x, y, dict(frozen), precision))
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for t, (x, y) in enumerate(batches, 1):
        if x.shape[0] != rows_per_block:
            raise ValueError("the load-balancing term is the batch's: "
                             "rows_per_block has to be the batch")
        loss, g = grad(p, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = g
        p, m, v = g2._adamw(p, g, m, v, hyper, jnp.float32(t))
        if store is not None:
            p = jax.tree_util.tree_map(
                lambda a: a.astype(store).astype(jnp.float32), p)
    return losses, first_grad, p
