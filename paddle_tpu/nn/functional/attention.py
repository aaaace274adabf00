"""Attention functionals.

Reference parity: the reference only has a score-materializing
``MultiHeadAttention`` (python/paddle/nn/layer/transformer.py:85) and an
inference-only fused kernel (operators/fused/multihead_matmul_op.cu).
TPU-native design: one `scaled_dot_product_attention` entry point that
dispatches to a Pallas flash-attention kernel on TPU backends (blockwise
online-softmax so the S×S score matrix never hits HBM) at long sequences
and to the pure-XLA form on the ``cpu`` platform and at short ones — a
choice made from the platform and the shapes, never from a failed
import.  Long-context sharded variants (ring attention over a mesh axis)
live in paddle_tpu/distributed/ring.py and reuse the same inner kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import primitive, ensure_tensor


def _reference_attention(q, k, v, mask=None, scale=None, is_causal=False):
    """[B, S, H, D] layout (paddle convention). Pure XLA."""
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qh = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if is_causal:
        sk = kh.shape[2]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
        q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _flash_available():
    """The Pallas flash kernel serves every backend but ``cpu``
    (Mosaic does not target it); the import is unguarded, so a JAX
    without the kernel fails here instead of quietly serving the XLA
    reference."""
    if jax.default_backend() == "cpu":
        return False
    from jax.experimental.pallas.ops.tpu.flash_attention import (  # noqa
        flash_attention)
    return True


# Flash engages at seq >= this (tunable; bench/perf experiments override).
# Below it, XLA's fused naive path wins on TPU unless memory forces flash.
FLASH_MIN_SEQ = 2048
# block-size policy for the pallas kernel:
#   None     -> the tuned defaults below (the kernel's own 128-blocks
#               measured 2.9x slower on v5e at S=4096: 7.6k -> 21.8k
#               tok/s GPT-2 345M train with 1024x1024 blocks)
#   "kernel" -> the pallas kernel's built-in defaults (A/B baseline)
#   a BlockSizes instance -> used as-is
FLASH_BLOCK_SIZES = None


def _default_block_sizes(seq_q, seq_kv):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    def pick(seq):
        # largest 128-multiple block that DIVIDES seq (the kernel rejects
        # non-dividing blocks); the dispatch gate guarantees both seq_q
        # and seq_kv are multiples of 128, so 128 always divides
        for b in (1024, 512, 256, 128):
            if seq % b == 0:
                return b
        return min(seq, 128)

    bq = pick(seq_q)
    bk = pick(seq_kv)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)


def _flash_attention(q, k, v, mask, scale, is_causal, segment_ids=None):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds, flash_attention)
    # pallas kernel expects [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    kwargs = {}
    if FLASH_BLOCK_SIZES is None:
        kwargs["block_sizes"] = _default_block_sizes(
            qh.shape[2], kh.shape[2])
    elif FLASH_BLOCK_SIZES != "kernel":
        kwargs["block_sizes"] = FLASH_BLOCK_SIZES
    if segment_ids is not None:
        # packed sequences: block-diagonal masking INSIDE the kernel —
        # no S x S score/mask tensor ever reaches HBM
        kwargs["segment_ids"] = SegmentIds(q=segment_ids,
                                           kv=segment_ids)
    out = flash_attention(qh, kh, vh, causal=is_causal, sm_scale=scale,
                          **kwargs)
    return jnp.swapaxes(out, 1, 2)


@primitive(name="scaled_dot_product_attention", nondiff=(3,))
def _sdpa(q, k, v, segment_ids=None, mask=None, scale=None,
          is_causal=False, use_flash=True):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    seq = q.shape[1]
    # Pallas flash attention wins when the S×S score tensor stresses HBM
    # (long sequences); at short seq XLA's fused naive path is faster on
    # TPU (measured: GPT-2 S=1024 trains ~1.7x faster via XLA than via the
    # pallas kernel, which pays layout transposes + bwd recompute).
    seq_kv = k.shape[1]
    if (use_flash and mask is None and _flash_available()
            and seq >= FLASH_MIN_SEQ and seq % 128 == 0
            and seq_kv % 128 == 0 and d % 64 == 0):
        return _flash_attention(q, k, v, mask, scale, is_causal,
                                segment_ids=segment_ids)
    if segment_ids is not None:
        # dense fallback: derive the block-diagonal mask (short seq /
        # CPU); combined with causal inside _reference_attention
        mask = (segment_ids[:, :, None]
                == segment_ids[:, None, :])[:, None, :, :]
    return _reference_attention(q, k, v, mask, scale, is_causal)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None,
                                 segment_ids=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle layout).

    ``segment_ids`` [B, S] int32 (packed sequences): attention is
    blocked to same-segment pairs — via the flash kernel's native
    SegmentIds at long seq (no S×S tensor), a derived dense mask
    otherwise."""
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    if attn_mask is not None and segment_ids is not None:
        raise ValueError(
            "scaled_dot_product_attention: pass attn_mask OR "
            "segment_ids, not both — silently dropping one would leak "
            "attention across the other's boundaries (fold any padding "
            "mask into the segment ids instead)")
    if attn_mask is not None:
        attn_mask = ensure_tensor(attn_mask)
        out = primitive(name="scaled_dot_product_attention_masked")(
            lambda qq, kk, vv, mm: _reference_attention(
                qq, kk, vv, mm, scale, is_causal))(q, k, v, attn_mask)
    elif segment_ids is not None:
        out = _sdpa(q, k, v, ensure_tensor(segment_ids), scale=scale,
                    is_causal=is_causal)
    else:
        out = _sdpa(q, k, v, scale=scale, is_causal=is_causal)
    if dropout_p > 0.0 and training:
        from .common import dropout
        out = dropout(out, p=dropout_p, training=training)
    return out
