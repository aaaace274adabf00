"""Attention functionals.

Reference parity: the reference only has a score-materializing
``MultiHeadAttention`` (python/paddle/nn/layer/transformer.py:85) and an
inference-only fused kernel (operators/fused/multihead_matmul_op.cu).
TPU-native design: one `scaled_dot_product_attention` entry point that
dispatches to a Pallas blockwise kernel on the TPU (online softmax, so
the S×S score matrix never reaches HBM in either direction) from the
measured crossover up, and to the pure-XLA form on every other platform,
under a mask, at short sequences, in a program that spans several devices
and at a dtype or head size nobody measured — a choice made by one pure
function of what the call shows (``attention_path``), never by a flag or
a failed import.  Long-context sharded variants (ring attention over a mesh axis)
live in paddle_tpu/distributed/ring.py and reuse the same inner kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ... import monitor
from ...core.dispatch import primitive, ensure_tensor
from ...distributed import mesh as mesh_mod


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


# -- which implementation a call takes ------------------------------------
#
# Measured on one TPU v5e (jax 0.9.0; PERF.md section 6, PR 38): forward
# plus backward of ONE attention layer, bf16, causal, 8,192 tokens at width
# 1,024, by the device trace.  "dense" is ``_reference_attention``,
# "blockwise" the splash kernel at the blocks ``_default_block_sizes``
# picks, "flash" ``pallas.ops.tpu.flash_attention`` at its best block (its
# backward broadcasts the row statistics to ``[B, H, S, block_k]`` float32
# in HBM, the sequence-square again, so it was not kept):
#
#   positions   head 64: dense / blockwise / flash   head 128: same
#     256           0.64 / 1.58 / 2.75 ms             0.40 / 0.78 / 1.21 ms
#     512           2.10 / 1.54 / 2.71                0.74 / 0.77 / 1.31
#   1,024           4.34 / 2.05 / 3.78                2.16 / 1.13 / 1.79
#   2,048           8.04 / 3.11 / 5.18                4.12 / 1.71 / 2.39
#
# and dense / blockwise alone (the review round's call), float32 at heads
# of 64 and of 128, bf16 at heads of 256:
#
#   positions   f32, 64        f32, 128       bf16, 256
#     256       2.29 / 2.61    1.14 / 1.31    0.32 / 0.54 ms
#     512       4.44 / 2.48    2.57 / 1.25    0.51 / 0.69
#   1,024       8.35 / 3.24    4.32 / 1.74    1.05 / 1.14
#   2,048      15.28 / 4.68    8.00 / 2.23    2.33 / 1.54
#
# The least positions from which the kernel won, by dtype and head size.
# What is not here was not measured and keeps the dense form (float16
# cannot be: Mosaic has no float16 vectors on the v5e).

_LEAST_POSITIONS = {("bfloat16", 64): 512, ("bfloat16", 128): 1024,
                    ("bfloat16", 256): 2048,
                    ("float32", 64): 512, ("float32", 128): 512}


def attention_path(platform, seq_q, seq_kv, head_dim, masked, devices=1,
                   dtype="bfloat16"):
    """``"blockwise"`` or ``"dense"``: which implementation a call of
    ``_sdpa`` takes, from what the call itself shows and nothing else
    (no flag, no environment variable, no model option).

    ``platform`` is ``jax.default_backend()``; ``masked`` says that an
    arbitrary ``[.., S, S]`` mask was given (causality and segment ids
    are not masks: the kernel applies both itself); ``devices`` is the
    size of the mesh the program being traced is compiled for
    (``distributed.mesh.program_devices``: GSPMD cannot partition a
    Mosaic kernel, so a program over several devices keeps the dense
    form).  The kernel is a TPU kernel, wants whole 128-row tiles of
    both sequences, and wins from the measured crossover up (the table
    above) at the dtypes and head sizes that were measured."""
    if platform != "tpu" or masked or devices != 1:
        return "dense"
    if seq_q % 128 or seq_kv % 128:
        return "dense"
    least = _LEAST_POSITIONS.get((jnp.dtype(dtype).name, head_dim))
    if least is None or min(seq_q, seq_kv) < least:
        return "dense"
    return "blockwise"


def _default_block_sizes(seq_q, seq_kv):
    """The splash kernel's blocks for a pair of sequence lengths: the
    largest of 512 / 256 / 128 that DIVIDES each (the kernel takes no
    other), for the forward and the fused backward alike.  512 was the
    best square block, or within 5% of it, at every measured shape of
    512 positions and more (unequal blocks of 1,024 / 1,024 / 512 read
    4-7% better at 2,048 positions and at heads of 128: not taken, one
    set of blocks); at 1,024 positions it skips a quarter of the square
    under the causal mask, which a block of 1,024 cannot."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    def pick(seq):
        # ``attention_path`` admits only multiples of 128
        return next(b for b in (512, 256, 128) if seq % b == 0)

    bq, bk = pick(seq_q), pick(seq_kv)
    return BlockSizes(
        block_q=bq, block_kv=bk, block_kv_compute=bk,
        block_q_dkv=bq, block_kv_dkv=bk, block_kv_dkv_compute=bk,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=64)
def _splash_kernel(seq_q, seq_kv, heads, is_causal, interpret):
    """The splash kernel for one shape of call, built once a process:
    every layer of a model calls the SAME object, so a step of 24 layers
    traces one forward and one backward body."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    if is_causal:
        # bottom-right aligned, as ``_reference_attention``'s tril
        one = sa.CausalMask((seq_q, seq_kv), offset=seq_kv - seq_q)
    else:
        one = sa.FullMask((seq_q, seq_kv))
    # the mask's block tables must be arrays, not tracers of whichever
    # program happened to ask first
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha(
            sa.MultiHeadMask([one] * heads),
            block_sizes=_default_block_sizes(seq_q, seq_kv),
            head_shards=1, q_seq_shards=1, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "is_causal",
                                             "interpret"))
def _blockwise_attention(q, k, v, segment_ids, scale, is_causal,
                         interpret=False):
    """Online-softmax attention over blocks (the Pallas splash kernel):
    scores, the running maximum and sum in float32, probabilities
    rounded to the inputs' dtype only as the operand of ``P V``, a
    backward that recomputes a block's scores.  No ``[B, H, S, S]``
    tensor reaches HBM in either direction.  Jitted, so that every call
    site of one shape shares one traced body."""
    from jax.experimental.pallas.ops.tpu.splash_attention import SegmentIds
    kernel = _splash_kernel(q.shape[1], k.shape[1], q.shape[2], is_causal,
                            interpret)
    # the kernel takes [H, S, D] of one batch row and applies no scale:
    # the queries carry it, multiplied in float32 and rounded once (exact
    # where the scale is a power of two, as at heads of 64; at heads of
    # 128 the scale itself is not rounded to bf16, only the scaled
    # queries are, as the dense form rounds every score)
    qh = jnp.swapaxes((q.astype(jnp.float32) * scale).astype(q.dtype), 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    if segment_ids is None:
        out = jax.vmap(kernel)(qh, kh, vh)
    else:
        # packed sequences: block-diagonal masking INSIDE the kernel
        out = jax.vmap(kernel)(qh, kh, vh, SegmentIds(q=segment_ids,
                                                      kv=segment_ids))
    return jnp.swapaxes(out, 1, 2)


@primitive(name="scaled_dot_product_attention", nondiff=(3,))
def _sdpa(q, k, v, segment_ids=None, mask=None, scale=None,
          is_causal=False):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    path = attention_path(jax.default_backend(), q.shape[1], k.shape[1], d,
                          mask is not None, mesh_mod.program_devices(),
                          q.dtype)
    # chosen while the program is traced: one count a call site
    monitor.counter("nn.attention." + path,
                    "attention call sites traced on this path").inc()
    if path == "blockwise":
        return _blockwise_attention(q, k, v, segment_ids, float(scale),
                                    bool(is_causal))
    if segment_ids is not None:
        # dense form: derive the block-diagonal mask; combined with
        # causal inside _reference_attention
        mask = (segment_ids[:, :, None]
                == segment_ids[:, None, :])[:, None, :, :]
    return _reference_attention(q, k, v, mask, scale, is_causal)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None,
                                 segment_ids=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle layout).

    ``segment_ids`` [B, S] int32 (packed sequences): attention is
    blocked to same-segment pairs — via the blockwise kernel's native
    SegmentIds where ``attention_path`` picks it (no S×S tensor), a
    derived dense mask otherwise."""
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
