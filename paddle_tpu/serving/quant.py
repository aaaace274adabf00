"""Quantized serving — int8 weight codes and int8 KV block pools.

KV bytes are the HBM ceiling on concurrent slots (every block held is
a block another request cannot reserve) and weight bytes bound
steady-state decode throughput, yet the quantization package
(quantization/weight_only.py, quantization/int8.py) never reached the
serving Engine.  This module is the bridge, in two independent halves:

* ``Engine(weight_dtype="int8")`` relayouts the serving checkpoint
  through weight-only int8 (``relayout_weights_int8``): every
  transformer-block Linear becomes a ``WeightOnlyInt8Linear`` whose
  int8 codes + per-output-channel f32 scales are registered BUFFERS —
  so they ride the engine's ``b_list`` into every compiled hot path
  (fused decode, fused spec-verify, paged chunk prefill, the ragged
  Pallas window) as live traced arrays, exactly as sampling params
  do.  No retracing, one program per config; the dequant sits
  adjacent to each matmul so XLA folds it into the operand read
  (the Tensor Processing Primitives framing: quantize/dequantize as
  fusable per-block primitives, never a whole-tensor pre-pass).

* ``Engine(kv_dtype="int8")`` stores the paged K/V pools as int8
  codes with a PER-BLOCK PER-HEAD f32 scale in a parallel scale pool
  (``QuantKV``): quantization happens at block write inside the
  dispatch (``paged_insert`` — a touched-block read-modify-write),
  dequantization at gather adjacent to the attention contraction
  (``paged_gather`` / the scale-aware ragged kernel), and the whole
  pool is NEVER dequantized at once — the Ragged Paged Attention
  motivation for keeping the gather math dtype-aware.  One logical
  block costs ``bs*H*hd`` code bytes + ``H`` scale floats instead of
  ``bs*H*hd`` f32s, so the same ``kv_budget_mb`` holds ~4x the
  blocks on f32 checkpoints (~2x vs bf16), compounding with mesh
  sharding (mp x).

Quantization convention (shared with quantization/weight_only.py):
``amax = max(|x|)`` clamped to 1e-8, codes =
``round(clip(x, -amax, amax) / amax * 127)``, stored scale =
``amax / 127`` so dequant is ``codes * scale``.  Re-quantizing an
untouched block under its own scale is EXACT (codes round-trip), so
the steady-state read-modify-write only loses precision on the
one-time event of a block's amax actually growing.

Scale-pool invariants (the serving/kvcache.py contract, extended):
one scale row ``[H]`` per physical block per layer per K/V; scales
travel WITH their block everywhere a block moves (copy-on-write,
export/import over the migration wire); shared (prefix-cache /
adopted) blocks are never re-quantized — writes only ever land in a
slot's own fresh blocks, so a shared block's scale is immutable while
shared.  Freshly allocated blocks get their scale rows ZEROED
(``codes * 0 = 0`` nullifies any stale garbage) before first write —
see ``Engine._zero_fresh_scales`` for why codes need no zeroing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-8  # amax clamp, matching weight_only's quantizer


class QuantKV:
    """One layer's quantized K (or V) block pool: int8 ``codes``
    ``[NB, bs, H, hd]`` + f32 ``scale`` ``[NB, H]`` (per-block
    per-head dequant multiplier).  Registered as a jax pytree so it
    flows through the engine's existing ``k_pools`` / ``v_pools``
    lists — every compiled dispatch keeps its (donated) pool
    arguments and signatures unchanged.  ``.shape`` / ``.dtype``
    proxy the codes array: callers that only read pool geometry
    (``k_pools[0].shape[1]`` for the block size) work on both forms.
    """

    __slots__ = ("codes", "scale")

    def __init__(self, codes, scale):
        self.codes = codes
        self.scale = scale

    @property
    def shape(self):
        return self.codes.shape

    @property
    def dtype(self):
        return self.codes.dtype

    def __repr__(self):
        return (f"QuantKV(codes={getattr(self.codes, 'shape', None)}, "
                f"scale={getattr(self.scale, 'shape', None)})")


jax.tree_util.register_pytree_node(
    QuantKV,
    lambda p: ((p.codes, p.scale), None),
    lambda _, leaves: QuantKV(*leaves))


def quantize_blocks(vals):
    """Whole-block quantize: f32 ``[n, bs, H, hd]`` -> (int8 codes,
    f32 scale ``[n, H]``) with a FRESH per-block per-head scale.
    Used where whole blocks are produced at once (the monolithic
    paged prefill's tail scatter, tests) — zero pad rows cannot
    inflate the amax, so a padded partial block quantizes its real
    rows at full precision."""
    vals = vals.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(vals), axis=(1, 3)), _EPS)
    scale = amax / 127.0                                   # [n, H]
    q = jnp.round(jnp.clip(vals, -amax[:, None, :, None],
                           amax[:, None, :, None])
                  / amax[:, None, :, None] * 127.0)
    return q.astype(jnp.int8), scale


def dequantize_blocks(codes, scale):
    """int8 ``[..., bs, H, hd]`` x f32 ``[..., H]`` -> f32 blocks
    (``codes * scale``, broadcast over rows and head_dim)."""
    return codes.astype(jnp.float32) * scale[..., None, :, None]


def paged_gather(pool, block_tables):
    """Dequantized logical rows for a batch of block tables:
    ``pool`` QuantKV, ``block_tables`` int32 ``[B, nbt]`` ->
    f32 ``[B, nbt*bs, H, hd]``.  The dequant multiplies the GATHERED
    blocks only — never the whole pool — and sits adjacent to the
    attention contraction so XLA fuses it into the operand read."""
    c = pool.codes[block_tables]            # [B, nbt, bs, H, hd]
    s = pool.scale[block_tables]            # [B, nbt, H]
    kf = c.astype(jnp.float32) * s[:, :, None, :, None]
    B = block_tables.shape[0]
    return kf.reshape(B, -1, c.shape[3], c.shape[4])


def paged_insert(pool, blk, off, vals):
    """Insert per-lane rows into a quantized block pool — the
    TOUCHED-BLOCK read-modify-write that keeps quantization at block
    granularity under incremental decode writes:

    1. gather each lane's target block (codes + scale), dequantize;
    2. overwrite the written rows.  Lanes sharing one physical block
       (a verify window spanning a block, parked slots on the scratch
       block) are ALL folded into EVERY copy of that block via a
       same-block x one-hot(row) selection, so duplicate copies are
       identical and the scatter-back's last-write-wins is
       deterministic;
    3. recompute the per-block per-head amax scale and requantize the
       WHOLE block.  Untouched rows round-trip exactly under an
       unchanged scale; a grown amax is a one-time precision step for
       the block's older rows.

    ``pool``: QuantKV; ``blk``/``off``: int32 ``[N]`` physical block
    and in-block row per lane; ``vals``: ``[N, H, hd]`` lane rows.
    Returns a new QuantKV.  Masked/parked lanes must be pre-routed to
    the scratch block (blk 0, off 0) by the caller — the same
    one-masking-rule contract as the fp scatter paths."""
    codes, scale = pool.codes, pool.scale
    bs = codes.shape[1]
    vals = vals.astype(jnp.float32)
    kf = dequantize_blocks(codes[blk], scale[blk])   # [N, bs, H, hd]
    # sel[i, j, r]: lane j writes row r of lane i's block copy
    sel = (blk[None, :] == blk[:, None])[:, :, None] \
        & (off[None, :, None] == jnp.arange(bs)[None, None, :])
    written = jnp.any(sel, axis=1)                   # [N, bs]
    ins = jnp.einsum("ijr,jhd->irhd", sel.astype(jnp.float32), vals)
    kf = jnp.where(written[:, :, None, None], ins, kf)
    q, s = quantize_blocks(kf)
    return QuantKV(codes.at[blk].set(q), scale.at[blk].set(s))


def _iter_block_linears(model):
    """Yield ``(path, layer)`` for every plain ``nn.Linear`` inside
    the model's transformer blocks (embeddings / lm_head excluded —
    weight-only serving quantizes the bandwidth-bound block matmuls
    and leaves the tied embedding table alone)."""
    from .. import nn
    from ..quantization.weight_only import WeightOnlyInt8Linear
    for bi, block in enumerate(model.serving_linear_stacks()):
        stack = [(f"blocks[{bi}]", block)]
        while stack:
            prefix, layer = stack.pop()
            for name, child in layer.named_children():
                path = f"{prefix}.{name}"
                if isinstance(child, WeightOnlyInt8Linear):
                    continue
                if isinstance(child, nn.Linear):
                    yield path, child
                else:
                    stack.append((path, child))


def relayout_weights_int8(model, compute_dtype=None):
    """Validate, then relayout every transformer-block Linear of a
    serving checkpoint through weight-only int8
    (quantization/weight_only.py math: per-output-channel abs-max
    codes, no calibration).  Validation runs FIRST over the whole
    model and raises a ``ValueError`` NAMING the offending layer —
    the old failure mode surfaced ``WeightOnlyInt8Linear``'s generic
    shape error from deep inside the relayout loop, after earlier
    layers were already swapped, leaving the model half-quantized.
    Returns the number of relayouted layers."""
    todo = list(_iter_block_linears(model))
    for path, lin in todo:
        w = getattr(lin, "weight", None)
        data = getattr(w, "_data", None)
        if data is None or data.ndim != 2 \
                or not jnp.issubdtype(data.dtype, jnp.floating):
            got = (f"shape {list(data.shape)} dtype {data.dtype}"
                   if data is not None else "no weight")
            raise ValueError(
                f"weight_dtype='int8' cannot relayout layer {path}: "
                f"{got} — weight-only int8 codes need a 2-D floating "
                "[in, out] Linear weight (conv/other kernels need "
                "quantization.int8's calibrated forms)")
    if not todo:
        raise ValueError(
            "weight_dtype='int8' found no Linear layers in the "
            "model's blocks to relayout — the tensor-parallel einsum "
            "form (use_mp=True) and pre-quantized models have "
            "nothing to code")
    from ..quantization.weight_only import quantize_weights_int8
    for block in model.serving_linear_stacks():
        quantize_weights_int8(block, compute_dtype=compute_dtype)
    return len(todo)
