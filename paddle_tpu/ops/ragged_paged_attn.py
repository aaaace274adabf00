"""Ragged paged attention — ONE Pallas kernel for every paged window.

The serving engine's paged attention used to be pure-XLA gather /
scatter through block tables, with the window width baked into each
compiled program's SHAPE: a one-token decode tick (S=1), a k-wide
speculative verify (S=k+1), and a chunked-prefill window (S=C) each
compiled their own executable, so the engine carried a program matrix
of roughly one entry per (layout, chunk shape, spec_k).  This module
is the kernel-level fix, grounded in "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU"
(PAPERS.md, arxiv 2604.15464): per-slot positions, window widths, and
block tables become kernel *data* instead of trace-time *shape* —

* the grid runs over SLOTS; each program instance walks its slot's
  block table (a kv-block loop inside the instance) against the
  shared physical pools,
* ``pos[b]`` (the slot's window start) drives the causal mask, so a
  short slot is masked by its length instead of padded to the pool's,
* ``width[b]`` says how many of the W query lanes are REAL this tick —
  a decode lane uses 1, a spec-verify lane k+1, a prefill-chunk lane
  its chunk length, and a parked slot 0 (its output lanes are zeroed,
  never read) — so mixed prefill-chunk + decode + spec traffic shares
  ONE program whose static width is just the engine's maximum.

STREAMING: the body is a flash-style ONLINE-SOFTMAX loop over STEPS
of ``pages_per_step`` pages (32 pages of 16 rows: 512 rows, 2 MB of K
and 2 MB of V a step at ``gpt3-1.3b``'s 16 heads of 128 in bf16).  A
slot walks ``ceil(live blocks / pages)`` steps, where its live blocks
end at the causal horizon ``ceil((pos + width) / block_size)``, so a
decode tick touches only the blocks that actually hold history and a
parked slot (width 0) costs its grid step and nothing else: no copy,
no arithmetic, zeroed output lanes.  Each step carries a per-(head,
lane) running max ``m``, normalizer ``l`` and an output accumulator
``acc`` rescaled by ``exp(m_old - m_new)`` — the standard
flash-attention recurrence.  The working set is the step's rows and an
``[W, step rows]`` score tile a head at a time, *independent of context
length*: multi-thousand-token contexts are not VMEM-bounded and the
compiled program stays O(1) in size.

COPIES IN FLIGHT UNDER THE ARITHMETIC: two VMEM buffers a pool, each a
step's rows ``[rows, H, hd]``.  A page is one contiguous copy (``[bs,
H, hd]`` rows: 64 KB at 16 x 16 x 128 bf16), and a step copies only
the pages up to the slot's last live block.  The copies of the NEXT
step (the same slot's next pages, or the first pages of the next LIVE
slot: the slots' order, their step counts and each step's buffer are
scalar-prefetched data computed from ``pos`` and ``width``) are
started before the present step's arithmetic and waited on only where
they are read, at the top of their own step; the first live slot's
first copies go out at grid step 0, and the last live slot's last step
has no successor and starts none.  ``vmem_limit_bytes`` is raised to
the four buffers plus the arithmetic's room (``_vmem_limit``: 8 MiB of
buffers, a limit of ~25 MiB at ``gpt3-1.3b``'s widths; the chip has
128 MiB).

WHAT IS FLOAT32.  Rows are contracted AS STORED: bf16 queries over bf16
rows go to the matrix unit as they are with float32 accumulation (a
product of two bf16 values is exact in float32), K and V are never
upcast in HBM and any other pool type is upcast in VMEM at most.  In
``[rows, H, hd]`` a head's rows lie one sublane apart in every tile:
a head is loaded STRIDED over the ``[rows * H, hd]`` view, bf16 pools
two heads at a time as 32-bit words cut apart by a shift and a mask
(``_heads``).  Scores, the running ``m`` / ``l`` / ``acc`` and the
probabilities are float32 throughout: over bf16 rows the probabilities
enter the value product as THREE bf16 terms (24 bits of mantissa)
stacked along the window, so the rows pass the matrix unit once and no
single bf16 pass rounds the weights.  Nothing multiplies the body by
heads x pages: heads are unrolled (16), a step's pages are a loop of
copies.

ONE QUERY ROW A SLOT (a decode step) leaves the matrix unit one row in
sixteen to work on while every K and V tile still has to be loaded as
a weight, and those loads bound a step, not the copies (PERF.md §5,
PR 47).  The row on the VECTOR unit instead (all heads at once in the
tiles' own layout, lane reductions) was written and timed there and
was slower at every trip size tried, so every window keeps the matrix
unit.

NUMERICS CONTRACT: online softmax reorders float summation (block-
sequential accumulation instead of one reduction over L), so the
kernel is **allclose** to the XLA oracle (``GPTAttention._slot_attn``)
— not bitwise — and the engine-level guarantee follows: greedy streams
are asserted TOKEN-IDENTICAL to the XLA oracle end-to-end across the
full layout matrix (paged x plain/chunked/spec x depth 1+2 x int8 KV x
adapter lanes; tests/test_ragged_attn.py), while seeded streams are
asserted deterministic (same seed => same stream).

WHERE IT RUNS.  Interpret mode is chosen on the ``cpu``
platform only (``_auto_interpret``); on every other backend the
kernel goes through Mosaic and either compiles or raises — there is
no fallback.  ``pos``, ``width``, the block tables and the slots'
order are scalar-prefetched to SMEM (``PrefetchScalarGridSpec``), both
pools stay in HBM (``memory_space=ANY``), the grid runs over slots in
order.  It needs ``head_dim % 128 == 0`` (a page copy moves whole lane
tiles); libtpu 0.0.34 compiles it for ``TPU v5 lite`` at f32 / bf16 /
int8 pools, H in {4, 8, 16}, block sizes 8-32 and windows up to 512
(``compile_check``; tests/test_ragged_attn.py pins it through the
compile-only topology).  TWO CALLERS: ``GPTAttention._slot_attn`` takes
it as the attention core of the decode and verify programs wherever
``models/gpt.py`` ``slot_attn_core`` says so (paged floating-point
pools, heads of 128, one TPU), and the ``attn_impl="ragged"`` tick
runs every window through it.  On the v5e a step is bound by the
matrix unit's weight loads (every K and V tile is a weight once; 5.0 us
a step of 512 rows, its copies 5.2 us at 806 GB/s): PERF.md §5, PR 47.

K/V WRITES stay outside the kernel (the callers' width-masked scatter
— see ``GPTAttention.ragged_window_paged``): lanes past ``width[b]``
land in the slot's own dp shard's SCRATCH block (physical row 0 on an
unsharded engine), which is how the scratch-block and spec-margin
invariants documented in serving/kvcache.py move from per-path code
into one masking rule.

SHARDED LOWERING (``sharded_ragged_paged_attention``): GSPMD cannot
partition a Mosaic-path ``pallas_call`` (the non-interpret TPU
lowering is opaque to the SPMD partitioner), so a 2-D ``(mp, dp)``
serving mesh runs the kernel under ``shard_map``: each mesh shard
executes its OWN grid over its ``B/dp`` slots, with the head axis
pre-sliced per 'mp' shard and each dp shard holding its contiguous
range of pool rows.  Per-slot ``(pos, width, block_table)`` stay
DATA — tables carry global block ids and the wrapper localizes them
by subtracting the shard's row offset (``axis_index('dp') *
blocks_per_shard``), which is exact because the engine's admission
gate only ever hands a slot blocks from its own shard's range.
On the forced CPU mesh (interpret mode) it is asserted
token-identical to the GSPMD-partitioned XLA oracle across the
serving layout matrix (tests/test_sharded_serving.py).
"""
from __future__ import annotations

import functools
import math


def _auto_interpret(platform=None):
    """Pallas interpret mode on the ``cpu`` platform only (tier-1 runs
    the kernel logic there token-for-token against the XLA oracle).
    Every other backend compiles through Mosaic or raises: a missing
    chip or an unsupported shape must never turn into a silent
    interpreted run."""
    if platform is None:
        import jax
        platform = jax.default_backend()
    return platform == "cpu"


# cache rows one step of the kernel's walk holds in VMEM for each pool:
# 32 pages of 16 rows.  A step's products are bound by the matrix unit's
# weight loads (every K and V tile is a weight once), and those cost
# less a row the more rows a step holds: 4.7 us a step of 256 rows,
# 5.0 us a step of 512 on the v5e (PERF.md, PR 47).
_STEP_ROWS = 512

def pages_per_step(block_size, blocks_per_slot):
    """Pages one step of the kernel holds for each pool: ``_STEP_ROWS``
    rows' worth, at least one, at most the table."""
    return max(1, min(_STEP_ROWS // int(block_size), int(blocks_per_slot)))


def stream_rows(pos, ahead, table_rows, block_size):
    """Host twin of the kernel's fetch, for the engine's counters
    (``ServingSpec.decode_rows``): the cache rows one dispatch copies
    into VMEM over all slots when slot b's window ends at ``pos[b] +
    ahead``: every live slot's own blocks up to the one that holds its
    window's last row (a step's pages past it are not copied), nothing
    for a parked slot (position 0)."""
    import numpy as np
    pos = np.asarray(pos, np.int64)
    bs = int(block_size)
    live = pos[pos > 0]
    return int((-(-np.minimum(live + ahead, table_rows) // bs)).sum()) * bs


def _heads(buf):
    """Head after head of a VMEM buffer ``[R, H, hd]``, each ``[R,
    hd]``: loads strided over the rows of the ``[R * H, hd]`` view (a
    row's heads lie one sublane apart in every tile).  bfloat16 pools
    are read as 32-bit words, two heads a word, and the pair is cut
    apart with a shift and a mask: a bfloat16 is the upper half of the
    float32 of the same value."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    R, H, hd = buf.shape
    if buf.dtype != jnp.bfloat16 or H % 2:
        for h in range(H):
            yield buf[:, h, :]
        return
    words = buf.reshape(R * H, hd).bitcast(jnp.uint32)
    for pair in range(H // 2):
        word = words[pair::H // 2, :]                   # [R, hd] uint32
        for half in (word << 16, word & jnp.uint32(0xFFFF0000)):
            yield pltpu.bitcast(half, jnp.float32).astype(jnp.bfloat16)


def _stream_impl(q, k_flat, v_flat, block_tables, pos, width,
                 block_size, interpret, k_scale=None, v_scale=None,
                 pages=None):
    """Flash-style online-softmax streaming kernel (module docstring):
    a loop over the slot's live blocks, ``pages`` K and V pages copied
    HBM -> VMEM a step into one of two buffers a pool while the other
    is contracted, running (m, l, acc) in VMEM scratch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, W, H, hd = q.shape
    nb = block_tables.shape[1]
    bs = block_size
    P = pages or pages_per_step(bs, nb)
    R = P * bs
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    if not interpret and hd % 128:
        raise ValueError(
            f"ragged_paged_attention: head_dim={hd} is not aligned to "
            "tiling: a page copy moves rows of whole 128-lane tiles")
    # head-major query / output blocks [1, H, Wp, hd]: each head's
    # [Wp, hd] window is then a plain 2-D tile.  Wp pads the window to
    # whole sublane tiles of q's dtype; the pad lanes are >= width, so
    # they are zeroed like any other masked lane and sliced off below.
    tile = 8 * max(1, 4 // q.dtype.itemsize)
    Wp = -(-W // tile) * tile
    qt = jnp.swapaxes(q, 1, 2)
    if Wp != W:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))
    # rows contracted as stored: a product of two bf16 values is exact
    # in f32, so bf16 queries over bf16 rows go to the matrix unit as
    # they are, with f32 accumulation; anything else contracts in f32
    exact = q.dtype == k_flat.dtype == jnp.bfloat16

    # what the kernel needs of the slots' order, as data: steps a slot
    # walks, the steps walked before it (which of the two buffers a
    # step uses is its parity in the whole call's sequence of steps, so
    # the copies for a slot's first step can be started by the slot
    # before it) and the first live slot at or after each index
    live_blocks = jnp.minimum(nb, (pos + jnp.maximum(width, 1) - 1) // bs
                              + 1)
    live_blocks = jnp.where(width > 0, live_blocks, 0).astype(jnp.int32)
    steps = -(-live_blocks // P)
    before = jnp.cumsum(steps) - steps
    idx = jnp.where(width > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.concatenate([
        jax.lax.cummin(idx, axis=0, reverse=True),
        jnp.full((1,), B, jnp.int32)])                       # [B + 1]

    def kernel(tables_ref, blocks_ref, steps_ref, before_ref, nxt_ref,
               pos_ref, width_ref, *rest):
        if quant:
            ks_ref, vs_ref, *rest = rest
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, m_ref, l_ref, acc_ref, sem) = rest
        b = pl.program_id(0)
        p = pos_ref[b]
        w = width_ref[b]
        n_steps = steps_ref[b]

        def each_page(slot, j, buf, act):
            # one copy a page and pool: physical block ids are runtime
            # data read from SMEM.  A step's pages past the slot's last
            # live block are not copied: what the buffer holds there
            # (an earlier step's rows, or the zeros it starts with)
            # lies past every query's horizon
            def page(i, carry):
                rows = pl.ds(tables_ref[slot, j * P + i] * bs, bs)
                dst = pl.ds(i * bs, bs)
                act(pltpu.make_async_copy(
                    k_hbm.at[rows], k_buf.at[buf, dst], sem.at[0, buf]))
                act(pltpu.make_async_copy(
                    v_hbm.at[rows], v_buf.at[buf, dst], sem.at[1, buf]))
                return carry
            jax.lax.fori_loop(
                0, jnp.minimum(blocks_ref[slot] - j * P, P), page, 0)

        def start(slot, j, buf):
            each_page(slot, j, buf, lambda c: c.start())

        @pl.when(b == 0)
        def _():
            # masked weights are exact zeros, and zero times what a
            # buffer holds before its first copy must be zero too
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

            @pl.when(nxt_ref[0] < B)
            def _():
                start(nxt_ref[0], 0, 0)

        s_ids = jax.lax.broadcasted_iota(jnp.int32, (Wp, R), 0)
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (Wp, R), 1)

        def page_scales(ref, j, h):
            # a [1, R] row of the step's per-page multipliers of head h
            out = jnp.zeros((1, R), jnp.float32)
            page = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) // bs
            for i in range(P):
                col = jnp.minimum(j * P + i, nb - 1)
                out = jnp.where(page == i, ref[b, col * H + h], out)
            return out

        def body(j, carry):
            buf = (before_ref[b] + j) % 2
            # the next step's copies go out before this step's rows
            # are touched: the slot's own next pages, or the first
            # pages of the next live slot
            last = j + 1 == n_steps
            to = jnp.where(last, nxt_ref[b + 1], b)

            @pl.when(to < B)
            def _():
                start(to, jnp.where(last, 0, j + 1), 1 - buf)

            each_page(b, j, buf, lambda c: c.wait())
            # query lane s sees cache positions <= pos + s — the
            # slot's LENGTH does the masking, not a padded shape
            visible = (j * R + r_ids) <= (p + s_ids)         # [Wp, R]
            for h, (kh, vh) in enumerate(zip(_heads(k_buf.at[buf]),
                                             _heads(v_buf.at[buf]))):
                qh = q_ref[0, h]                             # [Wp, hd]
                if not exact:
                    qh = qh.astype(jnp.float32)
                    kh = kh.astype(jnp.float32)
                    vh = vh.astype(jnp.float32)
                sc = jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if quant:
                    # quantized pools dequantize PER STREAMED PAGE:
                    # the codes' scores times that page's per-head
                    # scale, the weights times it before the codes
                    sc = sc * page_scales(ks_ref, j, h)
                sc = jnp.where(visible, sc, -1e30)
                m = m_ref[h]                                 # [Wp, 1]
                new_m = jnp.maximum(
                    m, jnp.max(sc, axis=1, keepdims=True))
                # select by the mask, not just the -1e30 floor: a
                # fully masked tile must contribute EXACTLY zero mass
                # even while the running max is still at its -1e30
                # init (where exp(sc - new_m) would read exp(0) = 1)
                pj = jnp.where(visible, jnp.exp(sc - new_m), 0.0)
                corr = jnp.exp(m - new_m)
                l_ref[h] = l_ref[h] * corr \
                    + jnp.sum(pj, axis=1, keepdims=True)
                if quant:
                    pj = pj * page_scales(vs_ref, j, h)
                if exact:
                    # f32 weights over bf16 rows in ONE pass of the
                    # rows through the matrix unit: the weights split
                    # into three bf16 terms (24 bits of mantissa)
                    # stacked along the window, each term's products
                    # exact in the f32 accumulator
                    p1 = pj.astype(jnp.bfloat16)
                    r1 = pj - p1.astype(jnp.float32)
                    p2 = r1.astype(jnp.bfloat16)
                    p3 = (r1 - p2.astype(jnp.float32)).astype(
                        jnp.bfloat16)
                    pv = jax.lax.dot_general(
                        jnp.concatenate([p1, p2, p3], axis=0), vh,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    pv = pv[:Wp] + pv[Wp:2 * Wp] + pv[2 * Wp:]
                else:
                    pv = jax.lax.dot_general(
                        pj, vh, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                acc_ref[h] = acc_ref[h] * corr + pv
                m_ref[h] = new_m
            return carry

        # causal horizon: the last visible position is pos + width - 1.
        # Blocks past the horizon are fully masked, so skipping them
        # is EXACT — and it is what makes per-tick block walks O(live
        # context), not O(table length).  A parked slot (width 0)
        # walks nothing and costs its grid step: zeroed output lanes.
        @pl.when(w > 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            jax.lax.fori_loop(0, n_steps, body, 0)
            # width as data: lanes past this slot's real window are
            # zeroed
            lane = jax.lax.broadcasted_iota(jnp.int32, (Wp, 1), 0)
            for h in range(H):
                ctx = acc_ref[h] / l_ref[h]
                o_ref[0, h] = jnp.where(lane < w, ctx, 0.0).astype(
                    o_ref.dtype)

        @pl.when(w <= 0)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def slot_block(b, *_):
        return (b, 0, 0, 0)

    scalars = [block_tables, live_blocks, steps, before, nxt, pos, width]
    if quant:
        # per-slot scale rows, gathered through the tables out here so
        # the kernel reads each (block, head) multiplier as an SMEM
        # scalar: [B, nb * H]
        scalars += [k_scale[block_tables].reshape(B, nb * H),
                    v_scale[block_tables].reshape(B, nb * H)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, Wp, hd), slot_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, Wp, hd), slot_block),
            scratch_shapes=[
                pltpu.VMEM((2, R, H, hd), k_flat.dtype),
                pltpu.VMEM((2, R, H, hd), v_flat.dtype),
                pltpu.VMEM((H, Wp, 1), jnp.float32),
                pltpu.VMEM((H, Wp, 1), jnp.float32),
                pltpu.VMEM((H, Wp, hd), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        # the work is data (the slots' lengths); XLA's scheduler is told
        # a quarter of the tables, so that it knows the call is long
        # and keeps its own copies in flight across it
        cost_estimate=pl.CostEstimate(
            flops=B * nb * bs * H * hd * Wp,
            bytes_accessed=B * nb * bs * H * hd * k_flat.dtype.itemsize // 2,
            transcendentals=B * nb * bs * H * Wp // 4),
        compiler_params=pltpu.CompilerParams(
            # the slots run in order: a slot starts its successor's
            # first copies
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(R, H, hd, Wp,
                                         k_flat.dtype.itemsize)),
        interpret=interpret,
        name="ragged_paged_attn_stream",
    )(*scalars, qt, k_flat, v_flat)
    return jnp.swapaxes(out[:, :, :W], 1, 2)


@functools.lru_cache(maxsize=None)
def _stream_jit():
    """``_stream_impl`` as ONE jitted function: a program that calls
    the kernel in each of its layers at the same shapes traces the body
    and lowers it to Mosaic once, and every layer calls that one
    function.  Traced and lowered a call, 24 layers of ``gpt3-1.3b``
    cost ~27 s of Python at every start of the process, cache or no
    cache: the persistent cache is keyed by the lowered module
    (PERF.md §5, PR 47)."""
    import jax
    return jax.jit(_stream_impl,
                   static_argnames=("block_size", "interpret", "pages"))


def _vmem_limit(step_rows, heads, head_dim, window, itemsize):
    """Scoped VMEM the kernel asks for: its four page buffers (two a
    pool), and room for one head's rows, scores and weights at a time
    beside the compiler's own; at ``gpt3-1.3b``'s widths and 32 pages a
    step 8 MiB of buffers and a limit of 25 MiB."""
    buffers = 4 * step_rows * heads * head_dim * itemsize
    per_head = 4 * step_rows * (4 * head_dim + 8 * window)
    return int(min(buffers + per_head + (16 << 20), 100 << 20))


def ragged_paged_attention(q, k_flat, v_flat, block_tables, pos, width,
                           *, block_size, interpret=None,
                           k_scale=None, v_scale=None, pages=None):
    """Ragged paged attention over a slot pool (see module docstring).

    q : [B, W, H, hd] query window per slot (W = the engine's static
        maximum window; real lanes per slot are ``width[b]``).
    k_flat / v_flat : [num_blocks * block_size, H, hd] — the paged
        pools flattened to physical rows (writes already scattered).
        With ``k_scale``/``v_scale`` these are int8 CODE rows.
    block_tables : int32 [B, L // block_size] physical block per
        logical block (row 0 = the scratch block for parked slots).
    pos : int32 [B] window start per slot (tokens already cached).
    width : int32 [B] real query lanes this tick (0 = parked; output
        lanes >= width are zeroed).
    k_scale / v_scale : optional f32 [num_blocks, H] per-block
        per-head dequant multipliers (``Engine(kv_dtype="int8")``):
        the kernel dequantizes each block in-loop — codes times the
        block's scale row, adjacent to the contraction — so the
        logical K/V row never materializes outside VMEM and the whole
        pool is never dequantized.  Pass both or neither.
    pages : pages a step copies for each pool (``pages_per_step``
        where None: 512 rows' worth).
    Returns ctx [B, W, H, hd] in q's dtype.
    """
    import jax.numpy as jnp

    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "ragged_paged_attention: pass both k_scale and v_scale "
            "(quantized pools) or neither (fp pools)")
    if interpret is None:
        interpret = _auto_interpret()
    if k_scale is not None:
        k_scale = jnp.asarray(k_scale, jnp.float32)
        v_scale = jnp.asarray(v_scale, jnp.float32)
    return _stream_jit()(
        q, k_flat, v_flat,
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(width, jnp.int32),
        block_size=int(block_size), interpret=bool(interpret),
        k_scale=k_scale, v_scale=v_scale, pages=pages)


def compile_check(*, num_slots, window, num_heads, head_dim,
                  block_size, blocks_per_slot, num_blocks, dtype,
                  quant=False, device=None):
    """Lower and compile the kernel through Mosaic at one shape,
    running nothing; raises the compiler's own error when it refuses.

    ``device`` defaults to ``jax.devices()[0]``.  A compile-only
    device works too (``jax.experimental.topologies.
    get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    .devices[0]``), which is how a CPU-only sandbox iterates on
    Mosaic errors.  ``Engine`` calls this at construction on every
    non-``cpu`` backend, with its per-shard shapes, so an unsupported
    shape fails there with the compiler's message instead of
    inside the first tick."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device or jax.devices()[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    B, H, hd = num_slots, num_heads, head_dim
    pool = spec((num_blocks * block_size, H, hd),
                jnp.int8 if quant else dtype)
    args = [spec((B, window, H, hd), dtype), pool, pool,
            spec((B, blocks_per_slot), jnp.int32),
            spec((B,), jnp.int32), spec((B,), jnp.int32)]
    if quant:
        args += [spec((num_blocks, H), jnp.float32)] * 2

    def run(q, k, v, tables, pos, width, *scales):
        ks, vs = scales if scales else (None, None)
        return ragged_paged_attention(
            q, k, v, tables, pos, width, block_size=block_size,
            interpret=False, k_scale=ks, v_scale=vs)

    jax.jit(run).lower(*args).compile()


def sharded_ragged_paged_attention(q, k_flat, v_flat, block_tables,
                                   pos, width, *, block_size,
                                   mesh=None, interpret=None,
                                   k_scale=None, v_scale=None):
    """``shard_map``-partitioned ragged paged attention over a 2-D
    ``(mp, dp)`` serving mesh (module docstring, SHARDED LOWERING).

    Same contract as ``ragged_paged_attention`` plus ``mesh`` (a jax
    Mesh with 'mp'/'dp' axes; defaults to the process-global serving
    mesh, ``distributed.mesh.get_mesh()``).  Each mesh shard runs its
    own kernel grid over the ``B/dp`` slots it owns:

    * q [B, W, H, hd] shards ``P('dp', None, 'mp', None)`` — slot rows
      over 'dp', whole heads pre-sliced over 'mp';
    * k_flat/v_flat [NB*bs, H, hd] shard ``P('dp', 'mp', None)`` —
      each dp shard's contiguous pool-row range, its heads' slice;
    * block_tables [B, L//bs] shard ``P('dp', None)`` and carry GLOBAL
      block ids — the body localizes them by subtracting
      ``axis_index('dp') * blocks_per_shard`` (exact: the engine's
      admission gate allocates a slot's blocks only from its own
      shard's range, serving/kvcache.py BlockPool(shards=...));
    * pos/width [B] shard ``P('dp')``; scales [NB, H] shard
      ``P('dp', 'mp')``.

    The per-shard body is the UNchanged kernel.  GSPMD cannot
    partition a Mosaic ``pallas_call``, so on a TPU mesh this wrapper
    is the only way to run the kernel; on the forced CPU mesh it
    partitions the interpret-mode lowering the same way, which is
    what the dp parity tests pin.  Output shards like q.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if mesh is None:
        from ..distributed import mesh as mesh_mod
        mesh = mesh_mod.get_mesh()
    if mesh is None:
        raise ValueError(
            "sharded_ragged_paged_attention needs a mesh: pass mesh=..."
            " or set the process-global serving mesh "
            "(distributed.mesh.set_mesh / Engine(mesh=...))")
    dp = int(mesh.shape.get("dp", 1))
    mp = int(mesh.shape.get("mp", 1))
    B, W, H, hd = q.shape
    rows = k_flat.shape[0]
    bs = int(block_size)
    if B % dp or (rows // bs) % dp:
        raise ValueError(
            f"sharded ragged kernel: B={B} slots and "
            f"{rows // bs} pool blocks must both divide by the mesh's "
            f"dp degree ({dp})")
    if H % mp:
        raise ValueError(
            f"sharded ragged kernel: H={H} heads must divide by the "
            f"mesh's mp degree ({mp}) — attention shards whole heads")
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            "sharded_ragged_paged_attention: pass both k_scale and "
            "v_scale (quantized pools) or neither (fp pools)")
    quant = k_scale is not None
    if interpret is None:
        interpret = _auto_interpret()
    interpret = bool(interpret)

    def body(q_l, k_l, v_l, tables_l, pos_l, width_l, *scales):
        # tables hold GLOBAL block ids; this shard's pool slice starts
        # at row offset axis_index('dp') * blocks_per_shard
        nb_local = k_l.shape[0] // bs
        local = tables_l - jax.lax.axis_index("dp") * nb_local
        ks, vs = scales if scales else (None, None)
        return ragged_paged_attention(
            q_l, k_l, v_l, local, pos_l, width_l, block_size=bs,
            interpret=interpret, k_scale=ks, v_scale=vs)

    qspec = P("dp", None, "mp", None)
    kvspec = P("dp", "mp", None)
    in_specs = [qspec, kvspec, kvspec, P("dp", None), P("dp"),
                P("dp")]
    args = [q, k_flat, v_flat,
            jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(pos, jnp.int32),
            jnp.asarray(width, jnp.int32)]
    if quant:
        in_specs += [P("dp", "mp"), P("dp", "mp")]
        args += [jnp.asarray(k_scale, jnp.float32),
                 jnp.asarray(v_scale, jnp.float32)]
    fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=qspec, check_vma=False)
    return fn(*args)
