"""One trip of the grouped-query decode walk as ONE Pallas kernel.

``models/sdar_moe.py`` ``GQAttention.attend`` walks its slots' cached
rows as a work list of (slot, chunk) items, ``walk_group`` items a trip
of a device loop (``models/programs.py`` ``walk_plan``).  In XLA a trip
is a gather of the items' pages AND THEN five small float32 fusions in
sequence with it (scores, weights, context products, the fold), so it
costs their sum.  Here the trip's body is one kernel that keeps the
NEXT item's page copies in flight under the PRESENT item's products, so
a trip costs the larger of the two; the loop over trips, with its data
trip count, stays XLA's (a grid cannot be a data trip count).

WHAT A TRIP DOES.  The pool stays in HBM (``memory_space=ANY``), read
as the flat rows ``[NB * bs, W]``, a row K and V of its K heads
head-major (``[K][k|v][hd]``).  The trip's items are scalar-prefetched
as ONE int32 vector (``trip_meta``: an item's page ids, its slot, its
first row, its slot's ``pos``, and how many items of the list are left
from it on).  Two VMEM buffers of one item's rows ``[chunk, W]``: item
i + 1's page copies (one contiguous ``[bs, W]`` copy a page) are
started before item i's arithmetic and waited on at the top of item
i + 1.  Items past the list's end (the last trip's padding) copy
nothing and cost nothing: the valid items are a prefix of the trip.

THE ORDER OF AN ITEM'S ARITHMETIC is what makes it fast: all K score
products first, ONE softmax step over the heads' stacked scores
``[K R, rows]``, then all K value products and one store of the run's
state.  A head at a time (product, max, exp, sum, product, fold, next
head) puts every product's latency on the critical path and read 42.7
us a trip of 16 items where this order reads 12.8 (K = 8; PERF.md §5,
PR 48).  The page copies are unrolled: a loop of copies is a block of
scalar work that nothing is scheduled beside (+7.5 us a trip of 32
items at 32 KB pages).  On the v5e a trip then ends at 1.02 x
(rows of 2,048) to 1.21 x (rows of 1,024) its copies' own time.

For each K/V head k the ``g x S`` query rows of that head go to the
matrix unit as they are against ``K = rows[:, 2k hd : (2k+1) hd]``
(a static 128-lane slice of the buffer; bf16 x bf16, float32
accumulation), the mask comes from data (``at < pos``; under a
``reach``, ``at > pos + s - reach`` for query row s), and the context
over ``V = rows[:, (2k+1) hd : (2k+2) hd]`` takes the float32 weights
as three bf16 terms stacked along the rows (``ops/ragged_paged_attn.py``
does the same: the rows pass the matrix unit once and no bf16 pass
rounds the weights).

WHERE THE FOLD LIVES.  The slots' running ``(top, den, acc)`` are ONE
float32 array ``[B, K R, hd + 128]`` (``pack_state``: lanes ``..hd``
the accumulator, lane ``hd`` the running maximum, lane ``hd + 1`` the
denominator) that the call takes aliased in and out.  The items lie
slot by slot in chunk order, so a trip is a few RUNS of one slot's
items: a run's state (and its slot's queries) is copied into one of
two VMEM carries when the run before it ends, every item of the run
folds into it there (the flash recurrence), and it is copied back when
the run ends, under the next run's arithmetic.  No partial leaves the
kernel and XLA folds nothing.

WHERE IT RUNS.  ``models/programs.py`` ``slot_attn_core`` is the rule
(one TPU, paged floating-point pools, heads of whole 128-lane tiles,
several slots, a table longer than one chunk); everywhere else the XLA
trip runs.  Interpreted on the ``cpu`` platform only, compiled by
Mosaic or refused everywhere else (``compile_check``).  Allclose to
the XLA trip, not bitwise: a run folds item by item where XLA folds a
trip's items at once.
"""
from __future__ import annotations

import functools
import math

from .ragged_paged_attn import _auto_interpret

# lanes the running maximum and denominator take beside the accumulator
_ML = 128
# what ``trip_meta`` keeps of an item after its page ids
_SLOT, _ROW, _POS, _LEFT = range(4)


def state_rows(rows, dtype):
    """Query rows of a K/V head as the kernel holds them: ``g x S``
    padded to whole sublane tiles of the queries' dtype."""
    import numpy as np
    tile = 8 * max(1, 4 // np.dtype(dtype).itemsize)
    return -(-rows // tile) * tile


def pack_state(top, den, acc, rows):
    """The walk's running state as the kernel keeps it.  top, den
    [B, K, g, S]; acc [B, S, K, g, hd] -> float32 [B, K rows, hd +
    128], a head's ``rows`` stacked on the next one's, row ``gi * S +
    s`` of each (``state_rows`` pads)."""
    import jax.numpy as jnp
    B, K, g, S = top.shape
    hd = acc.shape[-1]
    flat = jnp.concatenate([
        jnp.transpose(acc, (0, 2, 3, 1, 4)).reshape(B, K, g * S, hd),
        top.reshape(B, K, g * S, 1), den.reshape(B, K, g * S, 1),
        jnp.zeros((B, K, g * S, _ML - 2), jnp.float32)], axis=-1)
    return jnp.pad(flat, ((0, 0), (0, 0), (0, rows - g * S), (0, 0))
                   ).reshape(B, K * rows, hd + _ML)


def unpack_state(state, K, g, S):
    """``pack_state``'s inverse: (den [B, K, g, S], acc [B, S, K, g,
    hd])."""
    import jax.numpy as jnp
    B = state.shape[0]
    hd = state.shape[-1] - _ML
    live = state.reshape(B, K, -1, hd + _ML)[:, :, :g * S]
    return (live[..., hd + 1].reshape(B, K, g, S),
            jnp.transpose(live[..., :hd].reshape(B, K, g, S, hd),
                          (0, 3, 1, 2, 4)))


def pack_queries(qg, rows):
    """qg [B, S, K, g, hd] -> [B, K rows, hd], row ``gi * S + s`` of
    each head's ``rows``."""
    import jax.numpy as jnp
    B, S, K, g, hd = qg.shape
    flat = jnp.transpose(qg, (0, 2, 3, 1, 4)).reshape(B, K, g * S, hd)
    return jnp.pad(flat, ((0, 0), (0, 0), (0, rows - g * S), (0, 0))
                   ).reshape(B, K * rows, hd)


def trip_meta(cols, slot_of, chunk_of, valid, pos, chunk):
    """The work list as the kernel reads it, one row an item: its page
    ids ``cols`` [N, pages], its slot, its first row, its slot's
    ``pos``, and the valid items from it on (<= 0: the list has
    ended).  int32 [N, pages + 4]: a trip is ``group`` rows."""
    import jax.numpy as jnp
    item = jnp.arange(slot_of.shape[0], dtype=jnp.int32)
    left = jnp.sum(valid, dtype=jnp.int32) - item
    return jnp.concatenate([
        cols, slot_of[:, None], (chunk_of * chunk)[:, None],
        pos[slot_of][:, None], left[:, None]],
        axis=1).astype(jnp.int32)


def _trip_impl(state, q, pool, meta, *, heads, steps, reach, block_size,
               pages, group, interpret):
    """One trip (module docstring).  state float32 [B, K R, hd + 128]
    (aliased to the result); q [B, K R, hd]; pool [NB * bs, W]; meta
    int32 [group * (pages + 4)]."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, R, hd = heads, q.shape[1] // heads, q.shape[2]
    W, bs, P, G, S = pool.shape[1], block_size, pages, group, steps
    n_rows = P * bs
    M = P + 4
    scale = 1.0 / math.sqrt(hd)
    if not interpret and hd % 128:
        raise ValueError(
            f"gq_walk_trip: head_dim={hd} is not aligned to tiling: a "
            "head is a slice of whole 128-lane tiles of the fetched rows")
    # rows contracted as stored: a product of two bf16 values is exact
    # in f32; anything else contracts in f32
    exact = q.dtype == pool.dtype == jnp.bfloat16

    def kernel(meta_ref, _state_in, q_hbm, pool_hbm, state_ref,
               buf, qbuf, carry, sem_pages, sem_load, sem_store):
        n = jnp.clip(meta_ref[P + _LEFT], 0, G)

        def field(i, f):
            return meta_ref[i * M + P + f]

        def each_page(i, b, act):
            # unrolled: a loop of copies is a block of scalar work that
            # nothing else is scheduled beside (0.6 us an item of 16
            # pages on the v5e: PERF.md §5, PR 48)
            for j in range(P):
                act(pltpu.make_async_copy(
                    pool_hbm.at[pl.ds(meta_ref[i * M + j] * bs, bs)],
                    buf.at[b, pl.ds(j * bs, bs)], sem_pages.at[b]))

        def load(slot, r, act):
            # a run's state and its slot's queries, into carry r
            act(pltpu.make_async_copy(
                state_ref.at[slot], carry.at[r], sem_load.at[0, r]))
            act(pltpu.make_async_copy(
                q_hbm.at[slot], qbuf.at[r], sem_load.at[1, r]))

        def store(slot, r, act):
            act(pltpu.make_async_copy(
                carry.at[r], state_ref.at[slot], sem_store.at[r]))

        def bit(mask, r):
            return (mask >> r) & 1 == 1

        @pl.when(n > 0)
        def _():
            each_page(0, 0, lambda c: c.start())
            load(field(0, _SLOT), 0, lambda c: c.start())

        # every head's query rows stacked: row k * R + gi * S + s
        s_ids = jax.lax.broadcasted_iota(
            jnp.int32, (K * R, n_rows), 0) % R % S
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (K * R, n_rows), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (K * R, _ML), 1)

        def item(i, c):
            r, stored = c           # this run's carry; stores in flight
            b = i % 2
            slot = field(i, _SLOT)
            first = (i == 0) | (field(jnp.maximum(i - 1, 0), _SLOT)
                                != slot)
            more = i + 1 < n
            after = jnp.minimum(i + 1, G - 1)
            last = jnp.logical_not(more) | (field(after, _SLOT) != slot)

            # the next item's copies go out before this item's rows are
            # touched; where it opens a run, its state and queries too,
            # into the other carry (whose own store, a run ago, has to
            # have landed)
            @pl.when(more)
            def _():
                each_page(after, 1 - b, lambda c: c.start())
            opens = more & last
            landed = opens & bit(stored, 1 - r)

            @pl.when(landed)
            def _():
                store(0, 1 - r, lambda c: c.wait())

            @pl.when(opens)
            def _():
                load(field(after, _SLOT), 1 - r, lambda c: c.start())

            @pl.when(first)
            def _():
                load(slot, r, lambda c: c.wait())
            each_page(i, b, lambda c: c.wait())

            at = field(i, _ROW) + r_ids
            p = field(i, _POS)
            visible = at < p
            if reach is not None:
                visible = visible & (at > p + s_ids - reach)
            # the heads' products are independent: all K score
            # products first, ONE softmax step over the stacked scores
            # [K R, rows], then all K value products (a head at a time
            # the chain product - max - exp - sum - product leaves the
            # matrix unit waiting: 2.7 us an item at K = 8, PR 48)

            def head(k, half):       # head k's K (0) or V (1): [rows, hd]
                rows = buf[b, :, (2 * k + half) * hd:
                           (2 * k + half + 1) * hd]
                return rows if exact else rows.astype(jnp.float32)

            def queries(k):
                qh = qbuf[r, k * R:(k + 1) * R]
                return qh if exact else qh.astype(jnp.float32)
            sc = jnp.concatenate([jax.lax.dot_general(
                queries(k), head(k, 0), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                for k in range(K)], axis=0) * scale
            sc = jnp.where(visible, sc, -1e30)
            ml = carry[r, :, hd:]                        # [K R, 128]
            m, l = ml[:, 0:1], ml[:, 1:2]
            new_m = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            # select by the mask, not just the -1e30 floor: a row that
            # sees nothing of this item adds EXACTLY zero mass
            pj = jnp.where(visible, jnp.exp(sc - new_m), 0.0)
            corr = jnp.exp(m - new_m)
            new_l = l * corr + jnp.sum(pj, axis=1, keepdims=True)
            if exact:
                # f32 weights over bf16 rows in ONE pass of the rows
                # through the matrix unit: three bf16 terms (24 bits of
                # mantissa) stacked along a head's query rows
                p1 = pj.astype(jnp.bfloat16)
                r1 = pj - p1.astype(jnp.float32)
                p2 = r1.astype(jnp.bfloat16)
                terms = (p1, p2,
                         (r1 - p2.astype(jnp.float32)).astype(jnp.bfloat16))
            else:
                terms = (pj,)

            def context(k):
                pv = jax.lax.dot_general(
                    jnp.concatenate([t[k * R:(k + 1) * R] for t in terms],
                                    axis=0), head(k, 1),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return sum(pv[j * R:(j + 1) * R]
                           for j in range(len(terms)))
            carry[r, :, :hd] = carry[r, :, :hd] * corr + jnp.concatenate(
                [context(k) for k in range(K)], axis=0)
            carry[r, :, hd:] = jnp.where(
                lane == 0, new_m, jnp.where(lane == 1, new_l, 0.0))

            @pl.when(last)
            def _():
                store(slot, r, lambda c: c.start())
            stored = jnp.where(landed, stored & ~(1 << (1 - r)), stored)
            stored = jnp.where(last, stored | (1 << r), stored)
            return jnp.where(last, 1 - r, r), stored

        _, stored = jax.lax.fori_loop(
            0, n, item, (jnp.int32(0), jnp.int32(0)))
        for r in range(2):
            @pl.when(bit(stored, r))
            def _():
                store(0, r, lambda c: c.wait())

    itemsize = pool.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, n_rows, W), pool.dtype),
                pltpu.VMEM((2, K * R, hd), q.dtype),
                pltpu.VMEM((2, K * R, hd + _ML), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        # the state is updated where it lies: (meta, state, q, pool)
        input_output_aliases={1: 0},
        cost_estimate=pl.CostEstimate(
            flops=4 * G * n_rows * K * hd * R,
            bytes_accessed=G * n_rows * W * itemsize,
            transcendentals=G * n_rows * K * R),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(2 * n_rows * W * itemsize
                                 + 4 * K * R * (hd + _ML) * 4
                                 + (16 << 20))),
        interpret=interpret,
        name="gq_walk_trip",
    )(meta, state, q, pool)


@functools.lru_cache(maxsize=None)
def _trip_jit():
    """``_trip_impl`` as ONE jitted function: the layers of a program
    call the kernel at one or two shapes (a ``reach`` and None), and
    each shape is traced and lowered to Mosaic once, not once a layer
    (``ops/ragged_paged_attn.py`` ``_stream_jit`` has the numbers)."""
    import jax
    return jax.jit(_trip_impl, static_argnames=(
        "heads", "steps", "reach", "block_size", "pages", "group",
        "interpret"))


def gq_walk_trip(state, q, pool, meta, *, heads, steps, reach, block_size,
                 interpret=None):
    """One trip of the grouped-query decode walk (module docstring).

    state : float32 [B, K R, hd + 128], the slots' running state
        (``pack_state``); the result is the same array after the trip.
    q : [B, K R, hd], the slots' query rows (``pack_queries``).
    pool : [NB * bs, W], the layer's paged pool as flat rows, W >= 2 K
        hd.
    meta : int32 [group, pages + 4], the trip's items (``group`` rows
        of ``trip_meta``).
    heads : K, the K/V heads of a row.
    steps : S, the query rows a slot and query head carries (row r of
        a head's R stands at position ``pos + r % S``).
    reach : the layer's sliding window, or None.
    """
    if interpret is None:
        interpret = _auto_interpret()
    return _trip_jit()(
        state, q, pool, meta.reshape(-1), heads=int(heads),
        steps=int(steps),
        reach=None if reach is None else int(reach),
        block_size=int(block_size), pages=meta.shape[1] - 4,
        group=meta.shape[0], interpret=bool(interpret))


def compile_check(*, num_slots, kv_heads, groups, steps, head_dim,
                  row_width, block_size, pages, group, num_blocks, dtype,
                  reach=None, device=None):
    """Lower and compile the kernel through Mosaic at one shape,
    running nothing; raises the compiler's own error when it refuses
    (``ops/ragged_paged_attn.py`` ``compile_check`` says how a
    CPU-only sandbox uses it, and ``Engine`` calls it at construction
    through ``ServingSpec.attn_kernel_check``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device or jax.devices()[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    R = state_rows(groups * steps, dtype)

    def run(state, q, pool, meta):
        return gq_walk_trip(state, q, pool, meta, heads=kv_heads,
                            steps=steps, reach=reach,
                            block_size=block_size, interpret=False)

    jax.jit(run, donate_argnums=(0,)).lower(
        spec((num_slots, kv_heads * R, head_dim + _ML), jnp.float32),
        spec((num_slots, kv_heads * R, head_dim), dtype),
        spec((num_blocks * block_size, row_width), dtype),
        spec((group, pages + 4), jnp.int32)).compile()
