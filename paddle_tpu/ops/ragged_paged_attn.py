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

STREAMING: the body is a flash-style ONLINE-SOFTMAX loop.  K/V are
consumed one paged block at a time inside a ``fori_loop`` over the
slot's LIVE blocks (the loop stops at the causal horizon
``ceil((pos + width) / block_size)``, so a decode tick touches only
the blocks that actually hold history), carrying a per-(head, lane)
running max ``m``, normalizer ``l``, and an output accumulator ``acc``
rescaled by ``exp(m_old - m_new)`` per block — the standard
flash-attention recurrence.  The per-slot working set is therefore
**O(block_size x window)** — one K block, one V block, one
[H, W, block_size] score tile, and the [W, H, hd] accumulator —
*independent of context length*: multi-thousand-token contexts are not
VMEM-bounded and the compiled program stays O(1) in size.

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
no fallback.  The body is written for Mosaic: ``pos``,
``width`` and the block tables are scalar-prefetched to SMEM
(``PrefetchScalarGridSpec``), both pools stay in HBM
(``memory_space=ANY``), and each loop step DMAs ONE K block and ONE V
block into VMEM scratch, then runs a per-head ``[W, hd] x [hd, bs]``
contraction with the running ``(m, l, acc)`` in VMEM scratch.  It
needs ``head_dim % 128 == 0`` (the DMA slice must cover whole lane
tiles); libtpu 0.0.34 compiles it for ``TPU v5 lite`` at f32 / bf16 /
int8 pools, H in {4, 8, 16}, block sizes 8-32 and windows up to 512
(``compile_check``; tests/test_ragged_attn.py pins it through the
compile-only topology).  It is a correctness-level body: one page per
step, no double buffering, f32 contractions — tuning is ROADMAP S2.

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


def _stream_impl(q, k_flat, v_flat, block_tables, pos, width,
                 block_size, interpret, k_scale=None, v_scale=None):
    """Flash-style online-softmax streaming kernel (module docstring):
    a loop over the slot's live blocks, one K and one V block DMA'd
    HBM -> VMEM per step, running (m, l, acc) in VMEM scratch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, W, H, hd = q.shape
    nb = block_tables.shape[1]
    bs = block_size
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    # head-major query / output blocks [1, H, Wp, hd]: each head's
    # [Wp, hd] window is then a plain 2-D tile.  Wp pads the window to
    # whole f32 sublane tiles; the pad lanes are >= width, so they are
    # zeroed like any other masked lane and sliced off below.
    Wp = -(-W // 8) * 8
    qt = jnp.swapaxes(q, 1, 2)
    if Wp != W:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))

    def kernel(tables_ref, pos_ref, width_ref, *rest):
        if quant:
            ks_ref, vs_ref, *rest = rest
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, m_ref, l_ref, acc_ref, sem) = rest
        b = pl.program_id(0)
        p = pos_ref[b]
        w = width_ref[b]
        s_ids = jax.lax.broadcasted_iota(jnp.int32, (Wp, bs), 0)
        r_ids = jax.lax.broadcasted_iota(jnp.int32, (Wp, bs), 1)
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def body(j, carry):
            # fetch ONE paged block of each pool: physical block ids
            # are runtime data read from SMEM; bs is the only static
            # extent
            idx = tables_ref[b, j]
            rows = pl.ds(idx * bs, bs)
            ck = pltpu.make_async_copy(k_hbm.at[rows], k_buf, sem.at[0])
            cv = pltpu.make_async_copy(v_hbm.at[rows], v_buf, sem.at[1])
            ck.start()
            cv.start()
            ck.wait()
            cv.wait()
            # query lane s sees cache positions <= pos + s — the
            # slot's LENGTH does the masking, not a padded shape
            visible = (j * bs + r_ids) <= (p + s_ids)        # [Wp, bs]
            vis_f = visible.astype(jnp.float32)
            for h in range(H):
                qh = q_ref[0, h].astype(jnp.float32)         # [Wp, hd]
                kh = k_buf[:, h, :].astype(jnp.float32)      # [bs, hd]
                vh = v_buf[:, h, :].astype(jnp.float32)
                if quant:
                    # quantized pools dequantize PER STREAMED BLOCK —
                    # int8 codes times that block's per-head scale,
                    # right where the block enters the recurrence
                    kh = kh * ks_ref[b, j * H + h]
                    vh = vh * vs_ref[b, j * H + h]
                sc = jax.lax.dot_general(
                    qh, kh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                sc = jnp.where(visible, sc, -1e30)
                m = m_ref[h]                                 # [Wp, 1]
                new_m = jnp.maximum(
                    m, jnp.max(sc, axis=1, keepdims=True))
                # multiply by the mask, not just the -1e30 floor: a
                # fully masked tile must contribute EXACTLY zero mass
                # even while the running max is still at its -1e30
                # init (where exp(sc - new_m) would read exp(0) = 1)
                pj = jnp.exp(sc - new_m) * vis_f
                corr = jnp.exp(m - new_m)
                l_ref[h] = l_ref[h] * corr \
                    + jnp.sum(pj, axis=1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                    pj, vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[h] = new_m
            return carry

        # causal horizon: the last visible position is pos + width - 1
        # (width >= 1; a parked width-0 slot still walks block 0 so
        # the normalizer never hits zero — its lanes are zeroed below
        # anyway).  Blocks past the horizon are fully masked, so
        # skipping them is EXACT — and it is what makes per-tick block
        # walks O(live context), not O(table length).
        n_live = jnp.minimum(
            nb, (p + jnp.maximum(w, 1) - 1) // bs + 1)
        jax.lax.fori_loop(0, n_live, body, 0)
        # width as data: lanes past this slot's real window are zeroed
        # (parked slots — width 0 — return all-zero, never-read lanes)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Wp, 1), 0)
        for h in range(H):
            ctx = acc_ref[h] / l_ref[h]
            o_ref[0, h] = jnp.where(lane < w, ctx, 0.0).astype(
                o_ref.dtype)

    def slot_block(b, *_):
        return (b, 0, 0, 0)

    scalars = [block_tables, pos, width]
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
                pltpu.VMEM((bs, H, hd), k_flat.dtype),
                pltpu.VMEM((bs, H, hd), v_flat.dtype),
                pltpu.VMEM((H, Wp, 1), jnp.float32),
                pltpu.VMEM((H, Wp, 1), jnp.float32),
                pltpu.VMEM((H, Wp, hd), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, Wp, hd), q.dtype),
        interpret=interpret,
        name="ragged_paged_attn_stream",
    )(*scalars, qt, k_flat, v_flat)
    return jnp.swapaxes(out[:, :, :W], 1, 2)


def ragged_paged_attention(q, k_flat, v_flat, block_tables, pos, width,
                           *, block_size, interpret=None,
                           k_scale=None, v_scale=None):
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
    return _stream_impl(
        q, k_flat, v_flat,
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(width, jnp.int32),
        block_size=int(block_size), interpret=bool(interpret),
        k_scale=k_scale, v_scale=v_scale)


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
