"""Decoder that generates by diffusion over blocks, with grouped-query
attention and softmax-routed experts in every layer — the SDAR-MoE
family (SDAR-30B-A3B; Qwen3-MoE's layer under a block-causal mask),
served on ``serving.Engine``'s paged path.

Layer equations (pre-norm residual blocks ``x += Attn(RMSNorm(x));
x += MoE(RMSNorm(x))``, RMSNorm, no bias anywhere, untied head; d
hidden, H query heads, K key/value heads, hd the head size):

* attention, ``h = RMSNorm(x)``: ``q = h W_q`` [H, hd], ``k = h W_k``,
  ``v = h W_v`` [K, hd]; every head of q and of k is RMS-normalised
  over its hd numbers with a learned gain, then rotated to its absolute
  position (``rope``: dimension i pairs with i + hd/2).  Query head i
  reads K/V head ``i // (H / K)``; ``s_ij = q_i . k_j / sqrt(hd)``,
  softmax over the visible j, ``y = concat(o) W_o``.  **The cached row
  of a position and layer is K and V of its K heads, flat and head-major:
  ``[K][k|v][hd]``, ``2 K hd`` numbers in ONE pool a layer** (below).
* **visibility is block-causal**: with blocks of ``block_length`` = B
  positions aligned to absolute positions (the prompt included),
  position i sees j iff ``j // B <= i // B`` — bidirectional inside a
  block.
* every layer is routed: ``p = softmax(h W_g)`` in float32, the top k
  of p, ``w_e = p_e / sum_selected p``, ``y = sum w_e E_e(h)`` with
  ``E_e`` a SwiGLU; no shared expert, nothing dropped
  (``distributed/moe.py``; ``mla_moe.RoutedFFN`` told so).
* the logits of position i are for the token AT position i.

Generation (the family's ``block_diffusion_generate`` with the static
low-confidence schedule).  A prompt of n tokens: its ``n // B`` whole
blocks are prefilled under the mask and cached; no logit of the
prefill is used.  The answer is made a block at a time.  A block starts
as the prompt's ``n % B`` tail (first block only) then masks; a
**denoise pass** runs the block's B rows against the cache and the
block itself, takes ``x0 = argmax`` and ``c = softmax(logits)[x0]`` at
every masked position and fixes the ``B / T`` most confident to their
x0 (T = ``denoising_steps``); fixed positions never change.  With no
mask left, a **commit pass** runs the final tokens and their K/V is
what stays cached: exactly what a prefill of the same tokens writes.
Whether a position is masked is a flag the program keeps, not ``id ==
mask_token_id`` (a prompt or a greedy pick may hold that id).

Serving: ``serving_spec()`` declares one pool a layer of ``[2 K hd]``
rows (``[NB, bs, 1024]`` at 4 heads of 128; no V pool, as the latent
model) and a ``StepSpec`` of B rows a lane; ``serving_program`` offers
the fused step over all slots (denoise or commit a lane, by its flags;
the choice of what to fix, the stream's rule, the budget and EOS on the
device) and the paged chunk prefill, both returning an int32 counter
vector.  What is not here: sampled requests, the dynamic (threshold)
schedule, sliding windows, rope scaling.

Why the row is flat (``KVRowSpec`` states the rule): a pool's last two
axes must fill whole tiles of its dtype, or the walk's block gather
fetches part-filled tiles.  As ``[NB, bs, K, hd]`` the v5e tiles the
``[4, 128]`` bf16 row ``T(4,128)(2,1)``, a quarter of a register, and K
and V were two pools: two gathers a trip at 255 GB/s of the memory's
819 (chip runs, PR 35 and 36).  ``[NB, bs, 2 K hd]`` is tiled
``T(8,128)(2,1)`` with nothing padded, a trip fetches a block's K and V
in one gather of 32 KB blocks at 675 GB/s, and head k's K and V are the
128-lane slices ``2 k hd ..`` and ``(2 k + 1) hd ..`` of the row, which
a product reads where they lie (a reshape of the fetched rows to
``[.., K, hd]`` brings the small tiles back, with a copy a trip).
Head-major, so a later split over K/V heads is a contiguous lane range.
"""
from __future__ import annotations

import functools
import math

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from .mla_moe import (
    MOE_COUNTERS, RMSNorm, RoutedFFN, _lin, rope, write_chunk_rows)
from .programs import (
    KVRowSpec, ServedModel, ServingSpec, StepSpec, _backend, _over_a_mesh,
    _scoped, slot_attn_core, walk_chunk, walk_first, walk_group, walk_plan,
    walk_rows)

# the step's own counters, after the routed layers' four, in the order
# of the vector the programs return: lane-passes of each state, the
# positions fixed, the blocks whose rows became final (the chunk
# program's too)
STEP_COUNTERS = (("denoise_passes", None),
                 ("commit_passes", "committing"),
                 ("block_tokens_fixed", "fixed"),
                 ("blocks_committed", None))


def _first_masked(masked):
    """Index of a lane's first masked row, ``rows`` where none is:
    every position before it is final.  masked bool [B, W] -> [B]."""
    import jax.numpy as jnp
    W = masked.shape[1]
    return jnp.min(jnp.where(masked, jnp.arange(W, dtype=jnp.int32), W),
                   axis=1)


class GQAttention(nn.Layer):
    """Grouped-query attention with per-head q/k norms under the
    block-causal mask (module docstring).  What another family's layer
    sets (``models/afmoe.py``): ``reach``, a sliding window (position i
    sees j only where ``i - j < reach``; None: back to row 0), under
    which every walk starts at the first chunk a query can see;
    ``rotary`` False for a layer without positions; a ``block_length``
    of 1 is the causal mask."""

    def __init__(self, hidden, num_heads, num_kv_heads, head_dim,
                 rope_theta, eps, block_length, reach=None, rotary=True):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(
                f"query heads ({num_heads}) must be a whole number of "
                f"groups over the K/V heads ({num_kv_heads})")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.theta = head_dim, float(rope_theta)
        self.block_length = block_length
        self.reach, self.rotary = reach, bool(rotary)
        self.q_proj = nn.Linear(hidden, num_heads * head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(hidden, num_kv_heads * head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(hidden, num_kv_heads * head_dim,
                                bias_attr=False)
        self.q_norm = RMSNorm(head_dim, eps)
        self.k_norm = RMSNorm(head_dim, eps)
        self.o_proj = nn.Linear(num_heads * head_dim, hidden,
                                bias_attr=False)

    def project(self, h, pos):
        """h [B, S, D], pos [B, S] -> q [B, S, H, hd], k and v
        [B, S, K, hd]; q and k normed a head and rotated."""
        B, S = h.shape[0], h.shape[1]
        q = _lin(self.q_proj, h).reshape(B, S, self.num_heads,
                                         self.head_dim)
        k = _lin(self.k_proj, h).reshape(B, S, self.num_kv_heads,
                                         self.head_dim)
        v = _lin(self.v_proj, h).reshape(B, S, self.num_kv_heads,
                                         self.head_dim)
        if not self.rotary:
            return self.q_norm(q), self.k_norm(k), v
        at = pos[:, :, None]
        return (rope(self.q_norm(q), at, self.theta),
                rope(self.k_norm(k), at, self.theta), v)

    def output(self, ctx, h):
        """The layer's output from the heads' contexts ``ctx``
        [B, S, H hd] (``h`` is the layer's input, for a subclass whose
        output is gated by it)."""
        return _lin(self.o_proj, ctx)

    def one_slot_span(self, pos, chunk, trips):
        """(first, end) chunks of ONE slot's walk over the rows below
        ``pos`` [1]: to the chunk that holds row ``pos - 1``, from
        chunk 0 or, under a ``reach``, from ``walk_first``."""
        import jax.numpy as jnp
        end = jnp.clip((jnp.max(pos) + chunk - 1) // chunk, 0, trips)
        if self.reach is None:
            return 0, end
        return jnp.minimum(walk_first(jnp.max(pos), self.reach, chunk),
                           end), end

    def mask(self, blk):
        """Which of a step's or a chunk's own S rows see which, from
        their blocks ``blk = arange(S) // block_length`` (the first
        row's position is a multiple of the block): row s sees row t
        iff ``blk[t] <= blk[s]`` and, under a ``reach``, ``s - t <
        reach``.  bool [S, S]."""
        import jax.numpy as jnp
        sees = blk[None, :] <= blk[:, None]
        if self.reach is not None:
            at = jnp.arange(blk.shape[0])
            sees = sees & (at[:, None] - at[None, :] < self.reach)
        return sees

    def cache_rows(self, k, v):
        """k, v [..., K, hd] -> the rows a pool keeps, [..., 2 K hd],
        head-major: ``[K][k|v][hd]`` (module docstring)."""
        import jax.numpy as jnp
        return jnp.stack([k, v], axis=-2).reshape(*k.shape[:-2], -1)

    @_scoped("sdar.attend")
    def attend(self, q, new, pool, tables, pos):
        """Attention of each slot's S rows, which stand at positions
        ``pos[b] ..`` (a multiple of the block length), over the
        slot's cached rows BELOW ``pos[b]`` — all of them: they belong
        to earlier blocks — and over the S rows themselves under the
        block-causal mask, their K/V taken from ``new`` and not from
        the pool.  One pass with a running maximum and denominator,
        float32 accumulation, the pool read in its own dtype
        ``walk_chunk`` rows at a time: one gather of whole blocks a
        trip, head k's K and V read as lane slices of the fetched rows.

        Several slots (the step program) are walked as ``walk_plan``'s
        work list of (slot, chunk) items, each slot to its OWN ``pos``,
        ``walk_group`` items a trip of ONE device loop; a slot at
        position 0 (a parked lane, or a prompt shorter than a block)
        has no item.  One slot (the chunk program) walks its own chunks
        in turn.  A table of at most one chunk is read whole, without
        a loop.

        WHICH FORM A TRIP TAKES (``core``: ``models/programs.py``
        ``slot_attn_core``, from what the code can see).  On one TPU,
        over a paged floating-point pool at heads of whole 128-lane
        tiles, a trip of the work list is ONE Pallas kernel
        (``ops/gq_walk_trip.py``, ``_kernel_trips``): the next item's
        pages in flight under the present item's products, rows
        contracted as stored, the items folded into their slots'
        running state inside the kernel, a padding item neither copied
        nor computed.  Everywhere else (the CPU, where tier-1 runs;
        heads of 64, ``lfm2_moe``; a program over a mesh; the one-slot
        walk and the one-chunk table on every platform) a trip is
        XLA's: one gather of the items' blocks, then the products and
        the fold.  One algorithm, an online softmax over a work list,
        whose trip has two implementations by shape.  Under a
        ``reach`` row s sees the cached rows ``> pos + s - reach``
        only: the work list and the one-slot walk start at the first
        chunk the slot's first row sees (``walk_first``), and the mask
        is exact inside the first and the last chunk.

        q [B, S, H, hd]; new [B, S, 2 K hd] (``cache_rows``); pool
        [NB, bs, W], W >= 2 K hd; tables int32 [B, L // bs]; pos int32
        [B].  Returns [B, S, H * hd]."""
        import jax
        import jax.numpy as jnp
        B, S, H, hd = q.shape
        K, bs = self.num_kv_heads, pool.shape[1]
        g = H // K
        table_rows = tables.shape[1] * bs
        chunk = walk_chunk(table_rows, bs)
        scale = 1.0 / math.sqrt(hd)
        highest = jax.lax.Precision.HIGHEST
        qg = q.reshape(B, S, K, g, hd)

        def partial(qs, rows, visible):
            """Masked scores [b, K, g, S, n] of queries ``qs``
            [b, S, K, g, hd] over the keys of ``rows`` [b, n, W], and
            ``context(p)`` [b, S, K, g, hd] of weights p over their
            values; ``visible`` broadcasts to [b, S, n]."""
            def lanes(k, half):          # head k's K (0) or V (1)
                return rows[..., (2 * k + half) * hd:
                            (2 * k + half + 1) * hd]
            sc = jnp.stack([jnp.einsum(
                "bsgd,bnd->bgsn", qs[:, :, k],
                lanes(k, 0).astype(qs.dtype),
                preferred_element_type=jnp.float32)
                for k in range(K)], axis=1)
            return (jnp.where(visible[:, None, None], sc * scale, -1e30),
                    lambda p: jnp.stack([jnp.einsum(
                        "bgsn,bnd->bsgd", p[:, k],
                        lanes(k, 1).astype(jnp.float32),
                        precision=highest) for k in range(K)], axis=2))

        def per_ctx(a):            # [b, K, g, S] -> [b, S, K, g, 1]
            return jnp.transpose(a, (0, 3, 1, 2))[..., None]

        def fold(carry, sc, context):
            top, den, acc = carry
            new_top = jnp.maximum(top, jnp.max(sc, axis=-1))
            keep = jnp.exp(top - new_top)
            p = jnp.exp(sc - new_top[..., None])
            return (new_top, den * keep + jnp.sum(p, axis=-1),
                    acc * per_ctx(keep) + context(p))

        def rows_of(blocks):
            """pool[blocks] [b, n // bs, bs, W] -> [b, n, W]."""
            got = pool[blocks]
            return got.reshape(got.shape[0], -1, got.shape[-1])

        def trip(c, carry):
            start = jnp.minimum(c * chunk, table_rows - chunk)
            at = start + jnp.arange(chunk)
            blocks = jax.lax.dynamic_slice_in_dim(
                tables, start // bs, chunk // bs, axis=1)
            # rows below c * chunk were scored by an earlier trip (the
            # last trip of a table that is no whole number of chunks
            # starts early)
            sees = (at[None, :] < pos[:, None]) \
                & (at >= c * chunk)[None, :]
            return fold(carry, *partial(qg, rows_of(blocks),
                                        per_row(sees, at, pos)))

        def per_row(sees, at, start, cut=lambda a: a):
            """``sees`` [b, n] (which of the fetched rows, at rows
            ``at`` [n] or [b, n], lie below a slot's first row
            ``start`` [b]) for each of the slot's S rows, [b, 1 or S,
            n]: row s reaches back to ``start + s - reach``.  ``cut``
            takes a trip's items out of ``at`` and ``start``."""
            if self.reach is None:
                return sees[:, None, :]
            back = cut(start)[:, None] + jnp.arange(S)[None, :] \
                - self.reach
            return sees[:, None, :] & (cut(at)[..., None, :]
                                       > back[:, :, None])

        def walk_items(init):
            group = walk_group(B, pool.shape[2])
            slot_of, chunk_of, valid, n_trips = walk_plan(
                pos, 0, table_rows, chunk, group, self.reach)
            n_chunks = -(-table_rows // chunk)
            whole = jnp.pad(tables, ((0, 0), (
                0, n_chunks * chunk // bs - tables.shape[1])))
            cols = whole.reshape(B * n_chunks, chunk // bs)[
                slot_of * n_chunks + chunk_of]           # [N, chunk//bs]
            if self.core(B, pool, table_rows) == "kernel":
                from ..ops.gq_walk_trip import trip_meta
                return self._kernel_trips(
                    init, qg, pool, trip_meta(
                        cols, slot_of, chunk_of, valid, pos, chunk),
                    group, n_trips)
            at = (chunk_of * chunk)[:, None] + jnp.arange(chunk)[None, :]
            start = pos[slot_of]
            sees = (at < start[:, None]) & valid[:, None]        # [N, n]
            whose = ((slot_of[None, :] == jnp.arange(B)[:, None])
                     & valid[None, :])[..., None, None, None]

            def item_trip(t, carry):
                top, den, acc = carry

                def cut(a, axis=0):
                    return jax.lax.dynamic_slice_in_dim(
                        a, t * group, group, axis)
                sc, context = partial(
                    qg[cut(slot_of)], rows_of(cut(cols)),
                    per_row(cut(sees), at, start, cut))
                # the items' own partials, folded into their slots'
                # running state: item i weighs exp(m_i - new_top_b) in
                # its slot b, 0 elsewhere (and exactly 0 where it saw
                # no row: m_i = -1e30)
                m = jnp.max(sc, axis=-1)                 # [G, K, g, S]
                p = jnp.exp(sc - m[..., None])
                mine = cut(whose, 1)               # [B, G, 1, 1, 1]
                new_top = jnp.maximum(top, jnp.max(
                    jnp.where(mine, m[None], -1e30), axis=1))
                keep = jnp.exp(top - new_top)
                w = jnp.where(mine, jnp.exp(jnp.minimum(
                    m[None] - new_top[:, None], 0.0)), 0.0)
                return (new_top,
                        den * keep + jnp.einsum(
                            "bikgs,ikgs->bkgs", w, jnp.sum(p, axis=-1)),
                        acc * per_ctx(keep) + jnp.einsum(
                            "bikgs,iskgd->bskgd", w, context(p),
                            precision=highest))

            return jax.lax.fori_loop(0, n_trips, item_trip, init)

        # the rows themselves: position pos + s sees pos + t iff
        # t // block <= s // block (pos is a multiple of the block)
        blk = jnp.arange(S) // self.block_length
        init = fold((jnp.full((B, K, g, S), -1e30, jnp.float32),
                     jnp.zeros((B, K, g, S), jnp.float32),
                     jnp.zeros((B, S, K, g, hd), jnp.float32)),
                    *partial(qg, new, self.mask(blk)[None]))
        trips = -(-table_rows // chunk)
        if trips == 1:
            _, den, acc = trip(0, init)
        elif B > 1:
            _, den, acc = walk_items(init)
        else:
            _, den, acc = jax.lax.fori_loop(
                *self.one_slot_span(pos, chunk, trips), trip, init)
        return (acc / per_ctx(den)).astype(q.dtype).reshape(B, S, H * hd)

    def serving_core(self, *, paged, quant, table_rows, block_size,
                     slots):
        """``ServingSpec.attn_core`` of a model whose decode attention
        is ``attend``: the form a trip of the step program's walk
        takes, by the rule ``attend`` applies itself
        (``slot_attn_core``)."""
        form, why = slot_attn_core(
            _backend(), paged=paged, quant=quant, head_dim=self.head_dim,
            mesh=_over_a_mesh(), table_rows=table_rows,
            block_size=block_size, slots=slots)
        return {"form": form, "why": why, "platform": _backend(),
                "head_dim": self.head_dim}

    def core(self, slots, pool, table_rows):
        """The form (``"kernel"`` / ``"walk"``) of a trip of
        ``attend``'s walk over ``slots`` slots of ``pool`` [NB, bs, W],
        traced now."""
        import jax.numpy as jnp
        return self.serving_core(
            paged=True, quant=not jnp.issubdtype(pool.dtype, jnp.floating),
            table_rows=table_rows, block_size=pool.shape[1],
            slots=slots)["form"]

    def decode_rows(self, pos, ahead, table_rows, block_size,
                    padded=True):
        """Host twin of what ``attend``'s walk fetches for slots whose
        queries see the rows below ``pos[b] + ahead``
        (``ServingSpec.decode_rows``): ``walk_rows``' whole trips where
        XLA's trip runs; not ``padded``, the items alone
        (``ServingSpec.attn_kernel_rows``: the kernel copies nothing
        for a padding item)."""
        return walk_rows(pos, ahead, table_rows, block_size,
                         2 * self.num_kv_heads * self.head_dim,
                         self.reach, padded=padded)

    def _kernel_trips(self, init, qg, pool, meta, group, n_trips):
        """``walk_items``' loop with ``ops/gq_walk_trip.py`` as a
        trip's body: the running state packed as the kernel keeps it
        and updated where it lies, the trip's items ``group`` rows of
        ``meta``.  Returns (None, den, acc) as the XLA trips do."""
        import jax
        from ..ops import gq_walk_trip as kernel
        S, K, g = qg.shape[1], qg.shape[2], qg.shape[3]
        rows = kernel.state_rows(g * S, qg.dtype)
        queries = kernel.pack_queries(qg, rows)
        flat = pool.reshape(-1, pool.shape[-1])

        def trip(t, state):
            return kernel.gq_walk_trip(
                state, queries, flat,
                jax.lax.dynamic_slice_in_dim(meta, t * group, group),
                heads=K, steps=S, reach=self.reach,
                block_size=pool.shape[1])
        state = jax.lax.fori_loop(
            0, n_trips, trip, kernel.pack_state(*init, rows))
        return (None, *kernel.unpack_state(state, K, g, S))

    @_scoped("attention")
    def step_slots_paged(self, h, pool, tables, pos, walk_pos):
        """One block a slot: its S rows' K/V go into the block that
        holds ``pos[b] .. pos[b] + S`` — one in-place update a slot of
        the pool as it lies (a scatter is a loop of one trip a row on
        the v5e, ``mla_moe.write_chunk_rows``) — and the rows attend
        the cache below ``walk_pos[b]`` (``pos``, or 0 for a lane that
        does not step: it walks nothing) and themselves.  The update
        is made by every pass of every lane: what a denoise pass
        writes at ``pos ..`` is read by nobody (this block's passes
        take their own rows' K/V from the pass itself; a later block
        sees these rows only after the commit pass has written the
        final ones over them; a parked lane's table is the scratch
        block; a prefilling lane stands at its next chunk's first row,
        which that chunk writes before any query sees it).  h
        [B, S, D]; pool [NB, bs, W]; tables [B, L // bs]; pos [B],
        multiples of S with S dividing bs.  Returns (out [B, S, D],
        pool)."""
        import jax
        import jax.numpy as jnp
        B, S = h.shape[0], h.shape[1]
        q, k, v = self.project(h, pos[:, None] + jnp.arange(S)[None, :])
        new = self.cache_rows(k, v)
        bs = pool.shape[1]
        blocks = tables[jnp.arange(B), pos // bs]
        offs, stored = pos % bs, new.astype(pool.dtype)
        for b in range(B):
            pool = jax.lax.dynamic_update_slice(
                pool, stored[b:b + 1], (blocks[b], offs[b], 0))
        out = self.attend(q, new, pool, tables, walk_pos)
        return self.output(out, h), pool

    @_scoped("attention")
    def prefill_chunk_paged(self, h, pool, table, pos, true_len,
                            scratch):
        """C prompt tokens of ONE slot at ``pos .. pos + C`` (``pos``
        and ``true_len`` multiples of the block length): the rows of
        the first ``true_len`` go into the slot's blocks by
        ``write_chunk_rows``' in-place block updates, and the chunk
        attends the slot's rows below ``pos`` and itself under the
        block-causal mask.  h [1, C, D]; table [L // bs].  Returns
        (out [1, C, D], pool)."""
        import jax.numpy as jnp
        q, k, v = self.project(
            h, (pos + jnp.arange(h.shape[1]))[None, :])
        new = self.cache_rows(k, v)
        pool = write_chunk_rows(pool, new[0], table, pos, true_len,
                                scratch)
        out = self.attend(q, new, pool, table[None, :],
                          jnp.reshape(pos, (1,)))
        return self.output(out, h), pool

    def forward(self, h):
        """Uncached block-causal attention over whole sequences, h
        [B, S, D] (the CPU tests hold it against the reference)."""
        import jax
        import jax.numpy as jnp
        B, S = h.shape[0], h.shape[1]
        g = self.num_heads // self.num_kv_heads
        q, k, v = self.project(
            h, jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)))
        sc = jnp.einsum(
            "bskgd,bnkd->bkgsn",
            q.reshape(B, S, self.num_kv_heads, g, self.head_dim),
            k).astype(jnp.float32) / math.sqrt(self.head_dim)
        blk = jnp.arange(S) // self.block_length
        p = jax.nn.softmax(jnp.where(
            self.mask(blk)[None, None, None], sc, -1e30),
            axis=-1).astype(h.dtype)
        ctx = jnp.einsum("bkgsn,bnkd->bskgd", p, v)
        return self.output(
            ctx.reshape(B, S, self.num_heads * self.head_dim), h)


def walk_kernel_check(layers, steps, *, num_slots, block_size,
                      blocks_per_slot, num_blocks, dtype, device,
                      spec_k=None):
    """``ServingSpec.attn_kernel_check`` of a model whose attention
    ``layers`` are ``GQAttention`` carrying ``steps`` rows a slot:
    Mosaic compiles ``ops/gq_walk_trip.py`` for ``device`` once a
    distinct ``reach``, at the trip the step program will take over
    ``num_slots`` slots.  (No such model has a verify program:
    ``spec_k`` is taken and not read.)"""
    from ..ops.gq_walk_trip import compile_check
    attn = layers[0]
    width = 2 * attn.num_kv_heads * attn.head_dim
    chunk = walk_chunk(blocks_per_slot * block_size, block_size)
    for reach in sorted({a.reach for a in layers},
                        key=lambda r: (r is None, r)):
        try:
            compile_check(
                num_slots=num_slots, kv_heads=attn.num_kv_heads,
                groups=attn.num_heads // attn.num_kv_heads, steps=steps,
                head_dim=attn.head_dim, row_width=width,
                block_size=block_size, pages=chunk // block_size,
                group=walk_group(num_slots, width),
                num_blocks=num_blocks, dtype=dtype, reach=reach,
                device=device)
        except Exception as e:
            raise ValueError(f"at a reach of {reach}: {e}") from e


class SDARMoEBlock(nn.Layer):
    """``x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))``."""

    def __init__(self, cfg, block_length):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.input_norm = RMSNorm(d, eps)
        self.attn = GQAttention(
            d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rope_theta"], eps, block_length)
        self.post_norm = RMSNorm(d, eps)
        self.ffn = RoutedFFN(
            d, cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], 0, 1.0,
            cfg.get("norm_topk_prob", True), gate="softmax")

    @_scoped("mlp")
    def feed_forward(self, x, live):
        """x [B, S, D], live [B, S] -> (x + MoE(RMSNorm(x)), stats)."""
        h = self.post_norm(x)
        y, stats = self.ffn(h.reshape(-1, h.shape[-1]), live.reshape(-1))
        return x + y.reshape(x.shape), stats

    def step_slots_paged(self, x, pool, tables, pos, live):
        import jax.numpy as jnp
        a, pool = self.attn.step_slots_paged(
            self.input_norm(x), pool, tables, pos,
            jnp.where(live, pos, 0))
        x, stats = self.feed_forward(
            x + a, jnp.broadcast_to(live[:, None], x.shape[:2]))
        return x, pool, stats

    def prefill_chunk_paged(self, x, pool, table, pos, true_len,
                            scratch, live):
        a, pool = self.attn.prefill_chunk_paged(
            self.input_norm(x), pool, table, pos, true_len, scratch)
        x, stats = self.feed_forward(x + a, live[None, :])
        return x, pool, stats

    def forward(self, x):
        import jax.numpy as jnp
        x = x + self.attn(self.input_norm(x))
        return self.feed_forward(x, jnp.ones(x.shape[:2], bool))[0]


class SDARMoEModel(ServedModel, nn.Layer):
    """Decoder-only LM of the module's docstring.  ``config`` holds the
    published keys (``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``moe_intermediate_size``,
    ``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
    ``num_hidden_layers``, ``vocab_size``, ``max_position_embeddings``,
    ``rms_norm_eps``, ``rope_theta``); how it generates is the
    constructor's: ``block_length``, ``denoising_steps``,
    ``mask_token_id``, ``remasking_strategy``.  Build it under
    ``nn.LazyGuard()`` to declare the parameters without values."""

    def __init__(self, config, block_length=4, denoising_steps=4,
                 mask_token_id=151669,
                 remasking_strategy="low_confidence_static"):
        super().__init__()
        cfg = dict(config)
        if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step",
                                                 1) != 1:
            raise ValueError("every layer is routed here: "
                             "mlp_only_layers has to be empty and "
                             "decoder_sparse_step 1")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not written")
        if cfg.get("use_sliding_window") or cfg.get("attention_bias"):
            raise ValueError("sliding windows and attention biases are "
                             "not written")
        if remasking_strategy != "low_confidence_static":
            raise ValueError(
                f"remasking_strategy {remasking_strategy!r}: only "
                "'low_confidence_static' is written (the dynamic one "
                "adds a threshold test to the same pass)")
        if block_length % denoising_steps or not 1 <= block_length <= 16:
            raise ValueError(
                f"block_length ({block_length}) has to be 1..16 and a "
                f"whole number of denoising_steps ({denoising_steps})")
        if not 0 <= mask_token_id < cfg["vocab_size"]:
            raise ValueError(f"mask_token_id {mask_token_id} is not in "
                             f"the vocabulary ({cfg['vocab_size']})")
        self.config = cfg
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.mask_token_id = int(mask_token_id)
        d = cfg["hidden_size"]
        self.embed = self.create_parameter(
            [cfg["vocab_size"], d],
            default_initializer=I.Normal(0.0, 0.02))
        self.blocks = nn.LayerList([
            SDARMoEBlock(cfg, self.block_length)
            for _ in range(cfg["num_hidden_layers"])])
        self.norm = RMSNorm(d, cfg["rms_norm_eps"])
        self.lm_head = nn.Linear(d, cfg["vocab_size"], bias_attr=False)

    @_scoped("lm_head")
    def _head(self, x):
        import jax.numpy as jnp
        return _lin(self.lm_head, self.norm(x)).astype(jnp.float32)

    def forward(self, input_ids, masked=None):
        """Uncached logits [B, S, V] (float32) under the block-causal
        mask; ``masked`` [B, S] bool puts the mask token there."""
        import jax.numpy as jnp
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if masked is not None:
            ids = jnp.where(jnp.asarray(masked), self.mask_token_id, ids)
        x = self.embed._data[ids]
        for blk in self.blocks:
            x = blk(x)
        return Tensor(self._head(x))

    # -- the step's state ----------------------------------------------
    def open_block(self, tail):
        """``StepSpec.open``: the first block of a lane whose prefill
        left the ``tail`` prompt tokens (fewer than a block): they
        stand first and are final, the rest is masked.  flags: bit j =
        row j is masked, bit ``block_length`` = the lane steps."""
        import numpy as np
        W = self.block_length
        tok = np.full(W, self.mask_token_id, np.int32)
        tok[:len(tail)] = tail
        return tok, (1 << W) | (((1 << W) - 1) & ~((1 << len(tail)) - 1))

    def _counter_vector(self, stats, step):
        """int32 [8]: the routed layers' four (``MOE_COUNTERS``: pairs,
        experts hit, expert slots, the busiest expert's pairs, summed
        over the layers), then ``step``'s four (``STEP_COUNTERS``)."""
        import jax.numpy as jnp
        s = sum(stats)
        slots = len(self.blocks) * self.config["num_experts"]
        return jnp.stack([s[0], s[1], jnp.int32(slots), s[2], *step]
                         ).astype(jnp.int32)

    # -- step programs -------------------------------------------------
    @_scoped("sdar.unmask")
    def _unmask(self, logits, tok, masked, denoising):
        """The static low-confidence rule on the device: ``x0`` the
        best token and ``c`` its probability at every row; the
        ``block_length / denoising_steps`` masked rows of a denoising
        lane with the highest c are fixed to their x0.  logits
        [B, W, V] float32.  Returns (tok, masked, fixed [B, W])."""
        import jax
        import jax.numpy as jnp
        W = self.block_length
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # c = softmax(logits)[x0] = 1 / sum(exp(l - max l))
        c = 1.0 / jnp.sum(jnp.exp(
            logits - jnp.max(logits, axis=-1, keepdims=True)), axis=-1)
        fixed = jnp.zeros_like(masked)
        for _ in range(W // self.denoising_steps):
            left = masked & ~fixed & denoising[:, None]
            best = jnp.argmax(jnp.where(left, c, -1.0), axis=-1)
            fixed = fixed | (jax.nn.one_hot(best, W, dtype=bool)
                             & jnp.any(left, axis=-1, keepdims=True))
        return jnp.where(fixed, x0, tok), masked & ~fixed, fixed

    def _fused_step_slots(self, tok, pools, no_v, tables, pos, ctr,
                          eos, rem, flags):
        """One pass of every stepping lane's block (``StepSpec``; the
        module docstring).  A lane with a masked row denoises: the most
        confident masked rows are fixed, and the rows that are now
        final with every row before them are its newly sent tokens, cut
        at the budget and at the first EOS.  A lane with none commits:
        the K/V this pass wrote at ``pos ..`` stay, ``pos`` moves a
        block on and the next block opens as masks.  Lanes that do not
        step (parked, prefilling, out of budget) hit no expert, walk
        no cached row and keep their state.  tok int32 [B, W]; ``pools``
        one a layer; ``no_v`` is the engine's empty list of V pools,
        handed back as it came (the arguments keep the seam's order,
        which ``tests/benchmarks/planted_fault_commit.py`` wraps by
        position)."""
        import jax.numpy as jnp
        W = self.block_length
        masked = ((flags[:, None] >> jnp.arange(W)) & 1) > 0
        live = (rem > 0) & ((flags >> W) & 1 > 0)
        denoising = live & jnp.any(masked, axis=1)
        committing = live & ~jnp.any(masked, axis=1)
        x = self.embed._data[jnp.where(masked, self.mask_token_id, tok)]
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, pool, st = blk.step_slots_paged(x, pools[j], tables, pos,
                                               live)
            new_pools.append(pool)
            stats.append(st)
        new_tok, new_masked, fixed = self._unmask(
            self._head(x), tok, masked, denoising)
        # the stream's rule: a row is sent once it and every row before
        # it are final
        first = _first_masked(masked)
        count = jnp.minimum(_first_masked(new_masked) - first, rem)
        at = jnp.arange(W)[None, :]
        sent = (at >= first[:, None]) & (at < (first + count)[:, None])
        is_eos = sent & (eos >= 0)[:, None] & (new_tok == eos[:, None])
        hit_eos = jnp.any(is_eos, axis=1)
        count = jnp.where(
            hit_eos, jnp.argmax(is_eos, axis=1) - first + 1, count)
        count = jnp.where(denoising, count, 0).astype(jnp.int32)
        new_rem = jnp.where(denoising,
                            jnp.where(hit_eos, 0, rem - count), rem)
        # a commit opens the next block
        L = tables.shape[1] * pools[0].shape[1]
        new_pos = jnp.where(committing, jnp.minimum(pos + W, L - W), pos)
        new_tok = jnp.where(committing[:, None], self.mask_token_id,
                            new_tok)
        new_masked = new_masked | committing[:, None]
        new_flags = jnp.where(
            live, (1 << W) | jnp.sum(
                new_masked.astype(jnp.int32) << jnp.arange(W), axis=1),
            flags).astype(jnp.int32)
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        report = jnp.concatenate(
            [new_tok, first[:, None], count[:, None], new_pos[:, None],
             new_flags[:, None]], axis=1).astype(jnp.int32)
        counters = self._counter_vector(stats, (
            jnp.sum(denoising), jnp.sum(committing), jnp.sum(fixed),
            jnp.sum(committing)))
        return (report, done, new_tok, new_pos, ctr + count, new_rem,
                new_pools, no_v, counters, new_flags)

    def _chunk_prefill_tick_paged(self, toks, pools, table, pos,
                                  true_len, scratch):
        """C prompt tokens of one slot through every block: their K/V
        are cached, nothing else is kept (no logit of a prefill is
        used: the head does not run).  Returns (a [1, 1] handle of the
        last layer's output, pools, [], counters)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.arange(toks.shape[1]) < true_len
        x = self.embed._data[toks]
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, pool, st = blk.prefill_chunk_paged(
                x, pools[j], table, pos, true_len, scratch, live)
            new_pools.append(pool)
            stats.append(st)
        zero = jnp.int32(0)
        return (x[:, -1, :1].astype(jnp.float32), new_pools, [],
                self._counter_vector(stats, (
                    zero, zero, zero, true_len // self.block_length)))

    def _compiled_fused_decode_fn(self, pnames, params, cache_key,
                                  paged=False):
        """(p_list, b_list, pools, [], block_tables, tok [B, W],
        pos, temp, top_k, top_p, seed_lo, seed_hi, ctr, eos, rem,
        flags) -> (report [B, W + 4], done, new_tok, new_pos, new_ctr,
        new_rem, pools, [], counters, new_flags): ``StepSpec``'s
        contract.  Pools donated.  The sampling lanes are taken and
        not read (sampled requests are refused at ``submit``)."""
        if not paged:
            raise NotImplementedError(
                "the K/V pools are paged: no contiguous step")

        def body(pools, no_v, tables, tok, pos, _temp, _top_k, _top_p,
                 _slo, _shi, ctr, eos, rem, flags):
            return self._fused_step_slots(tok, pools, no_v, tables, pos,
                                          ctr, eos, rem, flags)
        return self._program("fused_decode", cache_key, params, pnames,
                             body)

    def _compiled_paged_chunk_prefill_fn(self, pnames, params,
                                         cache_key):
        """(p_list, b_list, pools, [], ids [1, C], block_table, pos,
        true_len, scratch) -> (handle [1, 1], pools, [], counters).
        Pools donated."""
        def body(pools, _v, ids, table, pos, true_len, scratch):
            return self._chunk_prefill_tick_paged(
                ids, pools, table, pos, true_len, scratch)
        return self._program("paged_chunk_prefill", cache_key, params,
                             pnames, body)

    # -- the serving seam ----------------------------------------------
    def decode_rows(self, pos, ahead, table_rows, block_size,
                    padded=True):
        """``ServingSpec.decode_rows`` (``attn_kernel_rows`` where not
        ``padded``).  The walk reads rows BELOW pos: the block itself
        (the ``ahead`` of the dispatch about to be issued) is not
        read."""
        return self.blocks[0].attn.decode_rows(
            pos, ahead - self.block_length, table_rows, block_size, padded)

    def serving_spec(self):
        from ..distributed.moe import grouped_matmul_impl
        cfg, W = self.config, self.block_length
        attn = self.blocks[0].attn
        k_proj = attn.k_proj
        dtype = getattr(k_proj, "compute_dtype", None) \
            or k_proj.weight._data.dtype
        step = "the step carries a block of rows a lane: "
        return ServingSpec(
            kv=KVRowSpec(len(self.blocks), dtype, (("kv", (
                2 * cfg["num_key_value_heads"] * cfg["head_dim"],)),)),
            max_positions=cfg["max_position_embeddings"],
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            counters=MOE_COUNTERS + STEP_COUNTERS,
            kernels={"moe.experts": grouped_matmul_impl()},
            decode_rows=self.decode_rows,
            attn_kernel_rows=functools.partial(self.decode_rows,
                                               padded=False),
            attn_core=attn.serving_core,
            attn_kernel_check=functools.partial(
                walk_kernel_check, [b.attn for b in self.blocks], W),
            step=StepSpec(rows=W, align=W, open=self.open_block,
                          report={"rows": W,
                                  "steps": self.denoising_steps}),
            unsupported={
                "contiguous": "a contiguous [slots, L] K/V buffer and "
                              "its step / prefill programs",
                "unchunked_prefill": "the per-length paged prefill "
                                     "program under the block-causal "
                                     "mask",
                "ragged": "a block-causal mask and grouped K/V heads in "
                          "ops/ragged_paged_attn.py, and the step's "
                          "state in the ragged window program",
                "spec": step + "a draft-and-verify window over it is "
                        "not written",
                "kv_int8": step + "QuantKV's touched-block rewrite "
                           "takes one row a slot",
                "mp": "grouped K/V heads over 'mp' and an expert axis "
                      "in SERVING_SPECS are not written",
                "lora": "LoRA banks fold into GPTAttention.out_proj; "
                        "o_proj here has no lane-gathered form",
                "offload": "rows a denoise pass wrote must never be "
                           "demoted: the host tier has no such rule",
                "migration": step + "the wire carries one current "
                             "token a stream, not a block's state",
                "sampling": "the sampled form of the unmasking rule "
                            "(x0 drawn through sample_lanes' keys, c "
                            "its probability)",
            })

    def serving_linear_stacks(self):
        return list(self.blocks)
