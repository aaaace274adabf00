"""Decoder most of whose layers remember a STATE and no cached row: a
gated short convolution in three layers of four, grouped-query
attention in the fourth, sigmoid-routed experts with no shared one —
the LFM2-MoE family (LFM2-8B-A1B), served on ``serving.Engine``'s
paged path.

Layer equations (d hidden, H query heads, K key/value heads, hd the
head size, L taps, E routed experts, k of them a token; ``RMSNorm(x) =
x / sqrt(mean(x^2) + eps) * gain``, no bias anywhere):

* embedding ``x_0 = Emb[id]``; head ``logits = RMSNorm(x_n) Emb^T``
  (tied).
* block l of kind ``layer_types[l]``: ``x <- x + Op(RMSNorm_op(x))``,
  then ``x <- x + FF(RMSNorm_ffn(x))``.
* ``Op`` of a ``conv`` layer, ``a`` its normed input: ``[b; c; u] = a
  W_in`` (``[d, 3d]``, three chunks of d in that order), ``s_t = b_t *
  u_t``, ``v_t = sum_j w_j * s_{t - (L - 1) + j}`` with ``w`` ``[d, L]``
  a channel's own taps (a depthwise causal convolution; s at negative
  positions is 0), ``Op = (c_t * v_t) W_out``.  **What the layer
  remembers of its whole context is ``(s_{t-L+1} .. s_{t-1})``:
  ``(L - 1) d`` numbers a SEQUENCE**, where an attention layer keeps a
  row a position.
* ``Op`` of a ``full_attention`` layer: ``sdar_moe.GQAttention`` under
  the causal mask (q and k RMS-normalised a head, then rotated; query
  head h reads K/V head ``h // (H / K)``; K and V flat in one ``[2 K
  hd]`` row of one pool a layer: at hd 64 a head is a 64-lane slice of
  the fetched rows).
* ``FF``, the first ``num_dense_layers`` layers: SwiGLU of width
  ``intermediate_size``.  The others: ``s = sigmoid(h W_r)`` in float32;
  the k experts are the top k of ``s + expert_bias``; ``w = s[choice]``
  over their sum ``+ 1e-6`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``FF(h) = sum_j w_j E_{c_j}(h)``, every
  expert a SwiGLU of width ``moe_intermediate_size``, dropless
  (``mla_moe.RoutedFFN`` without a shared expert).

**The state lives in the blocks of the paged cache**
(``models/programs.py`` ``KVRowSpec.block_rows``).  Beside the attention
layers' row pools ``[num_blocks, block_size, 2 K hd]`` there is ONE pool
``[num_blocks, n_conv (L - 1) d]``: one TAIL a block, under the same
layer-invariant block id, every conv layer's state side by side in it,
layer-major (a step then gathers its lanes' tails once and writes each
lane's once, not once a layer: 64 in-place updates a step, not 640).
The rule: *the state before position p is the tail of the block that
holds position p - 1* (zeros at p = 0), and
whoever computes positions up to ``e - 1`` of a block writes that
block's tail ``(s_{e-L+1} .. s_{e-1})``.  A full block's tail is final:
it is the snapshot at the block's edge that a prefix hit, a resumed
request or the next chunk continues from; a partial block is its slot's
own.  So nothing a slot is kept: admission, adoption, eviction,
preemption and resume move block ids as for every other model and the
state follows.  The decode step gathers each lane's tail from
``table[(pos - 1) // bs]``, computes one position and writes the tail
of ``table[pos // bs]`` (a parked lane's table is the scratch block);
the chunk program starts from the tail of the block that holds ``pos -
1``, convolves its C positions at once (L shifted multiply-adds over
``[state; s]``) and writes the tail of EVERY block its ``true_len``
positions touch, the last, partial one too.

A lane that is still prefilling takes a discarded decode step in every
tick, at its next chunk's first row, and so writes the tail of the
block that holds that row.  The engine keeps chunk starts on block
edges for a model with per-block rows (``prefill_chunk`` a multiple of
``kv_block_size``), so that block is one the next chunk touches and
rewrites before anything reads it, never the one the chunk's own state
comes from; the device runs the patch that parks the lane there, the
step and the chunk in dispatch order.

What is not here: a state through ``spec_k``'s rewind, migration and
the host tier (their payloads are rows), int8 pools, rope scaling, the
load-balance loss.
"""
from __future__ import annotations

import functools

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from .mla_moe import MOE_COUNTERS, GatedMLP, RMSNorm, RoutedFFN, _lin
from .programs import (
    KVRowSpec, ServedModel, ServingSpec, _scoped, sample_lanes,
    slot_sample_keys)
from .sdar_moe import GQAttention, walk_kernel_check

KINDS = ("conv", "full_attention")

# this family's counters, after the routed layers' four, in the order
# of the vector the step programs return: positions through the conv
# operators (live lanes x layers in a step, ``true_len`` x layers in a
# chunk); tails written that are final (their block's last position was
# computed), x layers; chunk programs by where their state came from;
# the cached rows the decode lanes' queries see and those a chunk's do,
# summed over the attention layers
LFM2_COUNTERS = (("conv_positions", None),
                 ("conv_tails_final", None),
                 ("conv_starts_from_tail", None),
                 ("conv_starts_from_zero", None),
                 ("attn_rows_seen", None),
                 ("attn_rows_seen_chunk", None))


class ShortConv(nn.Layer):
    """The gated short convolution (module docstring): ``in_proj``
    ``[d, 3d]`` gives b, c, u; ``conv_weight`` ``[d, L]`` a channel's
    taps, the last one the position's own; ``out_proj`` ``[d, d]``."""

    def __init__(self, hidden, taps):
        super().__init__()
        if taps < 2:
            raise ValueError(f"conv_L_cache ({taps}): a convolution of "
                             "at least two taps keeps a state")
        self.hidden, self.taps = hidden, taps
        self.in_proj = nn.Linear(hidden, 3 * hidden, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [hidden, taps], default_initializer=I.Normal(0.0, 0.02))
        self.out_proj = nn.Linear(hidden, hidden, bias_attr=False)

    def gates(self, h):
        """h [B, S, d] -> (s = b * u, c), each [B, S, d]."""
        d = self.hidden
        a = _lin(self.in_proj, h)
        return a[..., :d] * a[..., 2 * d:], a[..., d:2 * d]

    def convolve(self, ext):
        """``ext`` [B, L - 1 + S, d], the state then the S new inputs
        -> v [B, S, d] in float32: tap j times the input ``L - 1 - j``
        positions back."""
        import jax.numpy as jnp
        S = ext.shape[1] - self.taps + 1
        w = self.conv_weight._data.astype(jnp.float32)
        return sum(w[:, j] * ext[:, j:j + S].astype(jnp.float32)
                   for j in range(self.taps))

    @_scoped("conv.mix")
    def step(self, h, state):
        """One position a slot from ``state`` [B, L - 1, d] (the inputs
        of the positions before it; zeros at position 0).  h [B, 1, d].
        Returns (c, v, new state): what ``output`` takes, [B, 1, d], v
        in float32, and the state after this position."""
        import jax.numpy as jnp
        s, c = self.gates(h)
        ext = jnp.concatenate([state, s], axis=1)
        return c, self.convolve(ext), ext[:, 1:]

    @_scoped("conv.mix")
    def chunk(self, h, state, ends):
        """C positions of ONE slot from ``state`` [1, L - 1, d].  h
        [1, C, d]; ``ends`` int32 [n], for each block the chunk can
        touch the chunk's row that follows the block's last computed
        one (``tail_plan``).  Returns (c, v, tails [n, (L - 1) d]: of
        block j the inputs of the ``L - 1`` positions that end with its
        last computed one; ``ext`` reaches back into the state where
        that is the chunk's first)."""
        import jax.numpy as jnp
        s, c = self.gates(h)
        ext = jnp.concatenate([state, s], axis=1)
        rows = ext[0][ends[:, None] + jnp.arange(self.taps - 1)[None]]
        return c, self.convolve(ext), rows.reshape(ends.shape[0], -1)

    @_scoped("conv.out")
    def output(self, c, v):
        return _lin(self.out_proj, (c * v).astype(c.dtype))

    def forward(self, h):
        """Uncached, over whole sequences from a zero state."""
        import jax.numpy as jnp
        s, c = self.gates(h)
        ext = jnp.concatenate([jnp.zeros(
            (h.shape[0], self.taps - 1, self.hidden), s.dtype), s], axis=1)
        return self.output(c, self.convolve(ext))


class Lfm2Block(nn.Layer):
    """``x += Op(RMSNorm(x)); x += FF(RMSNorm(x))``: ``op`` a
    ``ShortConv`` or a causal ``GQAttention``, ``ffn`` a ``GatedMLP``
    (a leading dense layer) or a ``RoutedFFN`` without a shared
    expert."""

    def __init__(self, cfg, kind, routed):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["norm_eps"]
        self.kind, self.routed = kind, routed
        self.operator_norm = RMSNorm(d, eps)
        if kind == KINDS[0]:
            self.conv = ShortConv(d, cfg["conv_L_cache"])
        else:
            self.attn = GQAttention(
                d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                d // cfg["num_attention_heads"], cfg["rope_theta"], eps,
                block_length=1)
        self.ffn_norm = RMSNorm(d, eps)
        self.ffn = (RoutedFFN(d, cfg["moe_intermediate_size"],
                              cfg["num_experts"],
                              cfg["num_experts_per_tok"], 0,
                              cfg.get("routed_scaling_factor", 1.0),
                              cfg.get("norm_topk_prob", True),
                              norm_eps=1e-6)
                    if routed else GatedMLP(d, cfg["intermediate_size"]))

    @_scoped("mlp")
    def feed_forward(self, x, live):
        """x [B, S, D], live [B, S] -> (x + FF(RMSNorm(x)), stats or
        None)."""
        h = self.ffn_norm(x)
        if not self.routed:
            return x + self.ffn(h), None
        y, stats = self.ffn(h.reshape(-1, h.shape[-1]), live.reshape(-1))
        return x + y.reshape(x.shape), stats

    def step_slots_paged(self, x, held, tables, pos, live):
        """``held``: the layer's row pool (attention) or its lanes'
        states [B, L - 1, d] (conv).  Returns (x, stats, the pool or
        the new states)."""
        import jax.numpy as jnp
        h = self.operator_norm(x)
        if self.kind == KINDS[0]:
            c, v, held = self.conv.step(h, held)
            a = self.conv.output(c, v)
        else:
            a, held = self.attn.step_slots_paged(
                h, held, tables, pos, jnp.where(live, pos, 0))
        return (*self.feed_forward(x + a, live[:, None]), held)

    def prefill_chunk_paged(self, x, held, table, pos, true_len, scratch,
                            live, ends):
        """``held``: the layer's row pool (attention) or the slot's
        state [1, L - 1, d] (conv).  Returns (x, stats, the pool or the
        touched blocks' tails [n, (L - 1) d])."""
        h = self.operator_norm(x)
        if self.kind == KINDS[0]:
            c, v, held = self.conv.chunk(h, held, ends)
            a = self.conv.output(c, v)
        else:
            a, held = self.attn.prefill_chunk_paged(
                h, held, table, pos, true_len, scratch)
        return (*self.feed_forward(x + a, live[None, :]), held)

    def forward(self, x):
        import jax.numpy as jnp
        h = self.operator_norm(x)
        x = x + (self.conv(h) if self.kind == KINDS[0] else self.attn(h))
        return self.feed_forward(x, jnp.ones(x.shape[:2], bool))[0]


class Lfm2MoeModel(ServedModel, nn.Layer):
    """Decoder-only LM of the module's docstring.  ``config`` holds the
    published keys (``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``conv_L_cache``, ``conv_bias``,
    ``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
    ``num_experts_per_tok``, ``num_dense_layers``, ``num_hidden_layers``,
    ``layer_types``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``use_expert_bias``, ``vocab_size``, ``max_position_embeddings``,
    ``norm_eps``, ``rope_theta``).  Build it under ``nn.LazyGuard()``
    to declare the parameters without values."""

    def __init__(self, config):
        super().__init__()
        cfg = dict(config)
        if cfg.get("conv_bias"):
            raise ValueError("conv_bias is not written: the family's "
                             "projections and taps have no bias")
        if not cfg.get("use_expert_bias", True):
            raise ValueError("the gate selects by score + expert_bias")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not written")
        kinds = list(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"] \
                or set(kinds) - set(KINDS):
            raise ValueError(
                f"layer_types has to name one of {KINDS} for each of "
                f"the {cfg['num_hidden_layers']} layers")
        if KINDS[1] not in kinds:
            raise ValueError(
                "a model without an attention layer keeps no cached "
                "row: KVRowSpec wants one (layer_types)")
        self.config = cfg
        d = cfg["hidden_size"]
        self.embed = self.create_parameter(
            [cfg["vocab_size"], d],
            default_initializer=I.Normal(0.0, 0.02))
        self.blocks = nn.LayerList([
            Lfm2Block(cfg, kind, i >= cfg["num_dense_layers"])
            for i, kind in enumerate(kinds)])
        self.norm = RMSNorm(d, cfg["norm_eps"])
        # which of its kind's pools a block's is
        seen = {k: 0 for k in KINDS}
        self._pool_of = []
        for kind in kinds:
            self._pool_of.append(seen[kind])
            seen[kind] += 1

    @property
    def routed_layers(self):
        return sum(1 for b in self.blocks if b.routed)

    def layers_of(self, kind):
        return sum(1 for b in self.blocks if b.kind == kind)

    @_scoped("lm_head")
    def _head(self, x):
        """The final norm and the tied head: the embedding's rows are
        the head's columns, contracted where they lie."""
        import jax
        import jax.numpy as jnp
        h = self.norm(x)
        emb = self.embed._data
        return jax.lax.dot_general(
            h, emb.astype(h.dtype), (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def forward(self, input_ids):
        """Uncached logits [B, S, V] (float32)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else input_ids
        x = self.embed._data[ids]
        for blk in self.blocks:
            x = blk(x)
        return Tensor(self._head(x))

    def _counter_vector(self, stats, conv, seen, seen_chunk):
        """int32 [10]: the routed layers' four (``MOE_COUNTERS``), then
        ``LFM2_COUNTERS``; ``conv`` the four of the conv layers."""
        import jax.numpy as jnp
        s = sum(stats) if stats else jnp.zeros((3,), jnp.int32)
        slots = self.routed_layers * self.config["num_experts"]
        return jnp.stack([s[0], s[1], jnp.int32(slots), s[2], *conv,
                          seen, seen_chunk]).astype(jnp.int32)

    # -- the tails ------------------------------------------------------
    def _states(self, tails, blocks, pos):
        """The conv layers' states [n_conv][B, L - 1, d] out of the
        tails ``[B, n_conv (L - 1) d]`` of ``blocks`` [B], read once
        for all layers; zeros where ``pos`` [B] is 0.  One slice a
        lane of the pool as it lies: given ``tails[blocks]`` the v5e
        compiler splits a gather of rows this wide in two and first
        copies each HALF OF THE POOL out (1.2 GB a step, 8 ms by its
        own estimate; the compile for the described v5e, PR 46)."""
        import jax
        import jax.numpy as jnp
        d, keep = self.config["hidden_size"], self.config["conv_L_cache"] - 1
        got = jnp.concatenate([jax.lax.dynamic_slice(
            tails, (blocks[b], 0), (1, tails.shape[1]))
            for b in range(blocks.shape[0])])
        got = jnp.where((pos > 0)[:, None], got, 0)
        return [got[:, j * keep * d:(j + 1) * keep * d].reshape(-1, keep, d)
                for j in range(self.layers_of(KINDS[0]))]

    @staticmethod
    def tail_plan(pos, true_len, C, bs):
        """Which blocks a chunk of C positions at ``pos`` touches and
        where each one's tail ends: ``n = ceil((C + bs - 1) / bs)``
        blocks wherever ``pos`` lies in its block
        (``write_chunk_rows``); block j holds the chunk's rows ``[j bs
        - off, (j + 1) bs - off)``, the first ``true_len`` are
        computed.  Returns (ends int32 [n]: the chunk's row after
        block j's last computed one, lands bool [n]: a row was
        computed in it, final bool [n]: its last row was)."""
        import jax.numpy as jnp
        n = (C + 2 * bs - 2) // bs
        off = pos % bs
        j = jnp.arange(n)
        ends = jnp.clip(jnp.minimum((j + 1) * bs - off, true_len), 0, C)
        lands = ends > jnp.maximum(j * bs - off, 0)
        return ends, lands, lands & ((pos + ends) % bs == 0)

    # -- step programs -------------------------------------------------
    def _fused_decode_tick_slots(self, tok, pools, tails, tables, pos,
                                 temp, top_k, top_p, seed_lo, seed_hi,
                                 ctr, eos, rem):
        """``AfmoeModel._fused_decode_tick_slots`` over this family's
        two lists of pools: one token a slot through every block,
        sampling and the stop condition on the device, the same outputs,
        the counter vector last.  Each lane's states come from the tail
        of the block that holds ``pos - 1`` and the new ones go into
        the tail of the block that holds ``pos``, one in-place update a
        slot of the pool as it lies (``GQAttention.step_slots_paged``).
        A lane without budget (``rem <= 0``: parked) walks no cached row
        and is routed to no expert; its convolution runs and writes the
        tail its table names (the scratch block's)."""
        import jax
        import jax.numpy as jnp
        live = rem > 0
        B, bs = tok.shape[0], pools[0].shape[1]
        lane = jnp.arange(B)
        x = self.embed._data[tok[:, 0]][:, None, :]
        with jax.named_scope("conv.mix"):
            states = self._states(
                tails[0], tables[lane, jnp.maximum(pos - 1, 0) // bs], pos)
        held = {KINDS[0]: states, KINDS[1]: list(pools)}
        stats = []
        for blk, j in zip(self.blocks, self._pool_of):
            x, st, held[blk.kind][j] = blk.step_slots_paged(
                x, held[blk.kind][j], tables, pos, live)
            if st is not None:
                stats.append(st)
        with jax.named_scope("conv.mix"):
            new = jnp.concatenate(
                [st.reshape(B, -1) for st in held[KINDS[0]]],
                axis=1).astype(tails[0].dtype)
            blocks, pool = tables[lane, pos // bs], tails[0]
            for b in range(B):
                pool = jax.lax.dynamic_update_slice(
                    pool, new[b:b + 1], (blocks[b], 0))
        last = self._head(x)[:, -1, :]
        L = tables.shape[1] * bs
        keys = slot_sample_keys(seed_lo, seed_hi, ctr)
        sampled = sample_lanes(last, temp, top_k, top_p, keys)
        ids = jnp.where(live, sampled, tok[:, 0])
        hit_eos = live & (eos >= 0) & (ids == eos)
        new_rem = jnp.where(live, jnp.where(hit_eos, 0, rem - 1), rem)
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        new_pos = jnp.where(live, jnp.minimum(pos + 1, L - 1), pos)
        new_ctr = jnp.where(live, ctr + 1, ctr)
        n_conv, n_attn = (self.layers_of(k) for k in KINDS)
        lanes = jnp.sum(live, dtype=jnp.int32)
        conv = (n_conv * lanes,
                n_conv * jnp.sum(live & ((pos + 1) % bs == 0),
                                 dtype=jnp.int32), 0, 0)
        return (ids, done, ids[:, None], new_pos, new_ctr, new_rem,
                held[KINDS[1]], [pool], self._counter_vector(
                    stats, conv,
                    n_attn * jnp.sum(jnp.where(live, pos + 1, 0)), 0))

    def _chunk_prefill_tick_paged(self, toks, pools, tails, table, pos,
                                  true_len, scratch):
        """C prompt tokens of one slot through every block; the head
        runs on the last REAL position only.  The states come from the
        tail of the block that holds ``pos - 1``; the tail of every
        block the first ``true_len`` positions touch is written, a
        block no position lands in being taken to be ``scratch``
        (``write_chunk_rows``).  Returns (last logits [1, V], pools,
        tails, counters)."""
        import jax
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        C, bs = toks.shape[1], pools[0].shape[1]
        live = jnp.arange(C) < true_len
        x = self.embed._data[toks]
        with jax.named_scope("conv.mix"):
            at = jnp.reshape(pos, (1,))
            states = self._states(
                tails[0], table[jnp.maximum(at - 1, 0) // bs], at)
            ends, lands, final = self.tail_plan(pos, true_len, C, bs)
        held = {KINDS[0]: states, KINDS[1]: list(pools)}
        stats = []
        for blk, j in zip(self.blocks, self._pool_of):
            x, st, held[blk.kind][j] = blk.prefill_chunk_paged(
                x, held[blk.kind][j], table, pos, true_len, scratch, live,
                ends)
            if st is not None:
                stats.append(st)
        with jax.named_scope("conv.mix"):
            new = jnp.concatenate(held[KINDS[0]], axis=1).astype(
                tails[0].dtype)
            n = ends.shape[0]
            blocks = jnp.where(lands, table[jnp.minimum(
                pos // bs + jnp.arange(n), table.shape[0] - 1)], scratch)
            pool = tails[0]
            for j in range(n):
                pool = jax.lax.dynamic_update_slice(
                    pool, new[j:j + 1], (blocks[j], 0))
        last_h = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        n_conv, n_attn = (self.layers_of(k) for k in KINDS)
        from_tail = (pos > 0).astype(jnp.int32)
        # the union of what the chunk's queries see: the first one's
        # rows and every later query's own
        return (self._head(last_h)[:, -1, :], held[KINDS[1]], [pool],
                self._counter_vector(
                    stats, (n_conv * true_len,
                            n_conv * jnp.sum(final, dtype=jnp.int32),
                            from_tail, 1 - from_tail),
                    0, n_attn * (pos + true_len)))

    def _compiled_fused_decode_fn(self, pnames, params, cache_key,
                                  paged=False):
        """(p_list, b_list, pools, tails, block_tables, tok, pos, temp,
        top_k, top_p, seed_lo, seed_hi, ctr, eos, rem) -> (ids, done,
        new_tok, new_pos, new_ctr, new_rem, pools, tails, counters).
        Both lists of pools donated."""
        if not paged:
            raise NotImplementedError(
                "the pools are paged: no contiguous decode")

        def body(pools, tails, tables, tok, pos, *lanes):
            return self._fused_decode_tick_slots(tok, pools, tails,
                                                 tables, pos, *lanes)
        return self._program("fused_decode", cache_key, params, pnames,
                             body)

    def _compiled_paged_chunk_prefill_fn(self, pnames, params,
                                         cache_key):
        """(p_list, b_list, pools, tails, ids [1, C], block_table, pos,
        true_len, scratch) -> (last logits [1, V], pools, tails,
        counters).  Both lists of pools donated."""
        def body(pools, tails, ids, table, pos, true_len, scratch):
            return self._chunk_prefill_tick_paged(
                ids, pools, tails, table, pos, true_len, scratch)
        return self._program("paged_chunk_prefill", cache_key, params,
                             pnames, body)

    # -- the serving seam ----------------------------------------------
    def decode_rows(self, pos, ahead, table_rows, block_size,
                    padded=True):
        """``ServingSpec.decode_rows`` (``attn_kernel_rows`` where not
        ``padded``): the rows one decode dispatch
        fetches in an ATTENTION layer (a conv layer walks none).  The
        step's own row comes from the step itself: the walk reads rows
        below ``pos``."""
        return self._attention().decode_rows(pos, ahead - 1, table_rows,
                                             block_size, padded)

    def _attention(self):
        """The first attention layer's ``GQAttention`` (they are all
        alike)."""
        return next(b for b in self.blocks if b.kind == KINDS[1]).attn

    def serving_spec(self):
        from ..distributed.moe import grouped_matmul_impl
        cfg = self.config
        d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
        n_conv, n_attn = (self.layers_of(k) for k in KINDS)
        k_proj = self._attention().k_proj
        dtype = getattr(k_proj, "compute_dtype", None) \
            or k_proj.weight._data.dtype
        tails = "the conv layers' state lies in their blocks' tails: "
        rows = [("kv", (2 * cfg["num_key_value_heads"]
                        * (d // cfg["num_attention_heads"]),))]
        return ServingSpec(
            kv=KVRowSpec(n_attn, dtype, rows, block_rows=(
                ("conv", n_conv * (taps - 1) * d),) if n_conv else ()),
            max_positions=cfg["max_position_embeddings"],
            vocab_size=cfg["vocab_size"], hidden_size=d,
            counters=MOE_COUNTERS + LFM2_COUNTERS,
            kernels={"moe.experts": grouped_matmul_impl()},
            decode_rows=self.decode_rows,
            attn_kernel_rows=functools.partial(self.decode_rows,
                                               padded=False),
            attn_core=self._attention().serving_core,
            attn_kernel_check=functools.partial(
                walk_kernel_check,
                [b.attn for b in self.blocks if b.kind == KINDS[1]], 1),
            state={"conv": [taps - 1, d],
                   "layers": {"conv": n_conv, "attention": n_attn},
                   "per": "block"},
            unsupported={
                "contiguous": "a contiguous [slots, L] K/V buffer, a "
                              "state a slot and their step / prefill "
                              "programs",
                "unchunked_prefill": "the per-length paged prefill "
                                     "program over rows and tails",
                "ragged": "grouped K/V heads and a state beside the "
                          "rows in ops/ragged_paged_attn.py",
                "spec": tails + "a rejected draft's rewind would need "
                        "the state before the rejected rows, which no "
                        "block holds",
                "kv_int8": "K and V lie flat in one [2 K hd] row: "
                           "quant.py's QuantKV scales are per head; a "
                           "tail has no quantized form",
                "mp": "grouped K/V heads, a state's channels and an "
                      "expert axis over 'mp' in SERVING_SPECS",
                "lora": "LoRA banks fold into GPTAttention.out_proj; "
                        "o_proj and out_proj here have no lane-gathered "
                        "form",
                "offload": tails + "HostBlockStore entries are (layers, "
                           "2, bs, H, hd) rows",
                "migration": tails + "the migration wire's (layers, "
                             "K|V, blocks, bs, H, hd) payload moves "
                             "rows only",
            })

    def serving_linear_stacks(self):
        return list(self.blocks)
