"""GPT family (flagship LM).

Reference parity: PaddleNLP-style GPT built on the reference's
``nn.TransformerDecoder`` stack (``python/paddle/nn/layer/transformer.py``)
with Megatron TP via ``paddle.distributed.split``
(``distributed/collective.py:492,526``).

TPU-native design: pre-LN causal transformer whose attention goes through
``F.scaled_dot_product_attention`` (on one TPU chip a Pallas blockwise
kernel from the measured crossover up, 512 positions at heads of 64:
``nn/functional/attention.py`` ``attention_path``; the XLA form below it,
on a mesh and off the TPU); tensor
parallelism via Column/RowParallelLinear specs consumed by pjit; the
``GPTPipe`` variant exposes the identical-block structure the SPMD pipeline
engine needs (parallel/pipeline.py).  BASELINE configs 4/5 (GPT-2 345M
sharding stage2, GPT-3 1.3B hybrid) instantiate from ``GPT_CONFIGS``.
"""
from __future__ import annotations

import functools
import math
import threading
from contextlib import contextmanager as _contextmanager

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..core.tensor import Tensor
from ..nn.layer.scan import ScanLayers
from ..ops import reshape, transpose, concat
from .programs import (  # noqa: F401
    KVRowSpec, ServedModel, ServingSpec, _backend, _jit_named,
    _over_a_mesh, _scoped, filter_logits_lanes, sample_lanes,
    slot_attn_core, slot_sample_keys, walk_chunk, walk_group, walk_plan,
    walk_rows,
)


_sample_rows_jit = None  # lazily-jitted single-call sampler (below)


def _is_quant_kv(pool):
    """True when a paged K/V pool is a ``serving/quant.py``
    ``QuantKV`` (int8 codes + per-block per-head scales) rather than
    a plain fp array — the paged attention paths branch on this to
    quantize at block write and dequantize at gather."""
    return hasattr(pool, "codes") and hasattr(pool, "scale")


def _gather_blocks(pool, cols):
    """Rows of the blocks ``cols`` [n, k] name, block after block:
    ``[n, k * bs, H, hd]`` of a ``[NB, bs, H, hd]`` pool.  A ``QuantKV``
    pool dequantizes the fetched blocks only."""
    if _is_quant_kv(pool):
        from ..serving.quant import paged_gather
        return paged_gather(pool, cols)
    blocks = pool[cols]                         # [n, k, bs, H, hd]
    return blocks.reshape(cols.shape[0], -1, *blocks.shape[3:])


# Per-slot LoRA context (serving/lora.py).  Thread-local because jax
# traces on the calling thread while sibling engines over ONE model
# may trace concurrently — a plain module global would leak one
# engine's adapter banks into another's program.
_LORA_TLS = threading.local()


@_contextmanager
def _lora_scope(lora):
    """Activate per-slot LoRA deltas for every ``_lora_out`` call
    traced on this thread: ``lora`` is ``(adapter_id [B], a_bank
    [n_lanes, n_layers, r, E], b_bank [n_lanes, n_layers, E, r])``;
    empty means base model (the scope is a no-op, so the compiled
    builders can take ``*lora`` varargs and engines without adapters
    trace exactly the program they always traced)."""
    if not lora:
        yield
        return
    prev = getattr(_LORA_TLS, "ctx", None)
    _LORA_TLS.ctx = tuple(lora)
    try:
        yield
    finally:
        _LORA_TLS.ctx = prev


def sample_rows(last, temperature, top_k, top_p, seed_lo, seed_hi,
                ctr):
    """Standalone jitted twin of the fused dispatches' sampling tail:
    derive per-row keys from the seed words + counters and pick one
    token per row of ``last`` [B, V] (``GPTModel._sample_lanes``).
    The serving engine's first-token pick (prefill / final chunk)
    calls this instead of running the ops eagerly — eager
    ``lax.cond`` re-traces its branch closures on every call, which
    would recompile per admission; this wrapper has stable identity,
    so it compiles once per (B, V) shape for the whole process."""
    global _sample_rows_jit
    if _sample_rows_jit is None:
        import jax

        def pick(last, temperature, top_k, top_p, lo, hi, c):
            keys = GPTModel._slot_sample_keys(lo, hi, c)
            return GPTModel._sample_lanes(last, temperature, top_k,
                                          top_p, keys)

        _sample_rows_jit = jax.jit(pick)
    return _sample_rows_jit(last, temperature, top_k, top_p, seed_lo,
                            seed_hi, ctr)


GPT_CONFIGS = {
    # name: (n_layer, hidden, heads, ffn_mult, vocab, max_seq)
    "gpt2-small": dict(num_layers=12, hidden_size=768, num_heads=12,
                       vocab_size=50304, max_position=1024),
    "gpt2-medium": dict(num_layers=24, hidden_size=1024, num_heads=16,
                        vocab_size=50304, max_position=1024),  # 345M
    "gpt2-large": dict(num_layers=36, hidden_size=1280, num_heads=20,
                       vocab_size=50304, max_position=1024),
    "gpt3-1.3b": dict(num_layers=24, hidden_size=2048, num_heads=16,
                      vocab_size=50304, max_position=2048),
    "tiny": dict(num_layers=2, hidden_size=64, num_heads=4,
                 vocab_size=128, max_position=64),
}


class GPTEmbeddings(nn.Layer):
    def __init__(self, vocab_size, hidden_size, max_position,
                 dropout=0.1, use_mp=False):
        super().__init__()
        if use_mp:
            from ..distributed.sharding import VocabParallelEmbedding
            self.word_embeddings = VocabParallelEmbedding(
                vocab_size, hidden_size)
        else:
            self.word_embeddings = nn.Embedding(
                vocab_size, hidden_size,
                weight_attr=nn.ParamAttr(
                    initializer=I.Normal(0.0, 0.02)))
        self.position_embeddings = nn.Embedding(
            max_position, hidden_size,
            weight_attr=nn.ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.dropout = nn.Dropout(dropout)

    def forward(self, input_ids, position_offset=0, position_ids=None):
        import jax.numpy as jnp
        if position_ids is None:
            seq = input_ids.shape[-1]
            pos = Tensor(jnp.arange(seq, dtype=jnp.int32)
                         + position_offset)
        else:
            pos = position_ids  # packed sequences: per-doc reset
        emb = self.word_embeddings(input_ids) + \
            self.position_embeddings(pos)
        return self.dropout(emb)


class GPTAttention(nn.Layer):
    """Causal self-attention with fused QKV (one MXU matmul)."""

    def __init__(self, hidden_size, num_heads, dropout=0.1, use_mp=False,
                 use_sp=False):
        super().__init__()
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.hidden_size = hidden_size
        self.dropout = dropout
        self.use_mp = use_mp
        # sequence parallelism: both variants apply attention-probability
        # dropout — the ring per (device, ring-step) block, ulysses in the
        # local attention after the all-to-all (distributed/ring.py)
        if use_sp not in (False, True, "ring", "ulysses"):
            raise ValueError(
                f"use_sp={use_sp!r}: expected False, True/'ring', or "
                "'ulysses'")
        self.use_sp = use_sp
        init = nn.ParamAttr(initializer=I.Normal(0.0, 0.02))
        if use_mp:
            # Einsum-form head-parallel projections: weights carry the head
            # axis explicitly ([E, 3, H, hd] / [H, hd, E]) so the 'mp'
            # sharding lives on H end-to-end and NO reshape ever crosses a
            # sharded dim.  The [b,s,3E]->[b,s,3,H,hd] reshape of the fused
            # layout forced XLA SPMD into "involuntary full
            # rematerialization" (it cannot re-tile an E split into an H
            # split without replicating).
            from jax.sharding import PartitionSpec as P
            self.qkv_weight = self.create_parameter(
                [hidden_size, 3, num_heads, self.head_dim], attr=init)
            self.qkv_weight.partition_spec = P(None, None, "mp", None)
            self.qkv_weight.is_distributed = True
            self.qkv_bias = self.create_parameter(
                [3, 1, num_heads, self.head_dim], is_bias=True)
            self.qkv_bias.partition_spec = P(None, None, "mp", None)
            self.qkv_bias.is_distributed = True
            self.out_weight = self.create_parameter(
                [num_heads, self.head_dim, hidden_size], attr=init)
            self.out_weight.partition_spec = P("mp", None, None)
            self.out_weight.is_distributed = True
            self.out_bias = self.create_parameter(
                [hidden_size], is_bias=True)
        else:
            self.qkv_proj = nn.Linear(hidden_size, 3 * hidden_size,
                                      weight_attr=init)
            self.out_proj = nn.Linear(hidden_size, hidden_size,
                                      weight_attr=init)
        # which layer's LoRA factors this attention gathers —
        # GPTModel.__init__ stamps the real index on the unrolled form
        self._layer_idx = 0

    def _lora_out(self, x):
        """Output projection plus the per-slot LoRA delta: the one
        injection point every decode/verify/chunk/ragged/forward path
        funnels through.  With no active ``_lora_scope`` this IS
        ``out_proj`` — zero cost, zero behavior change.  Inside a
        scope, each batch row's ``adapter_id`` gathers its lane's
        zero-padded [r, E]/[E, r] factors out of the banks as traced
        DATA (lane 0 is all-zeros = base model), so one compiled
        program serves every adapter mix:

            y = out_proj(x) + (x @ a_sel^T) @ b_sel^T

        (the LoRA alpha/rank scale is pre-folded into the stored b;
        serving/lora.py pins this against the merged-weights oracle).
        """
        y = self.out_proj(x)
        ctx = getattr(_LORA_TLS, "ctx", None)
        if ctx is None:
            return y
        import jax.numpy as jnp
        aid, a_bank, b_bank = ctx
        li = self._layer_idx
        a_sel = a_bank[:, li][aid]          # [B, r, E]
        b_sel = b_bank[:, li][aid]          # [B, E, r]
        xd = x._data
        h = jnp.einsum("bse,bre->bsr", xd, a_sel)
        d = jnp.einsum("bsr,ber->bse", h, b_sel)
        return y + Tensor(d.astype(y._data.dtype))

    def _qkv_mp(self, x):
        from ..ops import einsum
        qkv = einsum("bse,ethd->btshd", x, self.qkv_weight) + self.qkv_bias
        return qkv[:, 0], qkv[:, 1], qkv[:, 2]

    @_scoped("attention")
    def decode(self, x, k_buf, v_buf, pos):
        """Windowed decode against FIXED-SIZE cache buffers (compiled
        generation): writes the window's k/v at ``pos..pos+S-1`` via
        dynamic_update_slice and each query attends causally over
        positions <= its own (S=1 is the classic one-token step; S>1 is
        the speculative verify window).  Static shapes throughout — one
        XLA program decodes every step.

        x: Tensor [B, S, E]; k_buf/v_buf: [B, L, H, hd] arrays;
        pos: traced int scalar (window start).  Returns
        (out Tensor [B, S, E], k_buf, v_buf).
        """
        import math as _math
        import jax
        import jax.numpy as jnp

        S = x.shape[1]
        if self.use_mp:
            q, k, v = self._qkv_mp(x)
        else:
            b = x.shape[0]
            qkv = self.qkv_proj(x)
            qkv = reshape(qkv, [b, S, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        qa, ka, va = q._data, k._data, v._data
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, ka.astype(k_buf.dtype), (0, pos, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, va.astype(v_buf.dtype), (0, pos, 0, 0))
        scale = 1.0 / _math.sqrt(self.head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            qa.astype(jnp.float32),
                            k_buf.astype(jnp.float32)) * scale
        L = k_buf.shape[1]
        # query at window offset q sees cache positions <= pos + q
        visible = (jnp.arange(L)[None, :]
                   <= pos + jnp.arange(S)[:, None])       # [S, L]
        scores = jnp.where(visible[None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         v_buf.astype(jnp.float32)).astype(qa.dtype)
        return self._project(ctx), k_buf, v_buf

    def _qkv_step(self, x):
        """Fused QKV for a slot-pool window: Tensor [B, S, E] ->
        (qa, ka, va) arrays [B, S, H, hd] (S=1 is the one-token decode
        step; S=k+1 is the speculative verify window).  Shared by the
        contiguous and paged slot decode/verify paths."""
        if self.use_mp:
            q, k, v = self._qkv_mp(x)
        else:
            b, s = x.shape[0], x.shape[1]
            qkv = self.qkv_proj(x)
            qkv = reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        return q._data, k._data, v._data

    def _slot_attn(self, qa, k_src, v_src, tables, pos):
        """Windowed attention over each slot's cache rows: f32 scores,
        per-row causal mask (the query at window offset q of slot b
        sees cache positions <= pos[b] + q), f32 softmax, value
        contraction, output projection.  ONE implementation shared by
        ``decode_slots`` / ``decode_slots_paged`` (S=1) and
        ``verify_slots`` / ``verify_slots_paged`` (S=k+1 speculative
        verify), so both the paged path's token-parity guarantee AND
        the speculative verify's greedy parity are structural, not
        by-convention.

        The cache is read ``walk_chunk`` rows at a time, each slot as
        far as its OWN window: several slots are walked as a WORK LIST
        of (slot, chunk) items built on the device from ``pos``
        (``walk_plan``, the routed models' rule): slot b has
        ``ceil((pos[b] + S) / chunk)`` items and a parked lane (position
        0) none, a trip takes ``walk_group`` items whatever slots they
        belong to (the fewer the wider a position's K and V are), and
        the trip count ``ceil(items / group)`` is data, so rows past a
        slot's own context are never fetched and the program stays
        one.  An item's masked scores give a partial
        (maximum, denominator, context); a trip folds its items'
        partials into their slots' running f32 state, so the softmax is
        over exactly the visible positions (masked rows, the last
        trip's padding items and slots without an item contribute
        exactly 0; a slot without an item returns the projection of
        zeros: that is where the engine parks a lane, and a lane that
        decodes stands at 1 or later).  Rows are contracted in the
        dtype the cache holds them in with f32 accumulation — a product
        of two bf16 values is exact in f32, so an f32 copy of the rows
        would carry nothing they do not; the probabilities stay f32
        through the value contraction (``HIGHEST``: no single bf16 pass
        over them).  One slot walks its own chunks in turn, and a table
        no longer than one chunk is read whole, without a loop.  The
        form is chosen from the shapes alone.

        On one TPU, for paged floating-point pools at heads of whole
        128-lane tiles, the same online softmax over the same rows runs
        as ONE Pallas kernel instead (``slot_attn_core`` is the rule,
        ``_stream_attn`` the call): each live slot's pages stream
        through VMEM, the next step's copies in flight under the
        arithmetic, rows contracted as stored with f32 sums and f32
        probabilities, a parked lane a grid step and nothing else.

        qa [B, S, H, hd]; k_src/v_src ``[NB, bs, H, hd]`` pools (plain
        or ``QuantKV``) read through ``tables`` int32 [B, L // bs], or
        ``[B, L, H, hd]`` buffers where ``tables`` is None (the
        contiguous layout); pos int32 [B] (window start per slot).
        Returns out Tensor [B, S, E]."""
        import math as _math
        import jax
        import jax.numpy as jnp
        import numpy as np

        B, S, H = qa.shape[0], qa.shape[1], qa.shape[2]
        bs = None if tables is None else k_src.shape[1]
        table_rows = k_src.shape[1] if tables is None \
            else tables.shape[1] * bs
        core, _ = slot_attn_core(
            _backend(), paged=tables is not None,
            quant=_is_quant_kv(k_src), head_dim=qa.shape[3],
            mesh=_over_a_mesh(self.use_mp), table_rows=table_rows,
            block_size=bs)
        if core == "kernel":
            return self._project(self._stream_attn(
                qa, k_src, v_src, tables, pos))
        chunk = walk_chunk(table_rows, bs)
        n_chunks = -(-table_rows // chunk)
        scale = 1.0 / _math.sqrt(self.head_dim)
        q_end = pos[:, None] + jnp.arange(S)[None, :]          # [B, S]

        def partial(q, rows_of, visible):
            """Masked f32 scores [n, H, S, size] of the queries ``q``
            [n, S, H, hd] over ``rows_of(k_src)`` [n, size, H, hd], and
            ``context(p)``: the f32 context [n, S, H, hd] of weights p
            over ``rows_of(v_src)``."""
            kc = rows_of(k_src)
            dt = jnp.promote_types(q.dtype, kc.dtype)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(dt),
                            kc.astype(dt),
                            preferred_element_type=jnp.float32) * scale
            return (jnp.where(visible[:, None, :, :], sc, -1e30),
                    lambda p: jnp.einsum(
                        "bhqk,bkhd->bqhd", p,
                        rows_of(v_src).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST))

        def window(start, size):
            # rows start..start+size-1 of every slot
            if tables is None:
                return lambda src: jax.lax.dynamic_slice_in_dim(
                    src, start, size, axis=1)
            cols = jax.lax.dynamic_slice_in_dim(
                tables, start // bs, size // bs, axis=1)
            return lambda src: _gather_blocks(src, cols)

        def per_ctx(a):                  # [B, H, S] -> [B, S, H, 1]
            return jnp.transpose(a, (0, 2, 1))[..., None]

        # the last chunk of a table that is no whole number of chunks
        # starts early and masks the rows it shares with the one before
        last = table_rows - chunk

        def trip(c, carry):
            top, den, acc = carry
            start = jnp.minimum(c * chunk, last)
            at = start + jnp.arange(chunk)
            sc, context = partial(
                qa, window(start, chunk),
                (at[None, None, :] <= q_end[:, :, None])
                & (at >= c * chunk)[None, None, :])
            new_top = jnp.maximum(top, jnp.max(sc, axis=-1))
            keep = jnp.exp(top - new_top)
            p = jnp.exp(sc - new_top[..., None])
            return (new_top, den * keep + jnp.sum(p, axis=-1),
                    acc * per_ctx(keep) + context(p))

        def walk_items(init):
            group = walk_group(B, 2 * H * qa.shape[3])
            slot_of, chunk_of, valid, n_trips = walk_plan(
                pos, S, table_rows, chunk, group)
            # What a trip needs of its items and no carry enters is
            # made for the whole list before the loop and cut a trip:
            # it depends on ``pos`` and the tables alone, so a
            # program's layers share one copy.
            first = np.minimum(np.arange(n_chunks) * chunk, last)
            start_of = jnp.asarray(first, jnp.int32)[chunk_of]
            at = start_of[:, None] + jnp.arange(chunk)[None, :]
            sees = ((at[:, None, :] <= q_end[slot_of][:, :, None])
                    & ((at >= (chunk_of * chunk)[:, None])
                       & valid[:, None])[:, None, :])      # [N, S, K]
            whose = ((slot_of[None, :] == jnp.arange(B)[:, None])
                     & valid[None, :])[..., None, None]  # [B, N, 1, 1]
            if tables is None:
                # an item's rows are one slice of its slot's buffer
                where = jnp.stack([slot_of, start_of], axis=1)

                def items(slices):
                    return lambda src: jax.vmap(
                        lambda w: jax.lax.dynamic_slice(
                            src, (w[0], w[1], 0, 0),
                            (1, chunk) + src.shape[2:])[0])(slices)
            else:
                # the block columns of every (slot, chunk) window, a
                # row each, and each item's row of them
                windows = tables[:, first[:, None] // bs
                                 + np.arange(chunk // bs)[None, :]]
                where = windows.reshape(B * n_chunks, chunk // bs)[
                    slot_of * n_chunks + chunk_of]       # [N, K // bs]

                def items(cols):
                    return lambda src: _gather_blocks(src, cols)

            def item_trip(t, carry):
                top, den, acc = carry

                def cut(a, axis=0):
                    return jax.lax.dynamic_slice_in_dim(
                        a, t * group, group, axis)
                sc, context = partial(qa[cut(slot_of)],
                                      items(cut(where)), cut(sees))
                # the items' own partials ...
                m = jnp.max(sc, axis=-1)                  # [G, H, S]
                p = jnp.exp(sc - m[..., None])
                # ... folded into their slots' running state: item g
                # weighs exp(m_g - new_top_b) in its slot b, 0 elsewhere
                # (and exactly 0 where it saw no row: m_g = -1e30)
                mine = cut(whose, 1)
                new_top = jnp.maximum(top, jnp.max(
                    jnp.where(mine, m[None], -1e30), axis=1))
                keep = jnp.exp(top - new_top)
                w = jnp.where(mine, jnp.exp(jnp.minimum(
                    m[None] - new_top[:, None], 0.0)), 0.0)  # [B,G,H,S]
                return (new_top,
                        den * keep + jnp.einsum(
                            "bghs,ghs->bhs", w, jnp.sum(p, axis=-1)),
                        acc * per_ctx(keep) + jnp.einsum(
                            "bghs,gshd->bshd", w, context(p),
                            precision=jax.lax.Precision.HIGHEST))

            _, den, acc = jax.lax.fori_loop(0, n_trips, item_trip, init)
            # a slot without an item: 0 / 1
            return jnp.where(den > 0, den, 1.0), acc

        if n_chunks == 1:
            sc, context = partial(
                qa, window(0, table_rows),
                jnp.arange(table_rows)[None, None, :]
                <= q_end[:, :, None])
            ctx = context(jax.nn.softmax(sc, axis=-1))
        else:
            init = (jnp.full((B, H, S), -1e30, jnp.float32),
                    jnp.zeros((B, H, S), jnp.float32),
                    jnp.zeros((B, S, H, qa.shape[3]), jnp.float32))
            if B > 1:
                den, acc = walk_items(init)
            else:
                live = jnp.clip((jnp.max(pos) + S + chunk - 1) // chunk,
                                1, n_chunks)
                _, den, acc = jax.lax.fori_loop(0, live, trip, init)
            ctx = acc / per_ctx(den)
        return self._project(ctx.astype(qa.dtype))

    def _project(self, ctx):
        """Output projection of the context [B, S, H, hd]: Tensor
        [B, S, E]."""
        out = Tensor(ctx)
        if self.use_mp:
            from ..ops import einsum
            return einsum("bshd,hde->bse", out, self.out_weight) + \
                self.out_bias
        out = reshape(out, [ctx.shape[0], ctx.shape[1],
                            self.num_heads * self.head_dim])
        return self._lora_out(out)

    @staticmethod
    def _stream_attn(qa, k_pool, v_pool, tables, pos):
        """``_slot_attn``'s core as the Pallas kernel: the same online
        softmax over each slot's own rows (a slot at position 0 is
        parked: no step, a context of zeros), the pools read in place
        as ``[NB * bs, H, hd]`` rows."""
        import jax.numpy as jnp
        from ..ops.ragged_paged_attn import ragged_paged_attention

        NB, bs, H, hd = k_pool.shape
        width = jnp.where(pos > 0, qa.shape[1], 0).astype(jnp.int32)
        return ragged_paged_attention(
            qa, k_pool.reshape(NB * bs, H, hd),
            v_pool.reshape(NB * bs, H, hd), tables, pos, width,
            block_size=bs)

    @_scoped("attention")
    def decode_slots(self, x, k_buf, v_buf, pos):
        """One-token decode with PER-SLOT positions (continuous
        batching, serving/engine.py): each batch row is an independent
        request slot at its own sequence position, so the cache write
        and the causal mask are per-row.  Same f32 score math as
        ``decode`` (via ``_slot_attn``) — row b of a slot batch
        computes exactly what a B=1 ``decode`` at ``pos[b]`` computes,
        which is what makes the serving engine token-identical to
        per-request ``generate()``.

        x: Tensor [B, 1, E]; k_buf/v_buf: [B, L, H, hd] arrays;
        pos: int32 [B] (per-slot write position).  Returns
        (out Tensor [B, 1, E], k_buf, v_buf).
        """
        import jax.numpy as jnp

        if x.shape[1] != 1:
            raise ValueError(
                f"decode_slots is a one-token step (got S={x.shape[1]});"
                " windowed decode keeps the shared-position decode()")
        qa, ka, va = self._qkv_step(x)
        rows = jnp.arange(qa.shape[0])
        k_buf = k_buf.at[rows, pos].set(ka[:, 0].astype(k_buf.dtype))
        v_buf = v_buf.at[rows, pos].set(va[:, 0].astype(v_buf.dtype))
        out = self._slot_attn(qa, k_buf, v_buf, None, pos)
        return out, k_buf, v_buf

    @_scoped("attention")
    def decode_slots_paged(self, x, k_pool, v_pool, block_tables, pos):
        """One-token decode reading K/V through per-slot BLOCK TABLES
        (paged KV cache — serving/kvcache.py): the physical pools hold
        fixed-size blocks shared across slots (prefix reuse, COW
        refcounts), and each slot's logical [L] cache row is the gather
        of its table's blocks.  The write scatters into the block
        holding ``pos[b]``; the table's blocks are then fetched a chunk
        at a time by the SAME ``_slot_attn`` as the contiguous path,
        so slot outputs are token-identical to ``decode_slots`` (and
        hence ``generate()``).

        x: Tensor [B, 1, E]; k_pool/v_pool: [NB, bs, H, hd] arrays —
        or ``QuantKV`` int8 pools (serving/quant.py), in which case
        the write goes through the touched-block requantizing insert
        and the fetch dequantizes ONLY the live chunks' blocks;
        block_tables: int32 [B, L//bs] (physical block per logical
        block); pos: int32 [B].  Returns (out [B, 1, E], k_pool,
        v_pool).
        """
        import jax.numpy as jnp

        if x.shape[1] != 1:
            raise ValueError(
                f"decode_slots_paged is a one-token step "
                f"(got S={x.shape[1]})")
        qa, ka, va = self._qkv_step(x)
        B = qa.shape[0]
        NB, bs = k_pool.shape[0], k_pool.shape[1]
        rows = jnp.arange(B)
        if _is_quant_kv(k_pool):
            from ..serving.quant import paged_insert
            blk = block_tables[rows, pos // bs]
            off = pos % bs
            k_pool = paged_insert(k_pool, blk, off, ka[:, 0])
            v_pool = paged_insert(v_pool, blk, off, va[:, 0])
        else:
            flat_k = k_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            flat_v = v_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            # physical row of logical position pos[b] in slot b's table
            widx = block_tables[rows, pos // bs] * bs + pos % bs  # [B]
            k_pool = flat_k.at[widx].set(
                ka[:, 0].astype(flat_k.dtype)).reshape(k_pool.shape)
            v_pool = flat_v.at[widx].set(
                va[:, 0].astype(flat_v.dtype)).reshape(v_pool.shape)
        out = self._slot_attn(qa, k_pool, v_pool, block_tables, pos)
        return out, k_pool, v_pool

    @_scoped("attention")
    def verify_slots(self, x, k_buf, v_buf, pos):
        """SPECULATIVE VERIFY window with per-slot positions
        (serving/spec.py): score W = k+1 window tokens per slot in one
        pass — token 0 is the slot's current (last emitted) token,
        tokens 1..k are draft proposals.  Each window token's K/V is
        written at ``pos[b] + offset`` and the queries attend causally
        through the SAME ``_slot_attn`` as the one-token decode, so
        window offset q of slot b computes exactly what a ``decode_slots``
        step at ``pos[b] + q`` would compute given the same prefix —
        the structural basis of the engine's greedy-parity guarantee.
        Rejected lanes leave garbage K/V past the accepted prefix; the
        engine only advances its write cursor over accepted lanes, and
        the next window re-writes every garbage row before any query
        can see it (cursor rewind, never a buffer operation).

        x: Tensor [B, W, E]; k_buf/v_buf: [B, L, H, hd] arrays;
        pos: int32 [B].  Returns (out Tensor [B, W, E], k_buf, v_buf).
        """
        import jax.numpy as jnp

        qa, ka, va = self._qkv_step(x)
        B, W = qa.shape[0], qa.shape[1]
        rows = jnp.arange(B)[:, None]                       # [B, 1]
        cols = pos[:, None] + jnp.arange(W)[None, :]        # [B, W]
        k_buf = k_buf.at[rows, cols].set(ka.astype(k_buf.dtype))
        v_buf = v_buf.at[rows, cols].set(va.astype(v_buf.dtype))
        out = self._slot_attn(qa, k_buf, v_buf, None, pos)
        return out, k_buf, v_buf

    @_scoped("attention")
    def verify_slots_paged(self, x, k_pool, v_pool, block_tables, pos):
        """Block-table twin of ``verify_slots`` (paged KV cache): the
        W window tokens scatter through each slot's block table and
        the table's blocks go through the SAME ``_slot_attn`` as
        ``decode_slots_paged``.  The engine's admission gate
        reserves the speculative margin up front (``_kv_gate`` adds
        ``spec_k`` to the worst case), so every window position —
        rejected lanes included — lands inside the slot's own reserved
        tail blocks: rollback is a cursor reset, never a pool
        operation.  Parked slots (all-zero tables) write through the
        scratch block as usual.

        x: Tensor [B, W, E]; k_pool/v_pool: [NB, bs, H, hd];
        block_tables: int32 [B, L//bs]; pos: int32 [B].  Returns
        (out Tensor [B, W, E], k_pool, v_pool).
        """
        import jax.numpy as jnp

        qa, ka, va = self._qkv_step(x)
        B, W = qa.shape[0], qa.shape[1]
        NB, bs = k_pool.shape[0], k_pool.shape[1]
        rows = jnp.arange(B)
        offs = pos[:, None] + jnp.arange(W)[None, :]        # [B, W]
        if _is_quant_kv(k_pool):
            from ..serving.quant import paged_insert
            blk = block_tables[rows[:, None], offs // bs].reshape(-1)
            off = (offs % bs).reshape(-1)
            H, hd = self.num_heads, self.head_dim
            k_pool = paged_insert(k_pool, blk, off,
                                  ka.reshape(B * W, H, hd))
            v_pool = paged_insert(v_pool, blk, off,
                                  va.reshape(B * W, H, hd))
        else:
            flat_k = k_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            flat_v = v_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            widx = (block_tables[rows[:, None], offs // bs] * bs
                    + offs % bs)                            # [B, W]
            k_pool = flat_k.at[widx].set(
                ka.astype(flat_k.dtype)).reshape(k_pool.shape)
            v_pool = flat_v.at[widx].set(
                va.astype(flat_v.dtype)).reshape(v_pool.shape)
        out = self._slot_attn(qa, k_pool, v_pool, block_tables, pos)
        return out, k_pool, v_pool

    @_scoped("attention")
    def ragged_window_paged(self, x, k_pool, v_pool, block_tables, pos,
                            width, scratch=None, sharded=False):
        """RAGGED paged window — the Pallas-kernel twin of the three
        paged window shapes (``decode_slots_paged`` S=1,
        ``verify_slots_paged`` S=k+1, ``prefill_chunk_paged`` S=C):
        per-slot ``pos``/``width``/``block_tables`` are runtime DATA,
        so one compiled program serves a batch mixing one-token decode
        lanes, spec-verify windows, and prefill chunks at once
        (ops/ragged_paged_attn.py).

        The window's K/V scatters through each slot's table with the
        WIDTH MASK applied here, before the kernel: lanes
        ``s >= width[b]`` land in the slot's own SCRATCH block
        (``scratch[b]``; physical row 0 when None — the unsharded
        engine) — which is the one masking rule that used to be
        three per-path invariants (parked slots' zero tables, the
        spec-margin reservation, chunked prefill's ``true_len`` pad
        lanes; see serving/kvcache.py).  Under a dp mesh the scratch
        row is per-slot DATA because each dp shard reserves its own
        scratch block — a masked lane may never write another shard's
        rows.  Valid lanes write exactly what their XLA twin writes.
        The kernel is the flash-style online-softmax block loop —
        O(block_size x W) working set, allclose to ``_slot_attn`` with
        greedy streams token-identical end-to-end (asserted in
        tests/test_ragged_attn.py).
        ``sharded=True`` (a 2-D mp x dp serving mesh) routes the
        kernel through ``sharded_ragged_paged_attention`` — the
        hand-written shard_map partitioning GSPMD cannot derive for
        the Mosaic path.

        x: Tensor [B, W, E]; k_pool/v_pool: [NB, bs, H, hd];
        block_tables: int32 [B, L//bs]; pos/width: int32 [B];
        scratch: optional int32 [B] per-slot scratch block id.
        Returns (out Tensor [B, W, E], k_pool, v_pool).
        """
        import jax.numpy as jnp
        from ..ops.ragged_paged_attn import (
            ragged_paged_attention, sharded_ragged_paged_attention)

        qa, ka, va = self._qkv_step(x)
        B, W = qa.shape[0], qa.shape[1]
        NB, bs = k_pool.shape[0], k_pool.shape[1]
        bps = block_tables.shape[1]
        rows = jnp.arange(B)
        H, hd = self.num_heads, self.head_dim
        if scratch is None:
            scratch = jnp.zeros(B, jnp.int32)
        offs = pos[:, None] + jnp.arange(W)[None, :]        # [B, W]
        # lanes past width[b] — and any out-of-range offset (runaway
        # defense: a clip into the table's LAST entry would overwrite
        # live rows of the slot's own cache) — scatter into the
        # slot's scratch block, the parked-lane semantics of the XLA
        # paths' pos clamps
        valid = (jnp.arange(W)[None, :] < width[:, None]) \
            & (offs < bps * bs)
        offs_safe = jnp.where(valid, offs, 0)
        blk = block_tables[rows[:, None], offs_safe // bs]
        if _is_quant_kv(k_pool):
            from ..serving.quant import paged_insert
            # same masking rule, insert form: masked lanes RMW their
            # slot's scratch block instead of scatter-row
            blk_q = jnp.where(valid, blk,
                              scratch[:, None]).reshape(-1)
            off_q = jnp.where(valid, offs_safe % bs, 0).reshape(-1)
            k_pool = paged_insert(k_pool, blk_q, off_q,
                                  ka.reshape(B * W, H, hd))
            v_pool = paged_insert(v_pool, blk_q, off_q,
                                  va.reshape(B * W, H, hd))
            # the kernel gets code rows + the parallel scale pools and
            # dequantizes per gathered block, inside the kv-block loop
            attn = (sharded_ragged_paged_attention if sharded
                    else ragged_paged_attention)
            ctx = attn(
                qa, k_pool.codes.reshape(NB * bs, H, hd),
                v_pool.codes.reshape(NB * bs, H, hd),
                block_tables, pos, width, block_size=bs,
                k_scale=k_pool.scale, v_scale=v_pool.scale)
            new_k, new_v = k_pool, v_pool
        else:
            flat_k = k_pool.reshape(NB * bs, H, hd)
            flat_v = v_pool.reshape(NB * bs, H, hd)
            widx = jnp.where(valid, blk * bs + offs_safe % bs,
                             scratch[:, None] * bs)
            flat_k = flat_k.at[widx].set(ka.astype(flat_k.dtype))
            flat_v = flat_v.at[widx].set(va.astype(flat_v.dtype))
            attn = (sharded_ragged_paged_attention if sharded
                    else ragged_paged_attention)
            ctx = attn(qa, flat_k, flat_v,
                       block_tables, pos, width,
                       block_size=bs)
            new_k = flat_k.reshape(k_pool.shape)
            new_v = flat_v.reshape(v_pool.shape)
        return self._project(ctx), new_k, new_v

    @_scoped("attention")
    def prefill_chunk_paged(self, x, k_pool, v_pool, block_table, pos,
                            true_len, scratch=0):
        """CHUNKED prefill through ONE slot's block table (budgeted
        chunked prefill — serving/engine.py ``prefill_chunk``): run a
        fixed-size window of C prompt tokens at positions
        ``pos..pos+C-1``, scattering their K/V block-granular through
        the slot's table and attending causally over the slot's whole
        gathered logical row — the adopted prefix blocks and earlier
        chunks' K/V included.  All shapes are static (C, pool, table);
        ``pos``/``true_len`` are traced scalars, so ONE XLA program
        serves every chunk of every prompt.  Pad lanes (>= true_len)
        scatter into the slot's SCRATCH block (``scratch``, a traced
        scalar block id — its dp shard's reserved row; physical row 0
        on an unsharded engine), whose content no live request ever
        reads.

        x: Tensor [1, C, E]; k_pool/v_pool: [NB, bs, H, hd] arrays;
        block_table: int32 [L//bs] (ONE slot's row); pos/true_len/
        scratch: traced int scalars.  Returns (out Tensor [1, C, E],
        k_pool, v_pool).
        """
        import math as _math
        import jax
        import jax.numpy as jnp

        C = x.shape[1]
        if self.use_mp:
            q, k, v = self._qkv_mp(x)
        else:
            qkv = self.qkv_proj(x)
            qkv = reshape(qkv, [1, C, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        qa, ka, va = q._data, k._data, v._data
        NB, bs = k_pool.shape[0], k_pool.shape[1]
        offs = pos + jnp.arange(C)                              # [C]
        valid = jnp.arange(C) < true_len
        offs_safe = jnp.where(valid, offs, 0)
        if _is_quant_kv(k_pool):
            from ..serving.quant import paged_gather, paged_insert
            # pad lanes RMW the slot's scratch block — the same
            # masking rule as the fp scatter's scratch widx
            blk = jnp.where(valid, block_table[offs_safe // bs],
                            scratch)
            off = jnp.where(valid, offs_safe % bs, 0)
            k_pool = paged_insert(k_pool, blk, off, ka[0])
            v_pool = paged_insert(v_pool, blk, off, va[0])
            k_rows = paged_gather(k_pool, block_table[None, :])
            v_rows = paged_gather(v_pool, block_table[None, :])
            new_k, new_v = k_pool, v_pool
            L = block_table.shape[0] * bs
        else:
            flat_k = k_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            flat_v = v_pool.reshape(NB * bs, self.num_heads,
                                    self.head_dim)
            # pad lanes write the slot's scratch block (garbage on
            # garbage)
            widx = jnp.where(
                valid,
                block_table[offs_safe // bs] * bs + offs_safe % bs,
                scratch * bs)
            flat_k = flat_k.at[widx].set(ka[0].astype(flat_k.dtype))
            flat_v = flat_v.at[widx].set(va[0].astype(flat_v.dtype))
            # gather the slot's whole logical [L] row (like
            # decode_slots_paged, one slot): chunk queries see the
            # adopted prefix, earlier chunks, and this chunk's own
            # fresh K/V
            gidx = ((block_table * bs)[:, None]
                    + jnp.arange(bs)[None, :]).reshape(-1)      # [L]
            k_rows = flat_k[gidx][None]
            v_rows = flat_v[gidx][None]
            new_k = flat_k.reshape(k_pool.shape)
            new_v = flat_v.reshape(v_pool.shape)
            L = gidx.shape[0]
        scale = 1.0 / _math.sqrt(self.head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk",
                            qa.astype(jnp.float32),
                            k_rows.astype(jnp.float32)) * scale
        visible = jnp.arange(L)[None, :] <= offs[:, None]       # [C, L]
        scores = jnp.where(visible[None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         v_rows.astype(jnp.float32)).astype(qa.dtype)
        return self._project(ctx), new_k, new_v

    @_scoped("attention")
    def forward(self, x, cache=None, doc_segments=None):
        b, s, _ = x.shape
        if doc_segments is not None and self.use_sp and cache is None:
            raise NotImplementedError(
                "packed-sequence attention is not supported under "
                "sequence parallelism (the ring/all-to-all kernels "
                "build their own causal masks)")
        if self.use_mp:
            q, k, v = self._qkv_mp(x)
        else:
            qkv = self.qkv_proj(x)
            qkv = reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cache is not None:
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            cache = (k, v)
        if self.use_sp and cache is None:
            # sequence/context parallelism over the 'sp' mesh axis — seq
            # stays sharded end-to-end.  use_sp=True/'ring': K/V blocks
            # rotate on the ICI ring (differentiable: the ring is a
            # lax.scan).  use_sp='ulysses': all-to-all swaps seq<->head
            # sharding (lower comm volume when heads % sp == 0).  NEW
            # capability vs the reference (§5.7).
            from ..core import rng as _rng
            dp = self.dropout if (self.training and self.dropout) else 0.0
            rk = _rng.op_key(q) if dp else None
            try:
                from ..static import program as _sprog
                if isinstance(rk, _sprog.Variable):
                    rk, dp = None, 0.0  # static-graph symbolic key
            except ImportError:
                pass
            if self.use_sp == "ulysses":
                # probs-dropout applies in the local attention after the
                # all-to-all, per-device keys folded over mesh coords
                from ..distributed.ring import ulysses_attention
                out = ulysses_attention(q, k, v, axis="sp", causal=True,
                                        dropout_p=dp, rng_key=rk)
            else:
                from ..distributed.ring import ring_attention
                out = ring_attention(q, k, v, axis="sp", causal=True,
                                     dropout_p=dp, rng_key=rk)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, segment_ids=doc_segments, is_causal=True,
                dropout_p=self.dropout, training=self.training)
        if self.use_mp:
            from ..ops import einsum
            # contraction over (H, hd): XLA turns the 'mp'-sharded H
            # contraction into a psum — the row-parallel allreduce
            out = einsum("bshd,hde->bse", out, self.out_weight) + \
                self.out_bias
        else:
            out = reshape(out, [b, s, self.num_heads * self.head_dim])
            out = self._lora_out(out)
        if cache is not None:
            return out, cache
        return out


class GPTMLP(nn.Layer):
    def __init__(self, hidden_size, ffn_hidden=None, dropout=0.1,
                 use_mp=False):
        super().__init__()
        ffn_hidden = ffn_hidden or 4 * hidden_size
        init = nn.ParamAttr(initializer=I.Normal(0.0, 0.02))
        if use_mp:
            from ..distributed.sharding import (ColumnParallelLinear,
                                                RowParallelLinear)
            self.fc1 = ColumnParallelLinear(hidden_size, ffn_hidden,
                                            weight_attr=init,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(ffn_hidden, hidden_size,
                                         weight_attr=init,
                                         input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(hidden_size, ffn_hidden, weight_attr=init)
            self.fc2 = nn.Linear(ffn_hidden, hidden_size, weight_attr=init)
        self.dropout = nn.Dropout(dropout)

    @_scoped("mlp")
    def forward(self, x):
        return self.dropout(self.fc2(F.gelu(self.fc1(x),
                                            approximate=True)))


class GPTBlock(nn.Layer):
    """Pre-LN transformer block — the pipelined unit for GPTPipe."""

    def __init__(self, hidden_size, num_heads, dropout=0.1, use_mp=False,
                 use_recompute=False, moe_experts=0,
                 recompute_policy=None, use_sp=False):
        super().__init__()
        self.ln1 = nn.LayerNorm(hidden_size)
        self.attn = GPTAttention(hidden_size, num_heads, dropout, use_mp,
                                 use_sp=use_sp)
        self.ln2 = nn.LayerNorm(hidden_size)
        if moe_experts:
            from ..distributed.moe import MoELayer
            self.mlp = MoELayer(hidden_size, num_experts=moe_experts)
        else:
            self.mlp = GPTMLP(hidden_size, dropout=dropout, use_mp=use_mp)
        self.use_recompute = use_recompute
        self.recompute_policy = recompute_policy

    def _inner(self, x, doc_segments=None):
        x = x + self.attn(self.ln1(x), doc_segments=doc_segments)
        x = x + self.mlp(self.ln2(x))
        return x

    def decode(self, x, k_buf, v_buf, pos):
        """Fixed-buffer one-token decode (see GPTAttention.decode)."""
        attn_out, k_buf, v_buf = self.attn.decode(self.ln1(x), k_buf,
                                                  v_buf, pos)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_buf, v_buf

    def decode_slots(self, x, k_buf, v_buf, pos):
        """Per-slot-position one-token decode (GPTAttention.decode_slots)."""
        attn_out, k_buf, v_buf = self.attn.decode_slots(self.ln1(x),
                                                        k_buf, v_buf, pos)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_buf, v_buf

    def decode_slots_paged(self, x, k_pool, v_pool, block_tables, pos):
        """Block-table one-token decode (GPTAttention.decode_slots_paged)."""
        attn_out, k_pool, v_pool = self.attn.decode_slots_paged(
            self.ln1(x), k_pool, v_pool, block_tables, pos)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_pool, v_pool

    def verify_slots(self, x, k_buf, v_buf, pos):
        """Speculative verify window (GPTAttention.verify_slots)."""
        attn_out, k_buf, v_buf = self.attn.verify_slots(self.ln1(x),
                                                        k_buf, v_buf, pos)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_buf, v_buf

    def verify_slots_paged(self, x, k_pool, v_pool, block_tables, pos):
        """Block-table speculative verify (GPTAttention.verify_slots_paged)."""
        attn_out, k_pool, v_pool = self.attn.verify_slots_paged(
            self.ln1(x), k_pool, v_pool, block_tables, pos)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_pool, v_pool

    def ragged_window_paged(self, x, k_pool, v_pool, block_tables, pos,
                            width, scratch=None, sharded=False):
        """Ragged Pallas window (GPTAttention.ragged_window_paged)."""
        attn_out, k_pool, v_pool = self.attn.ragged_window_paged(
            self.ln1(x), k_pool, v_pool, block_tables, pos, width,
            scratch=scratch, sharded=sharded)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_pool, v_pool

    def prefill_chunk_paged(self, x, k_pool, v_pool, block_table, pos,
                            true_len, scratch=0):
        """Block-table chunked prefill (GPTAttention.prefill_chunk_paged)."""
        attn_out, k_pool, v_pool = self.attn.prefill_chunk_paged(
            self.ln1(x), k_pool, v_pool, block_table, pos, true_len,
            scratch=scratch)
        x = x + attn_out
        x = x + self.mlp(self.ln2(x))
        return x, k_pool, v_pool

    def forward(self, x, cache=None, doc_segments=None):
        if cache is not None:
            attn_out, cache = self.attn(self.ln1(x), cache=cache)
            x = x + attn_out
            x = x + self.mlp(self.ln2(x))
            return x, cache
        if self.use_recompute:
            from ..distributed.fleet.utils import recompute
            # bound method → recompute collects params from `self`
            return recompute(self._inner, x, doc_segments,
                             policy=self.recompute_policy)
        return self._inner(x, doc_segments)


class GPTScanBlocks(ScanLayers):
    """All transformer blocks as ONE ``lax.scan`` over stacked params
    (see ``nn.ScanLayers`` for the general mechanism and contracts).

    Init is bit-identical to the unrolled ``LayerList`` under the same
    seed, training parity is exact (``tests/test_gpt_scan.py``), and
    the 1.3B full-step XLA compile drops 212-460s -> 18.6s on the CPU
    rehearsal (round 3, record deleted at bring-up).  Scope: the dense AND packed
    (doc_segments flash-masked) training/forward paths; KV-cache
    decode serves through ``GPTModel._sync_decode_twin`` (round 5).
    Tensor/sequence parallel and MoE variants stay on the unrolled
    form (their blocks are not homogeneous scan bodies)."""

    def __init__(self, num_layers, hidden_size, num_heads, dropout=0.1,
                 use_recompute=False, recompute_policy=None):
        super().__init__(
            lambda: GPTBlock(hidden_size, num_heads, dropout),
            num_layers, use_recompute=use_recompute,
            recompute_policy=recompute_policy)


class GPTLMHead(nn.Layer):
    def __init__(self, hidden_size, vocab_size, use_mp=False):
        super().__init__()
        self.use_mp = use_mp
        self.ln_f = nn.LayerNorm(hidden_size)
        init = nn.ParamAttr(initializer=I.Normal(0.0, 0.02))
        if use_mp:
            from ..distributed.sharding import ColumnParallelLinear
            self.lm_head = ColumnParallelLinear(
                hidden_size, vocab_size, weight_attr=init, has_bias=False,
                gather_output=True)
        else:
            self.lm_head = nn.Linear(hidden_size, vocab_size,
                                     weight_attr=init, bias_attr=False)

    @_scoped("lm_head")
    def forward(self, x):
        return self.lm_head(self.ln_f(x))


class GPTModel(ServedModel, nn.Layer):
    """Decoder-only LM returning logits [B, S, V]."""

    def __init__(self, num_layers=12, hidden_size=768, num_heads=12,
                 vocab_size=50304, max_position=1024, dropout=0.1,
                 use_mp=False, use_recompute=False, moe_experts=0,
                 moe_every=2, fused_loss=False, recompute_policy=None,
                 use_sp=False, fused_loss_chunk=128, scan_layers=False,
                 attn_impl="xla"):
        super().__init__()
        if attn_impl not in ("xla", "ragged"):
            raise ValueError(
                f"attn_impl must be 'xla' or 'ragged', "
                f"got {attn_impl!r}")
        # serving-kernel selection default: 'xla' keeps the paged
        # gather/scatter dispatches (the CPU tier-1 parity oracle);
        # 'ragged' routes the paged decode / spec-verify / chunked-
        # prefill attention core through the Pallas ragged paged
        # attention kernel (ops/ragged_paged_attn.py) — per-slot
        # window widths as data, ONE compiled program for every paged
        # window shape — in its flash-style online-softmax STREAMING
        # form (O(block_size x window) working set, long-context
        # first-class).  Engine(attn_impl=...) overrides per engine.
        self.attn_impl = attn_impl
        # decode-twin reconstruction needs the dense hyperparams
        # (scan_layers forbids mp/sp/moe, so these suffice)
        self._init_config = dict(
            num_layers=num_layers, hidden_size=hidden_size,
            num_heads=num_heads, vocab_size=vocab_size,
            max_position=max_position, dropout=dropout,
            fused_loss=fused_loss, fused_loss_chunk=fused_loss_chunk,
            attn_impl=attn_impl)
        self.fused_loss = fused_loss
        # sequence-chunk size of the fused head+CE scan: larger chunks =
        # fewer scan iterations and bigger matmuls, more live logits HBM
        self.fused_loss_chunk = fused_loss_chunk
        self.embeddings = GPTEmbeddings(vocab_size, hidden_size,
                                        max_position, dropout, use_mp)
        # moe_experts>0: every `moe_every`-th block (1-based) swaps its FFN
        # for an expert-parallel MoE layer; moe_every=1 -> every block
        if moe_experts and moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {moe_every}")
        self.scan_layers = scan_layers
        if scan_layers:
            # one compiled block body instead of num_layers copies (see
            # GPTScanBlocks); heterogeneous/parallel block variants keep
            # the unrolled form
            if use_mp or use_sp or moe_experts:
                raise ValueError(
                    "scan_layers supports the dense block only — "
                    "tensor/sequence-parallel and MoE variants use the "
                    "unrolled form (their blocks are not homogeneous "
                    "scan bodies)")
            self.blocks = GPTScanBlocks(
                num_layers, hidden_size, num_heads, dropout,
                use_recompute=use_recompute,
                recompute_policy=recompute_policy)
        else:
            self.blocks = nn.LayerList([
                GPTBlock(hidden_size, num_heads, dropout, use_mp,
                         use_recompute,
                         moe_experts=(moe_experts
                                      if moe_experts
                                      and (i + 1) % moe_every == 0
                                      else 0),
                         recompute_policy=recompute_policy,
                         use_sp=use_sp)
                for i in range(num_layers)])
            for i, blk in enumerate(self.blocks):
                # each attention gathers ITS layer's LoRA factors
                blk.attn._layer_idx = i
        self.head = GPTLMHead(hidden_size, vocab_size, use_mp)

    def forward(self, input_ids, labels=None, caches=None,
                position_offset=0, doc_lens=None):
        doc_segments = position_ids = None
        if doc_lens is not None:
            if caches is not None:
                raise ValueError(
                    "doc_lens (packed sequences) cannot combine with "
                    "KV-cache decoding")
            position_ids, doc_segments, label_keep = packed_doc_inputs(
                doc_lens, input_ids.shape[-1])
            if labels is not None:
                # a document's last token must not be scored against the
                # NEXT document's first token; positions past the packed
                # total are padding — both become ignore_index
                import jax.numpy as _jnp
                from ..core.dispatch import ensure_tensor as _et
                from ..ops import where as _where
                labels = _et(labels)
                labels = _where(label_keep, labels,
                                Tensor(_jnp.full((), -100,
                                                 labels._data.dtype)))
        x = self.embeddings(input_ids, position_offset=position_offset,
                            position_ids=position_ids)
        if self.scan_layers:
            if caches is not None:
                raise NotImplementedError(
                    "scan_layers covers the training/forward path; "
                    "for KV-cache decode call generate(), which serves "
                    "through an auto-synced unrolled twin "
                    "(_sync_decode_twin)")
            # packed mode rides along: doc_segments is a scan-invariant
            # extra broadcast to every layer (the cache slot stays None,
            # and ScanLayers drops None extras while keeping positions)
            x = self.blocks(x, None, doc_segments)
        else:
            if caches is not None:
                new_caches = []
                for blk, cache in zip(self.blocks, caches):
                    x, cache = blk(x, cache=cache)
                    new_caches.append(cache)
                return self.head(x), new_caches
            for blk in self.blocks:
                x = blk(x, doc_segments=doc_segments)
        if labels is not None and self.fused_loss \
                and not self.head.use_mp:
            # head + CE fused per sequence chunk: the [B, S, vocab] logits
            # never hit HBM (see F.fused_linear_cross_entropy).  Packed
            # mode masks boundary/padding labels via ignore_index — the
            # materializing CE fallback OOMs at long budgets (39.7GB at
            # budget 4096 vs 15.75GB HBM)
            h = self.head.ln_f(x)
            # ignore_index always on: the unfused fallback CE below
            # defaults to -100, and -100-padded labels without doc_lens
            # would otherwise NaN through take_along_axis fill semantics
            return F.fused_linear_cross_entropy(
                h, self.head.lm_head.weight, labels,
                chunk_size=self.fused_loss_chunk,
                ignore_index=-100)
        logits = self.head(x)
        if labels is not None:
            b, s, v = logits.shape
            return F.cross_entropy(reshape(logits, [b * s, v]),
                                   reshape(labels, [b * s]))
        return logits

    @staticmethod
    def _filter_logits(last, temperature, top_k, top_p):
        """Sampling filters (temperature / top-k / top-p nucleus) on f32
        logits [B, V].  Pure jnp — shared verbatim by the eager per-token
        loop and the fused on-device scan so both paths draw from the
        identical filtered distribution."""
        import jax
        import jax.numpy as jnp
        if temperature != 1.0:
            last = last / temperature
        if top_k and top_k > 0:
            kth = jax.lax.top_k(last, top_k)[0][:, -1:]
            last = jnp.where(last < kth, -1e9, last)
        if top_p < 1.0:
            # clamp so top_p <= 0 means "top token only" (the keep-mask
            # below would otherwise mask EVERYTHING and sample uniformly)
            p_eff = max(float(top_p), 1e-9)
            # nucleus filtering: mask tokens outside the smallest set
            # whose cumulative probability reaches top_p (sorted
            # descending; the top token always survives)
            srt = jnp.sort(last, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep entries whose PREFIX (exclusive) mass is still < top_p
            keep = (cum - probs) < p_eff
            cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                             keepdims=True)
            last = jnp.where(last < cutoff, -1e9, last)
        return last

    # the per-slot sampling of the fused dispatches is every served
    # model's (models/programs.py)
    _filter_logits_lanes = staticmethod(filter_logits_lanes)
    _slot_sample_keys = staticmethod(slot_sample_keys)
    _sample_lanes = staticmethod(sample_lanes)

    def _decode_tick(self, tok, k_bufs, v_bufs, pos):
        """One-token decode against fixed-size cache buffers: embeddings
        -> each block's decode -> head.  Shared by the per-token jitted
        step and the fused whole-decode scan so the two compiled paths
        cannot diverge.  Returns (last_logits [B, V], new_k, new_v)."""
        logits, new_k, new_v = self._decode_window(tok, k_bufs, v_bufs,
                                                   pos)
        return logits[:, -1, :], new_k, new_v

    def _decode_window(self, toks, k_bufs, v_bufs, pos):
        """Windowed decode: run S tokens at positions pos..pos+S-1
        against the fixed cache buffers in ONE forward, returning the
        FULL logits [B, S, V] (the speculative verify needs every
        position; ``_decode_tick`` is the S-agnostic single source both
        compiled paths and the fused scan build on)."""
        x = self.embeddings(Tensor(toks), position_offset=pos)
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.decode(x, k_bufs[j], v_bufs[j], pos)
            new_k.append(kb)
            new_v.append(vb)
        return self.head(x)._data, new_k, new_v

    def _decode_tick_slots(self, tok, k_bufs, v_bufs, pos):
        """One-token decode over a SLOT POOL: like ``_decode_tick`` but
        ``pos`` is int32 [B] — every batch row is an independent request
        at its own position (continuous batching; serving/engine.py).
        Returns (last_logits [B, V], new_k, new_v)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        x = self.embeddings(Tensor(tok), position_ids=Tensor(pos[:, None]))
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.decode_slots(x, k_bufs[j], v_bufs[j], pos)
            new_k.append(kb)
            new_v.append(vb)
        return self.head(x)._data[:, -1, :], new_k, new_v

    def _decode_tick_slots_paged(self, tok, k_pools, v_pools,
                                 block_tables, pos):
        """One-token decode over a PAGED slot pool: like
        ``_decode_tick_slots`` but K/V live in shared fixed-size blocks
        and each slot reads/writes through its block table
        (serving/kvcache.py).  Returns (last_logits [B, V], new_k,
        new_v)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        x = self.embeddings(Tensor(tok), position_ids=Tensor(pos[:, None]))
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.decode_slots_paged(x, k_pools[j], v_pools[j],
                                               block_tables, pos)
            new_k.append(kb)
            new_v.append(vb)
        return self.head(x)._data[:, -1, :], new_k, new_v

    def _spec_verify_tick_slots(self, toks, k_bufs, v_bufs, pos):
        """SPECULATIVE VERIFY over a slot pool: run the W = k+1 window
        tokens of every slot (current token + k drafts) in ONE forward
        at per-slot positions ``pos[b]..pos[b]+W-1``, returning the
        FULL logits — the engine accepts the longest prefix where the
        target's argmax equals the draft, plus the one bonus token.
        Like ``_decode_tick_slots`` but windowed (``verify_slots``).
        Returns (logits [B, W, V], new_k, new_v)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        W = toks.shape[1]
        pids = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        x = self.embeddings(Tensor(toks), position_ids=Tensor(pids))
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.verify_slots(x, k_bufs[j], v_bufs[j], pos)
            new_k.append(kb)
            new_v.append(vb)
        return self.head(x)._data, new_k, new_v

    def _spec_verify_tick_slots_paged(self, toks, k_pools, v_pools,
                                      block_tables, pos):
        """Paged twin of ``_spec_verify_tick_slots``: the window's K/V
        scatters through per-slot block tables (``verify_slots_paged``).
        Returns (logits [B, W, V], new_k, new_v)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        W = toks.shape[1]
        pids = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        x = self.embeddings(Tensor(toks), position_ids=Tensor(pids))
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.verify_slots_paged(
                x, k_pools[j], v_pools[j], block_tables, pos)
            new_k.append(kb)
            new_v.append(vb)
        return self.head(x)._data, new_k, new_v

    def _fused_decode_tick_slots(self, tok, k_bufs, v_bufs, pos, temp,
                                 top_k, top_p, seed_lo, seed_hi, ctr,
                                 eos, rem, block_tables=None):
        """FUSED one-token decode + ON-DEVICE sampling over the slot
        pool: run the decode tick, then sample every lane in the same
        dispatch (``_sample_lanes`` with per-slot params and
        seed+counter-derived keys) and advance the device-resident
        step state — so a steady-state engine tick uploads nothing and
        downloads only the [B] sampled ids instead of the [B, V]
        logits matrix.  ``temperature == 0`` lanes are greedy (raw
        argmax of the lane's logits).

        DEVICE-SIDE STOP CONDITION (the async engine loop's safety
        contract): ``eos`` [B] int32 (-1 = none) and ``rem`` [B] int32
        (remaining token budget) are per-slot lanes checked ON DEVICE.
        A lane whose sampled id hits its eos, or whose budget runs
        out, gets ``rem`` zeroed; a lane with ``rem <= 0`` is FROZEN —
        token, position, and rng counter stop advancing, so a tick
        dispatched BEFORE the host has consumed the previous tick's
        ids can never run a finished request past its reserved rows.
        The frozen state is summarized in the returned bit-packed done
        mask ([ceil(B/8)] uint8), so the host learns who finished from
        a few bytes instead of an early sync.  Frozen/parked rows
        still compute (their K/V write parks on the frozen cursor row
        — the slot's own reserved row, or the paged scratch block —
        and is rewritten before any query can see it).
        Returns (ids [B], done [ceil(B/8)] uint8, new_tok [B,1],
        new_pos [B], new_ctr [B], new_rem [B], new_k, new_v)."""
        import jax.numpy as jnp
        if block_tables is None:
            last, new_k, new_v = self._decode_tick_slots(
                tok, k_bufs, v_bufs, pos)
            L = k_bufs[0].shape[1]
        else:
            last, new_k, new_v = self._decode_tick_slots_paged(
                tok, k_bufs, v_bufs, block_tables, pos)
            L = block_tables.shape[1] * k_bufs[0].shape[1]
        keys = self._slot_sample_keys(seed_lo, seed_hi, ctr)
        sampled = self._sample_lanes(last, temp, top_k, top_p, keys)
        live = rem > 0
        ids = jnp.where(live, sampled, tok[:, 0])
        hit_eos = live & (eos >= 0) & (ids == eos)
        new_rem = jnp.where(live, jnp.where(hit_eos, 0, rem - 1), rem)
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        new_pos = jnp.where(live, jnp.minimum(pos + 1, L - 1), pos)
        new_ctr = jnp.where(live, ctr + 1, ctr)
        return (ids, done, ids[:, None], new_pos, new_ctr, new_rem,
                new_k, new_v)

    def _fused_spec_verify_tick_slots(self, toks, k_bufs, v_bufs, pos,
                                      lanes, temp, top_k, top_p,
                                      seed_lo, seed_hi, ctr, eos, rem,
                                      block_tables=None):
        """FUSED speculative verify + ON-DEVICE acceptance: score the
        W = k+1 window positions, pick every lane's token on device
        (lane j's key = fold(request_key, ctr + j), so each emitted
        token's draw matches the one-token tick's draw for the same
        prefix), and count the accepted prefix — the leading run of
        REAL draft lanes (j < lanes[b]) whose draft equals the pick —
        so acceptance no longer needs the [B, W, V] logits pull; the
        tick downloads picks [B, W] + counts + the done mask only.

        DEVICE-SIDE STOP CONDITION: ``eos``/``rem`` lanes clamp the
        emitted window on device — ``n_emit = min(n_acc + 1, rem,
        lanes-through-the-first-eos-pick)`` — exactly the host emit
        loop's stopping rule (mismatch, budget exhausted, or EOS
        emitted), so the device cursor advances by n_emit, a lane
        whose budget hits zero (or that emits its eos) freezes, and a
        blind-dispatched next window can never run a finished request
        past its reserved rows.  TWIN NOTE: the ragged path's
        ``_fused_ragged_tick_slots`` mode-0 branch re-implements this
        accept/eos/rem epilogue with two deliberate divergences
        (lane-width gating via ``width``; pos clamp L-1 vs L-W — see
        its comments); a stop-condition change HERE must be mirrored
        there (the host consume side already shares one loop,
        ``Engine._emit_window_lane``).  Returns (picks [B, W],
        n_acc [B], n_emit [B], done [ceil(B/8)] uint8, new_tok [B,1],
        new_pos [B], new_ctr [B], new_rem [B], new_k, new_v)."""
        import jax.numpy as jnp
        if block_tables is None:
            logits, new_k, new_v = self._spec_verify_tick_slots(
                toks, k_bufs, v_bufs, pos)
            L = k_bufs[0].shape[1]
        else:
            logits, new_k, new_v = self._spec_verify_tick_slots_paged(
                toks, k_bufs, v_bufs, block_tables, pos)
            L = block_tables.shape[1] * k_bufs[0].shape[1]
        B, W = toks.shape
        picks = jnp.stack(
            [self._sample_lanes(
                logits[:, j], temp, top_k, top_p,
                self._slot_sample_keys(seed_lo, seed_hi, ctr + j))
             for j in range(W)], axis=1)                    # [B, W]
        match = (toks[:, 1:] == picks[:, :-1]) & \
            (jnp.arange(W - 1)[None, :] < lanes[:, None])
        # length of the leading matched prefix: first False index in
        # match (the appended sentinel catches the all-matched row)
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((B, 1), bool)], axis=1), axis=1)
        live = rem > 0
        hit_eos = (eos[:, None] >= 0) & (picks == eos[:, None])
        # 1-based lane index of the first eos pick (W + 1 = no stop)
        eos_stop = jnp.where(jnp.any(hit_eos, axis=1),
                             jnp.argmax(hit_eos, axis=1) + 1, W + 1)
        n_emit = jnp.where(
            live, jnp.minimum(jnp.minimum(n_acc + 1, rem), eos_stop),
            0).astype(jnp.int32)
        last_idx = jnp.maximum(n_emit - 1, 0)
        new_tok = jnp.where(
            live[:, None],
            jnp.take_along_axis(picks, last_idx[:, None], axis=1),
            toks[:, :1])
        new_rem = jnp.where(
            live, jnp.where(n_emit == eos_stop, 0, rem - n_emit), rem)
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        new_pos = jnp.where(live, jnp.minimum(pos + n_emit, L - W), pos)
        return (picks, n_acc, n_emit, done, new_tok, new_pos,
                ctr + n_emit, new_rem, new_k, new_v)

    def _ragged_window_tick_slots(self, toks, k_pools, v_pools,
                                  block_tables, pos, width,
                                  scratch=None, sharded=False,
                                  head_lanes=None):
        """RAGGED window forward over the paged slot pool: run each
        slot's ``width[b]`` real window tokens (of the static maximum
        W) at positions ``pos[b]..`` through every block's
        ``ragged_window_paged`` — one-token decode lanes, k+1 verify
        windows, and prefill chunks mixed in ONE dispatch of ONE
        program.  ``head_lanes`` (int32 [B, K], optional) gathers K
        window lanes per slot BEFORE the LM head, so the vocab matmul
        pays for the lanes something actually reads instead of the
        full static window — lanes are per-position independent
        through LayerNorm + head, so gather-then-head equals
        head-then-gather.  Returns (logits [B, W, V] — or [B, K, V]
        with head_lanes — new_k, new_v)."""
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        W = toks.shape[1]
        maxp = self.embeddings.position_embeddings.weight.shape[0]
        # clamp only protects the garbage lanes past width (their
        # embeddings are computed and discarded); real lanes satisfy
        # pos + s < max_position by the engine's admission contract
        pids = jnp.minimum(
            pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :],
            maxp - 1)
        x = self.embeddings(Tensor(toks), position_ids=Tensor(pids))
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.ragged_window_paged(
                x, k_pools[j], v_pools[j], block_tables, pos, width,
                scratch=scratch, sharded=sharded)
            new_k.append(kb)
            new_v.append(vb)
        if head_lanes is not None:
            x = Tensor(jnp.take_along_axis(
                x._data, head_lanes[:, :, None], axis=1))
        return self.head(x)._data, new_k, new_v

    def _fused_ragged_tick_slots(self, toks, k_pools, v_pools,
                                 block_tables, width, mode, lanes, tok,
                                 pos, temp, top_k, top_p, seed_lo,
                                 seed_hi, ctr, eos, rem, scratch=None,
                                 sharded=False, emit_w=None):
        """FUSED ragged window + on-device sample / accept-scan /
        stop-condition epilogue — the ONE program that replaces the
        fused decode, fused spec-verify, AND paged chunk-prefill
        dispatches (``Engine(attn_impl="ragged")``).  Per-slot
        ``mode`` lanes pick the epilogue semantics:

        * mode 0 — decode / spec-verify window: lane 0 is the slot's
          device-resident current token, lanes 1.. the uploaded
          drafts; every lane is sampled with key fold(seed, ctr + j),
          the longest-accepted-prefix scan runs IN the epilogue (the
          satellite fold: acceptance needs no separate dispatch and
          the d2h payload stays picks + counts + done), and the
          eos/rem stop condition clamps/freezes exactly like
          ``_fused_spec_verify_tick_slots`` (the TWIN — a
          stop-condition change in either epilogue must be mirrored;
          see the twin note there) — with zero draft lanes this
          degenerates to the fused one-token decode (n_emit 1).
        * mode 1 — prefill chunk: ``width[b]`` prompt tokens are
          written through the slot's table; nothing samples or
          emits, the cursor advances by the chunk width on device.
        * mode 2 — FINAL prefill chunk: like mode 1, plus the last
          real lane's logits sample the request's next token with the
          UNSHIFTED key fold(seed, ctr) — the same draw a one-token
          tick would make for this prefix — delivered on picks lane 0.

        Width-masked lanes (and whole parked slots, width 0) write the
        scratch block and compute discarded garbage; frozen lanes
        (rem 0) keep tok/pos/ctr unchanged so blind async dispatch
        stays safe.  ``emit_w`` (static) caps the SAMPLED lanes at
        the emit-reachable window — spec_k+1, or 1 without
        speculation: a chunk-widened window (W = chunk > spec_k+1)
        can never emit past lane spec_k, so sampling those lanes
        would burn a full-vocab filter+categorical per tick on picks
        nobody can read, and the cap also shrinks the picks d2h
        payload back to the spec path's.  Dropping high lanes is
        draw-exact: each lane is an independent ``_sample_lanes``
        call, so low lanes' rbg draws are untouched.  Returns
        (picks [B, E] where E = min(W, emit_w or W), n_acc [B],
        n_emit [B], done [ceil(B/8)] uint8, new_tok [B,1], new_pos
        [B], new_ctr [B], new_rem [B], new_k, new_v)."""
        import jax.numpy as jnp
        B, W = toks.shape
        E = min(W, emit_w) if emit_w else W
        # mode-0 lanes take lane 0 from the device-resident token
        # cursor (steady state uploads only the draft/chunk array)
        window = jnp.where(
            (mode == 0)[:, None],
            jnp.concatenate([tok, toks[:, 1:]], axis=1), toks)
        # the LM head pays only for lanes something reads: the E
        # emit-reachable lanes (mode-0 picks) plus each slot's LAST
        # REAL lane (the final-chunk first-token draw) — a
        # chunk-widened window (W = chunk) never runs a [B, W, V]
        # vocab matmul for it
        head_lanes = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(E, dtype=jnp.int32)[None, :],
                              (B, E)),
             jnp.maximum(width - 1, 0)[:, None]], axis=1)   # [B, E+1]
        logits, new_k, new_v = self._ragged_window_tick_slots(
            window, k_pools, v_pools, block_tables, pos, width,
            scratch=scratch, sharded=sharded,
            head_lanes=head_lanes)                     # [B, E+1, V]
        L = block_tables.shape[1] * k_pools[0].shape[1]
        picks = jnp.stack(
            [self._sample_lanes(
                logits[:, j], temp, top_k, top_p,
                self._slot_sample_keys(seed_lo, seed_hi, ctr + j))
             for j in range(E)], axis=1)                    # [B, E]
        # final-chunk pick: the last REAL lane's logits with the
        # unshifted counter key (the stream's next draw, token index
        # ctr — prefill/chunk emission and decode ticks share one
        # per-request key sequence).  Drawn per slot through lax.map
        # — a B=1 body, NOT a vmapped batch: under the repo's rbg
        # default PRNG a vmapped categorical's bits depend on the
        # WHOLE key batch, and the XLA oracle's first-token pick
        # (``sample_rows``) is a B=1 draw — this reproduces the draw
        # MECHANISM bit-for-bit.  The kernel's online softmax
        # reorders float summation, so the seeded guarantee against
        # the XLA arm is determinism (same seed => same stream), with
        # greedy streams token-identical.  Behind a lax.cond: ticks
        # without a final-chunk lane (the steady state) skip the
        # per-slot scan entirely.
        import jax
        last_logits = logits[:, E]  # the gathered last-real lane
        is_final = mode == 2

        def _first_draws(_):
            def one(args):
                row, t, k, p, lo, hi, c = args
                return self._sample_lanes(
                    row[None], t[None], k[None], p[None],
                    self._slot_sample_keys(lo[None], hi[None],
                                           c[None]))[0]
            return jax.lax.map(one, (last_logits, temp, top_k, top_p,
                                     seed_lo, seed_hi, ctr))

        last_pick = jax.lax.cond(
            jnp.any(is_final), _first_draws,
            lambda _: jnp.zeros((B,), jnp.int32), None)
        is_pref = mode == 1
        # a lane is live only when this dispatch actually carries it
        # (width > 0): a PREFILLING slot waiting for budget — or a
        # parked one — is frozen by its zero width, not by a mirror
        # re-upload (the XLA chunk path dirties state every chunk;
        # the ragged path's whole point is that it does not)
        live = (rem > 0) & (width > 0)
        match = (window[:, 1:E] == picks[:, :E - 1]) & \
            (jnp.arange(E - 1)[None, :] < lanes[:, None])
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.zeros((B, 1), bool)], axis=1), axis=1)
        hit_eos = (eos[:, None] >= 0) & (picks == eos[:, None])
        eos_stop = jnp.where(jnp.any(hit_eos, axis=1),
                             jnp.argmax(hit_eos, axis=1) + 1, E + 1)
        n_emit0 = jnp.minimum(jnp.minimum(n_acc + 1, rem), eos_stop)
        fc_eos = (eos >= 0) & (last_pick == eos)
        n_emit = jnp.where(
            is_pref, 0,
            jnp.where(is_final, jnp.minimum(1, rem),
                      jnp.where(live, n_emit0, 0))).astype(jnp.int32)
        last_idx = jnp.maximum(n_emit - 1, 0)
        pick_tok = jnp.take_along_axis(picks, last_idx[:, None],
                                       axis=1)
        new_tok = jnp.where(
            is_final[:, None], last_pick[:, None],
            jnp.where(is_pref[:, None] | ~live[:, None], tok,
                      pick_tok))
        new_rem = jnp.where(
            is_pref, rem,
            jnp.where(is_final, jnp.where(fc_eos, 0, rem - 1),
                      jnp.where(live,
                                jnp.where(n_emit == eos_stop, 0,
                                          rem - n_emit), rem)))
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        adv = jnp.where(is_pref | is_final, width,
                        jnp.where(live, n_emit, 0))
        # L-1, not the spec twin's L-W: a chunk-widened window's
        # legitimate prefill positions can exceed L-W (long prompt),
        # so the stronger clamp would REWIND them; runaway writes are
        # instead parked in the scratch block by the width+range mask
        # in ragged_window_paged
        new_pos = jnp.minimum(pos + adv, L - 1)
        new_ctr = ctr + n_emit
        picks = picks.at[:, 0].set(
            jnp.where(is_final, last_pick, picks[:, 0]))
        return (picks, n_acc, n_emit, done, new_tok, new_pos, new_ctr,
                new_rem, new_k, new_v)

    def _compiled_ragged_window_fn(self, pnames, params, cache_key,
                                   emit_w=None, sharded=False):
        """Build (or fetch) the jitted FUSED RAGGED WINDOW dispatch
        (``Engine(attn_impl="ragged")``): (p_list, b_list, k_pools,
        v_pools, block_tables [B, L//bs], scratch [B], toks [B, W],
        width [B],
        mode [B], lanes [B], tok [B,1], pos [B], temp [B], top_k [B],
        top_p [B], seed_lo [B], seed_hi [B], ctr [B], eos [B],
        rem [B]) -> (picks [B, min(W, emit_w)], n_acc [B], n_emit
        [B], done
        [ceil(B/8)] uint8, new_tok [B,1], new_pos [B], new_ctr [B],
        new_rem [B], k_pools, v_pools).  ``scratch`` is each slot's
        dp shard's scratch block id (all zeros unsharded) and
        ``sharded=True`` (a 2-D mp x dp mesh) runs the kernel under
        shard_map.  The attention core is the
        Pallas ragged paged attention kernel (interpret mode on the
        cpu platform, Mosaic everywhere else),
        and EVERY window shape — one-token decode, k+1 spec verify,
        C-token prefill chunk, mixed in one batch — is per-slot DATA,
        so the (layout, chunk shape, spec_k) compile matrix collapses
        to this ONE program per engine config (compile-probe kind
        ``ragged_window``; asserted by the compile-matrix regression
        test).  Pools donated."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        # emit_w and the sharded lowering are baked into the compiled
        # program (emit_w fixes the picks lane count; sharded picks
        # shard_map vs plain pallas_call), so they MUST distinguish
        # cache entries — enforced here rather than trusted to every
        # caller's key
        cache_key = (cache_key, None if emit_w is None else int(emit_w),
                     bool(sharded))
        cache = getattr(self, "_ragged_window_fn_cache", None)
        if cache is None:
            cache = self._ragged_window_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, k_pools, v_pools, block_tables,
                 scratch, toks, width, mode, lanes, tok, pos, temp,
                 top_k, top_p, seed_lo, seed_hi, ctr, eos, rem,
                 *lora):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad(), _lora_scope(lora):
                    out = model._fused_ragged_tick_slots(
                        toks, k_pools, v_pools, block_tables, width,
                        mode, lanes, tok, pos, temp, top_k, top_p,
                        seed_lo, seed_hi, ctr, eos, rem,
                        scratch=scratch, sharded=sharded,
                        emit_w=emit_w)
            return out

        fn = _jit_named("ragged_window", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching the other caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "ragged_window", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    # -- compile-event hook (serving observability) --------------------
    def _compiled_fused_decode_fn(self, pnames, params, cache_key,
                                  paged=False):
        """Build (or fetch) the jitted FUSED decode+sample tick of the
        serving engine: contiguous layout (p_list,
        b_list, k_pools, v_pools, tok [B,1], pos [B], temp [B],
        top_k [B], top_p [B], seed_lo [B], seed_hi [B], ctr [B],
        eos [B], rem [B]) or paged layout (+ block_tables [B, L//bs]
        before tok) -> (ids [B], done [ceil(B/8)] uint8, new_tok
        [B,1], new_pos [B], new_ctr [B], new_rem [B], k_pools,
        v_pools).  The whole per-tick hot state (current token,
        position, rng counter, remaining budget) is both input and
        output, and the stop condition (EOS / max_new) is checked on
        device against the eos/rem lanes, so the engine
        keeps the returned device handles and a steady-state tick
        performs ZERO uploads and ONE [B]-int download — the host
        round-trip that used to bound decode is gone.  ONE XLA program
        per layout (every sampling param is a traced lane).  Pools
        donated."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_fused_decode_fn_cache", None)
        if cache is None:
            cache = self._fused_decode_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        if paged:
            def pure(p_list, b_list, k_pools, v_pools, block_tables,
                     tok, pos, temp, top_k, top_p, seed_lo, seed_hi,
                     ctr, eos, rem, *lora):
                with _swapped(params, dict(zip(pnames, p_list))), \
                        _swapped(mbuffers, dict(zip(bnames, b_list))):
                    with autograd.no_grad(), _lora_scope(lora):
                        out = model._fused_decode_tick_slots(
                            tok, k_pools, v_pools, pos, temp, top_k,
                            top_p, seed_lo, seed_hi, ctr, eos, rem,
                            block_tables=block_tables)
                return out
        else:
            def pure(p_list, b_list, k_pools, v_pools, tok, pos, temp,
                     top_k, top_p, seed_lo, seed_hi, ctr, eos, rem,
                     *lora):
                with _swapped(params, dict(zip(pnames, p_list))), \
                        _swapped(mbuffers, dict(zip(bnames, b_list))):
                    with autograd.no_grad(), _lora_scope(lora):
                        out = model._fused_decode_tick_slots(
                            tok, k_pools, v_pools, pos, temp, top_k,
                            top_p, seed_lo, seed_hi, ctr, eos, rem)
                return out

        fn = _jit_named("fused_decode", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching the other caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "fused_decode", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _compiled_fused_spec_verify_fn(self, pnames, params, cache_key,
                                       paged=False):
        """Build (or fetch) the jitted FUSED speculative verify +
        on-device sample/accept dispatch (``Engine(spec_k=...)``):
        contiguous layout (p_list, b_list,
        k_pools, v_pools, toks [B, W], lanes [B], pos [B], temp [B],
        top_k [B], top_p [B], seed_lo [B], seed_hi [B], ctr [B],
        eos [B], rem [B]) or paged layout (+ block_tables before
        toks) -> (picks [B, W], n_acc [B], n_emit [B], done
        [ceil(B/8)] uint8, new_tok [B,1], new_pos [B], new_ctr [B],
        new_rem [B], k_pools, v_pools).  ONE XLA program per
        (window, layout) — W and the pool shapes are static, per-slot
        positions and block tables are runtime inputs, so a fixed
        ``spec_k`` means exactly one compile per layout however
        traffic varies (compile-probe asserted in
        tests/test_serving.py).  Both layouts score the window through
        the same ``_slot_attn`` as their one-token decode twins, which
        is what makes speculative greedy outputs token-identical to
        the non-speculative engine.  The draft window uploads (drafts
        come from the host proposer); the download is picks + accept
        counts, never the [B, W, V] logits.  Pools donated."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_fused_spec_verify_fn_cache", None)
        if cache is None:
            cache = self._fused_spec_verify_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        if paged:
            def pure(p_list, b_list, k_pools, v_pools, block_tables,
                     toks, lanes, pos, temp, top_k, top_p, seed_lo,
                     seed_hi, ctr, eos, rem, *lora):
                with _swapped(params, dict(zip(pnames, p_list))), \
                        _swapped(mbuffers, dict(zip(bnames, b_list))):
                    with autograd.no_grad(), _lora_scope(lora):
                        out = model._fused_spec_verify_tick_slots(
                            toks, k_pools, v_pools, pos, lanes, temp,
                            top_k, top_p, seed_lo, seed_hi, ctr, eos,
                            rem, block_tables=block_tables)
                return out
        else:
            def pure(p_list, b_list, k_pools, v_pools, toks, lanes,
                     pos, temp, top_k, top_p, seed_lo, seed_hi, ctr,
                     eos, rem, *lora):
                with _swapped(params, dict(zip(pnames, p_list))), \
                        _swapped(mbuffers, dict(zip(bnames, b_list))):
                    with autograd.no_grad(), _lora_scope(lora):
                        out = model._fused_spec_verify_tick_slots(
                            toks, k_pools, v_pools, pos, lanes, temp,
                            top_k, top_p, seed_lo, seed_hi, ctr, eos,
                            rem)
                return out

        fn = _jit_named("fused_spec_verify", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching the other caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "fused_spec_verify", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _chunk_prefill_tick(self, toks, k_bufs, v_bufs, pos, true_len):
        """One CHUNKED-prefill dispatch against a slot's contiguous
        cache row: run C prompt tokens at positions pos..pos+C-1
        through each block's windowed ``decode`` (writes the chunk's
        K/V, attends causally over earlier chunks + the chunk itself),
        then run the LM head on the chunk's last REAL position only
        (``true_len - 1``) — non-final chunks discard their logits, so
        the head matmul never pays for the whole window.  Returns
        (last_logits [1, V], new_k, new_v)."""
        import jax
        x = self.embeddings(Tensor(toks), position_offset=pos)
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.decode(x, k_bufs[j], v_bufs[j], pos)
            new_k.append(kb)
            new_v.append(vb)
        E = x.shape[-1]
        last_h = jax.lax.dynamic_slice(
            x._data, (0, true_len - 1, 0), (1, 1, E))
        return self.head(Tensor(last_h))._data[:, -1, :], new_k, new_v

    def _chunk_prefill_tick_paged(self, toks, k_pools, v_pools,
                                  block_table, pos, true_len,
                                  scratch=0):
        """Paged twin of ``_chunk_prefill_tick``: the chunk's K/V
        scatters block-granular through ONE slot's block table and the
        attention context is the slot's gathered logical row (adopted
        prefix blocks included).  ``scratch`` is the slot's dp
        shard's scratch block id (traced scalar; 0 unsharded).
        Returns (last_logits [1, V], new_k, new_v)."""
        import jax
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        x = self.embeddings(Tensor(toks), position_offset=pos)
        new_k, new_v = [], []
        for j, blk in enumerate(self.blocks):
            x, kb, vb = blk.prefill_chunk_paged(
                x, k_pools[j], v_pools[j], block_table, pos, true_len,
                scratch=scratch)
            new_k.append(kb)
            new_v.append(vb)
        E = x.shape[-1]
        last_h = jax.lax.dynamic_slice(
            x._data, (0, true_len - 1, 0), (1, 1, E))
        return self.head(Tensor(last_h))._data[:, -1, :], new_k, new_v

    def _compiled_chunk_prefill_fn(self, pnames, params, cache_key, C,
                                   L, nh, hd, kv_dtype):
        """Build (or fetch) the jitted CONTIGUOUS chunk prefill:
        (p_list, b_list, k_pools, v_pools, ids [1, C], slot_idx, pos,
        true_len) -> (last_logits [1, V], k_pools, v_pools).  The
        serving engine's budgeted-chunked-prefill dispatch: the slot's
        [L] cache row is sliced out of the [B, L, H, hd] pools, the
        chunk runs through ``_chunk_prefill_tick``, and the updated row
        is written back — ONE program per fixed chunk shape serves
        EVERY chunk of EVERY prompt (slot_idx/pos/true_len are traced),
        so a fixed ``prefill_chunk`` means a bounded compile set.  Pad
        lanes of a partial final chunk write garbage rows past the
        prompt end — parity-safe under the causal mask (positions <
        true_len never see the pad tail, and decode overwrites each
        garbage row before any query can see it), and the engine
        requires C | L so the window never clamps onto live rows.
        Pools donated."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_chunk_prefill_fn_cache", None)
        if cache is None:
            cache = self._chunk_prefill_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, k_pools, v_pools, ids_arr, slot_idx,
                 pos, true_len, *lora):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad(), _lora_scope(lora):
                    k_bufs = [jax.lax.dynamic_slice(
                        kp, (slot_idx, 0, 0, 0), (1, L, nh, hd))
                        for kp in k_pools]
                    v_bufs = [jax.lax.dynamic_slice(
                        vp, (slot_idx, 0, 0, 0), (1, L, nh, hd))
                        for vp in v_pools]
                    last, new_k, new_v = model._chunk_prefill_tick(
                        ids_arr, k_bufs, v_bufs, pos, true_len)
                    k_pools = [jax.lax.dynamic_update_slice(
                        kp, nk.astype(kp.dtype), (slot_idx, 0, 0, 0))
                        for kp, nk in zip(k_pools, new_k)]
                    v_pools = [jax.lax.dynamic_update_slice(
                        vp, nv.astype(vp.dtype), (slot_idx, 0, 0, 0))
                        for vp, nv in zip(v_pools, new_v)]
            return last, k_pools, v_pools

        fn = _jit_named("chunk_prefill", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching _prefill_fn_cache
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "chunk_prefill", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _compiled_paged_chunk_prefill_fn(self, pnames, params,
                                         cache_key):
        """Build (or fetch) the jitted PAGED chunk prefill: (p_list,
        b_list, k_pools, v_pools, ids [1, C], block_table [L//bs], pos,
        true_len, scratch) -> (last_logits [1, V], k_pools, v_pools).
        ``scratch`` (traced scalar) is the slot's dp shard's scratch
        block id — pad lanes park there, never in another shard's
        rows.  The
        block-table twin of ``_compiled_chunk_prefill_fn``
        (``_chunk_prefill_tick_paged``): every shape is static and
        pos/true_len are traced, so ONE program serves every chunk —
        including resumed chunks after an adopted prefix-cache span
        (the adopted blocks are already in the table; ``pos`` starts at
        the adopted token count).  Pools donated."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_paged_chunk_prefill_fn_cache", None)
        if cache is None:
            cache = self._paged_chunk_prefill_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, k_pools, v_pools, ids_arr, block_table,
                 pos, true_len, scratch, *lora):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad(), _lora_scope(lora):
                    last, new_k, new_v = \
                        model._chunk_prefill_tick_paged(
                            ids_arr, k_pools, v_pools, block_table,
                            pos, true_len, scratch=scratch)
            return last, new_k, new_v

        fn = _jit_named("paged_chunk_prefill", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching the other caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "paged_chunk_prefill", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _compiled_paged_prefill_fn(self, pnames, params, cache_key,
                                   s_tail, n_ctx, n_tail, bs, nh, hd,
                                   kv_dtype):
        """Build (or fetch) the jitted BLOCK-GRANULAR prefill: (p_list,
        b_list, k_pools, v_pools, ids_tail [1, s_tail], ctx_blocks
        [n_ctx], tail_blocks [n_tail]) -> (last_logits [1, V], k_pools,
        v_pools).  ONE dispatch per admission: gathers the adopted
        prefix blocks as attention context (``n_ctx`` full blocks =
        the prefix-cache hit span, whose K/V is NOT recomputed), runs
        the prompt's non-shared tail at position offset ``n_ctx*bs``,
        and scatters the tail's K/V into the slot's fresh blocks.
        ``n_ctx = 0`` is the miss case — then this computes exactly
        what ``_compiled_prefill_fn`` computes (same forward, empty
        context), just stored block-granular.  The pad rows of the last
        (partial) tail block hold garbage that is parity-safe: the
        causal gather mask hides
        positions > pos until decode overwrites them, and partial
        blocks are never registered in the prefix cache.  Pools
        donated."""
        import jax
        import jax.numpy as jnp
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_paged_prefill_fn_cache", None)
        if cache is None:
            cache = self._paged_prefill_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)
        ctx_len = n_ctx * bs

        def _ctx_rows(pool, ctx_blocks):
            # adopted-prefix context: quantized pools dequantize ONLY
            # the gathered ctx blocks (codes x per-block scale row),
            # never the pool
            if _is_quant_kv(pool):
                from ..serving.quant import dequantize_blocks
                rows = dequantize_blocks(pool.codes[ctx_blocks],
                                         pool.scale[ctx_blocks])
                return rows.reshape(1, ctx_len, nh, hd)
            return pool[ctx_blocks].reshape(1, ctx_len, nh, hd)

        def _store_tail(pool, tail, tail_blocks):
            # tail scatter: whole fresh blocks quantize with a FRESH
            # per-block scale (pad rows are zeros — no amax inflation)
            if _is_quant_kv(pool):
                from ..serving.quant import QuantKV, quantize_blocks
                qt, st = quantize_blocks(tail)
                return QuantKV(pool.codes.at[tail_blocks].set(qt),
                               pool.scale.at[tail_blocks].set(st))
            return pool.at[tail_blocks].set(tail.astype(pool.dtype))

        def pure(p_list, b_list, k_pools, v_pools, ids_arr, ctx_blocks,
                 tail_blocks, *lora):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad(), _lora_scope(lora):
                    caches = [(Tensor(_ctx_rows(kp, ctx_blocks)),
                               Tensor(_ctx_rows(vp, ctx_blocks)))
                              for kp, vp in zip(k_pools, v_pools)]
                    logits, caches = model.forward(
                        Tensor(ids_arr), caches=caches,
                        position_offset=ctx_len)
                    pad = ((0, 0), (0, n_tail * bs - s_tail),
                           (0, 0), (0, 0))
                    new_k, new_v = [], []
                    for (ck, cv), kp, vp in zip(caches, k_pools,
                                                v_pools):
                        kt = jnp.pad(ck._data[:, ctx_len:], pad)[0] \
                            .reshape(n_tail, bs, nh, hd)
                        vt = jnp.pad(cv._data[:, ctx_len:], pad)[0] \
                            .reshape(n_tail, bs, nh, hd)
                        new_k.append(_store_tail(kp, kt, tail_blocks))
                        new_v.append(_store_tail(vp, vt, tail_blocks))
            return logits._data[:, -1, :], new_k, new_v

        fn = _jit_named("paged_prefill", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching _prefill_fn_cache
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "paged_prefill", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _fused_generate_fn(self, pnames, params, cache_key, n_steps,
                           start_pos, do_sample, temperature, top_k,
                           top_p, out_dtype):
        """Build (or fetch) the jitted WHOLE-DECODE fn: a lax.scan over
        ``n_steps`` one-token steps with sampling on device — the entire
        generation is ONE dispatch and ONE host sync.  The per-token
        compiled path (``_compiled_decode_fn``) pays a host round-trip
        per token (its cost on today's chip path is not measured —
        ROADMAP S4).  K/V buffers
        live in the scan carry (donated; updated in place).

        Trade-off vs the per-token step: the scan length and batch/cache
        shapes are part of the program, so each distinct (batch, total
        length, n_steps, sampling config) compiles its own executable —
        callers with naturally varying prompt lengths should bucket
        them.  The cache is FIFO-bounded to keep resident executables
        in check."""
        import jax
        import jax.numpy as jnp
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_gen_fn_cache", None)
        if cache is None:
            cache = self._gen_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pick(last, key):
            """Sample/argmax the next token from raw logits; returns
            (tok [B, 1], advanced key)."""
            last = last.astype(jnp.float32)
            if do_sample:
                last = GPTModel._filter_logits(last, temperature,
                                               top_k, top_p)
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, last, axis=-1)
            else:
                nxt = jnp.argmax(last, axis=-1)
            return nxt.astype(out_dtype).reshape(-1, 1), key

        def pure(p_list, b_list, k_bufs, v_bufs, last0, key0):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad():
                    def body(carry, i):
                        kbs, vbs, last, key = carry
                        tok, key = pick(last, key)
                        last, new_k, new_v = model._decode_tick(
                            tok, kbs, vbs, start_pos + i)
                        return (tuple(new_k), tuple(new_v), last, key), \
                            tok
                    init = (tuple(k_bufs), tuple(v_bufs), last0, key0)
                    # n_steps-1 scanned forwards; the final token needs
                    # no forward (the eager loop's 'skip the dead
                    # forward' break) — sample it from the carry
                    (_, _, last, key), toks = jax.lax.scan(
                        body, init,
                        jnp.arange(n_steps - 1, dtype=jnp.int32))
                    tok_last, _ = pick(last, key)
            # toks [N-1, B, 1] -> [B, N-1]; append the final sample
            toks = jnp.swapaxes(toks[..., 0], 0, 1) \
                if n_steps > 1 else jnp.zeros(
                    (tok_last.shape[0], 0), out_dtype)
            return jnp.concatenate([toks, tok_last], axis=1)

        # no donate_argnums: unlike the per-token step the K/V buffers
        # are consumed by the scan but never returned, so they cannot
        # alias an output — donating them only emits a warning
        fn = _jit_named("fused_generate", pure)
        if len(cache) >= 8:  # FIFO bound on resident executables
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "fused_generate", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _spec_generate_fn(self, pnames, params, cache_key, max_new,
                          start_pos, draft_k, ngram, out_dtype,
                          do_sample=False, temperature=1.0, top_k=0,
                          top_p=1.0):
        """Build (or fetch) the jitted SPECULATIVE whole-decode fn
        (round 5; NEW vs reference): prompt-lookup drafting + windowed
        verify, one device dispatch for the entire generation.

        Each iteration drafts ``draft_k`` tokens by finding the most
        recent previous occurrence of the last ``ngram`` generated
        tokens (prompt-lookup decoding — no draft model, ideal for
        summarization/code/chat where output n-grams repeat) and
        verifies the whole window in ONE forward via
        ``_decode_window``.  Greedy by construction: every emitted
        token is the model's own argmax from the windowed forward —
        drafts only decide how many tokens each forward yields
        (1..k+1).  On CPU this matches ``compiled='fused'`` greedy
        bit-for-bit (the tests assert it); on TPU a near-tie logit may
        round differently between the S=1 and S=W programs (shape-
        dependent GEMM tiling), so the cross-path guarantee there is
        "a valid greedy decode", not bit-identity.

        ``do_sample=True`` keeps the target distribution EXACT with a
        deterministic draft: position i of the window gets an
        independent sample s_i from the filtered conditional; the
        accepted prefix is ``draft_i == s_i``.  Each kept s_i is
        conditioned on a prefix that equals the accepted tokens, and
        its key is independent of the acceptance event, so emitted
        tokens are true conditional samples (the degenerate-draft case
        of Leviathan et al. rejection sampling).  The RANDOM STREAM
        differs from ``compiled='fused'`` (per-position keys vs
        per-step), so sampled outputs differ run-shape-to-run-shape —
        both are exact samples; only greedy is cross-path identical.
        Rejected-tail cache/sequence slots are overwritten before any
        later read (the window rewrites from its own start).  Batches
        advance SYNCHRONIZED by the per-step minimum accepted count —
        committed tokens always lie within every row's own accept run,
        so each row stays exactly its own greedy/sampled trajectory
        (sync costs speed on divergent rows, never correctness; B=1 is
        the latency sweet spot).

        Returns (ids [B, max_new], n_forwards) — the second value is
        the accept-rate diagnostic (forwards == max_new - 1 means
        nothing accepted; ~ max_new/(k+1) at full acceptance).
        """
        import jax
        import jax.numpy as jnp
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_spec_fn_cache", None)
        if cache is None:
            cache = self._spec_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)
        W = draft_k + 1
        T = start_pos + max_new + W        # margin: no update clamping

        def pick_row(logits_row, key):
            """One token from one position's logits: filtered sample or
            argmax (mirrors _fused_generate_fn's pick, per-position)."""
            row = logits_row.astype(jnp.float32)
            if do_sample:
                row = GPTModel._filter_logits(row[None, :], temperature,
                                              top_k, top_p)[0]
                return jax.random.categorical(key, row).astype(jnp.int32)
            return jnp.argmax(row).astype(jnp.int32)

        def pure(p_list, b_list, k_bufs, v_bufs, last0, ids_arr, key0):
            B = ids_arr.shape[0]
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad():
                    seq = jnp.zeros((B, T), jnp.int32)
                    seq = jax.lax.dynamic_update_slice(
                        seq, ids_arr.astype(jnp.int32), (0, 0))
                    t0_keys = jax.vmap(
                        lambda r: jax.random.fold_in(
                            key0, 2 ** 30 + r))(jnp.arange(B))
                    t0 = jax.vmap(pick_row)(last0, t0_keys)     # [B]
                    seq = seq.at[:, start_pos].set(t0)
                    win_idx = (jnp.arange(T)[:, None]
                               + jnp.arange(ngram)[None, :])

                    def draft_row(srow, pos):
                        pat = jax.lax.dynamic_slice(
                            srow, (pos - (ngram - 1),), (ngram,))
                        wins = srow[jnp.clip(win_idx, 0, T - 1)]
                        ok = jnp.all(wins == pat[None, :], axis=1)
                        # occurrences ending strictly before this one
                        ok &= (jnp.arange(T) + ngram - 1) < pos
                        found = jnp.any(ok)
                        j = jnp.where(found,
                                      T - 1 - jnp.argmax(ok[::-1]), 0)
                        dstart = jnp.clip(j + ngram, 0, T - draft_k)
                        d = jax.lax.dynamic_slice(srow, (dstart,),
                                                  (draft_k,))
                        # no match: repeat the current token (a guess
                        # like any other — rejection costs nothing
                        # beyond the fixed window forward)
                        return jnp.where(found, d,
                                         jnp.full((draft_k,),
                                                  srow[pos]))

                    def cond(c):
                        # t0 (from the prefill logits) is already in
                        # the buffer; the loop fills max_new - 1 more
                        return c[4] < max_new - 1

                    def body(c):
                        seq, kbs, vbs, pos, n_out, n_fwd = c
                        cur = jax.lax.dynamic_slice(seq, (0, pos),
                                                    (B, 1))
                        d = jax.vmap(lambda sr: draft_row(sr, pos))(
                            seq)                            # [B, k]
                        w = jnp.concatenate([cur, d], axis=1)
                        logits, new_k, new_v = model._decode_window(
                            w, list(kbs), list(vbs), pos)
                        # per-(row, position) keys independent of the
                        # acceptance event: kept samples stay true
                        # conditional draws
                        keys = jax.vmap(jax.vmap(
                            lambda r, i: jax.random.fold_in(
                                key0, (n_fwd * B + r) * W + i),
                            in_axes=(None, 0)), in_axes=(0, None))(
                            jnp.arange(B), jnp.arange(W))
                        preds = jax.vmap(jax.vmap(pick_row))(
                            logits, keys)                   # [B, W]
                        match = d == preds[:, :draft_k]
                        # per-row accepted prefix; rows advance in sync
                        # by the batch MINIMUM (committed tokens stay
                        # within every row's own accept run, so each
                        # row remains exactly its own greedy/sampled
                        # trajectory — sync costs speed, not
                        # correctness)
                        m_row = jnp.argmin(jnp.concatenate(
                            [match, jnp.zeros((B, 1), bool)],
                            axis=1), axis=1)                # [B]
                        m = jnp.min(m_row)
                        seq = jax.lax.dynamic_update_slice(
                            seq, preds, (0, pos + 1))
                        adv = m + 1
                        return (seq, tuple(new_k), tuple(new_v),
                                pos + adv, n_out + adv, n_fwd + 1)

                    init = (seq, tuple(k_bufs), tuple(v_bufs),
                            jnp.asarray(start_pos, jnp.int32),
                            jnp.asarray(0, jnp.int32),
                            jnp.asarray(0, jnp.int32))
                    seq, _, _, _, _, n_fwd = jax.lax.while_loop(
                        cond, body, init)
            out = jax.lax.dynamic_slice(seq, (0, start_pos),
                                        (B, max_new))
            return out.astype(out_dtype), n_fwd

        fn = _jit_named("spec_generate", pure)
        if len(cache) >= 8:  # FIFO bound, matching the other caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "spec_generate", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _compiled_prefill_fn(self, pnames, params, cache_key, b, s, L,
                             nh, hd, kv_dtype):
        """Build (or fetch) the jitted prefill: (p_list, b_list,
        ids [B, S]) -> (last_logits [B, V], k_bufs, v_bufs padded to L).
        The eager prefill dispatches every op individually — hundreds of
        host round-trips before the first token when the device is
        remote; this makes the whole prompt pass (and the cache padding)
        ONE dispatch."""
        import jax
        import jax.numpy as jnp
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_prefill_fn_cache", None)
        if cache is None:
            cache = self._prefill_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, ids_arr, *lora):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad(), _lora_scope(lora):
                    empty = [(Tensor(jnp.zeros((b, 0, nh, hd),
                                               kv_dtype)),
                              Tensor(jnp.zeros((b, 0, nh, hd),
                                               kv_dtype)))
                             for _ in model.blocks]
                    logits, caches = model.forward(Tensor(ids_arr),
                                                   caches=empty)
                    pad = ((0, 0), (0, L - s), (0, 0), (0, 0))
                    k_bufs = [jnp.pad(ck._data, pad) for ck, _ in caches]
                    v_bufs = [jnp.pad(cv._data, pad) for _, cv in caches]
            return logits._data[:, -1, :], k_bufs, v_bufs

        fn = _jit_named("prefill", pure)
        if len(cache) >= 8:  # FIFO bound, matching _gen_fn_cache
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "prefill", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def _compiled_decode_fn(self, pnames, params, cache_key):
        """Build (or fetch) the jitted one-token decode step: (p_list,
        b_list, k_bufs, v_bufs, tok [B,1], pos) -> (last_logits [B,V],
        k_bufs, v_bufs).  Fixed shapes — ONE XLA program serves every
        decode step (the eager path re-dispatches every op per token).
        K/V buffers are DONATED (in-place update, no per-token copy);
        the jitted fn is cached on the model so repeated generate()
        calls never recompile.  Model BUFFERS (e.g. weight-only-int8
        codes) are threaded as arguments, not closed over — closure
        capture would bake them into the executable as XLA constants,
        doubling their HBM footprint."""
        import jax
        from ..core import autograd
        from ..jit import _swapped

        cache = getattr(self, "_decode_fn_cache", None)
        if cache is None:
            cache = self._decode_fn_cache = {}
        if cache_key in cache:
            return cache[cache_key]

        model = self
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, k_bufs, v_bufs, tok, pos):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad():
                    last, new_k, new_v = model._decode_tick(
                        tok, k_bufs, v_bufs, pos)
            return last, new_k, new_v

        fn = _jit_named("decode", pure, donate_argnums=(2, 3))
        if len(cache) >= 8:  # FIFO bound, matching the other decode caches
            cache.pop(next(iter(cache)))
        cache[cache_key] = (self._compile_probe(
            "decode", cache_key, fn), bnames, mbuffers)
        return cache[cache_key]

    def generate(self, input_ids, max_new_tokens=20, temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None, seed=None,
                 compiled=False, draft_k=8, lookup_ngram=3):
        """KV-cached autoregressive decoding (greedy / top-k / top-p
        nucleus sampling; ``top_p<=0`` degenerates to top-1).

        The reference snapshot has no generation loop (PaddleNLP-era
        feature); provided here because incremental decode is the natural
        consumer of the attention cache.  ``compiled=True`` decodes
        through ONE jitted fixed-shape step (dynamic_update_slice into
        preallocated K/V buffers) instead of per-token eager dispatch.
        ``compiled="fused"`` goes further: the ENTIRE decode loop runs
        on device as one lax.scan (sampling included) — one dispatch,
        one host sync, no per-token round-trips (the right mode whenever
        the device is remote or per-call latency matters; its one
        trade-off is that early-eos stopping cannot skip the remaining
        scan steps, though the returned ids are truncated identically).
        ``compiled="speculative"`` (round 5): prompt-lookup drafting +
        windowed verify — up to ``draft_k + 1`` tokens per forward on
        repetitive text; greedy output equals fused greedy bit-for-bit
        on CPU (on TPU near-tie logits may round differently across
        window shapes), and sampling draws exact conditional samples
        via per-position keys + equality acceptance (a different random
        stream than 'fused', so sampled tokens differ between the two
        modes — both exact).  Batches advance by the per-step minimum
        accepted count (each row stays its own exact trajectory);
        ``draft_k``/``lookup_ngram`` tune the draft window.
        Accept-rate diagnostic: ``self.last_spec_forwards``.
        Returns [B, S + new] ids.
        """
        import jax
        import jax.numpy as jnp
        from ..core import rng as rng_mod, autograd
        from ..core.tensor import Tensor as T

        if self.scan_layers:
            # decode needs per-block KV caches; serve through an
            # auto-synced unrolled twin (round 5) — weights are sliced
            # views of the stacked params, re-synced every call so a
            # freshly-trained scan model decodes its current weights
            twin = self._sync_decode_twin()
            out = twin.generate(
                input_ids, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, seed=seed,
                compiled=compiled, draft_k=draft_k,
                lookup_ngram=lookup_ngram)
            self.last_spec_forwards = getattr(
                twin, "last_spec_forwards", None)
            return out
        ids = input_ids._data if hasattr(input_ids, "_data") else \
            jnp.asarray(input_ids)
        b, s = ids.shape
        if max_new_tokens <= 0:
            return T(ids)  # every path: prompt unchanged, no sampling
        max_position = self.embeddings.position_embeddings.weight.shape[0]
        if s + max_new_tokens > max_position:
            raise ValueError(
                f"generate: prompt ({s}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_position "
                f"({max_position}) — positions past the table would "
                "silently clamp")
        nh = self.blocks[0].attn.num_heads
        hd = self.blocks[0].attn.head_dim
        attn0 = self.blocks[0].attn
        if attn0.use_mp:
            kv_dtype = attn0.qkv_weight._data.dtype
        else:
            # compute_dtype first: a weight-only-int8 projection's
            # .weight property would MATERIALIZE the dequantized matrix
            # just to answer this dtype probe
            kv_dtype = getattr(attn0.qkv_proj, "compute_dtype", None) \
                or attn0.qkv_proj.weight._data.dtype
        # sampling whenever temperature/top_k/top_p ask for it; greedy
        # otherwise
        do_sample = ((top_k and top_k > 0) or temperature != 1.0
                     or top_p < 1.0)
        was_training = self.training
        self.eval()
        try:
            with autograd.no_grad():
                out = [ids]
                key = rng_mod.key_for(seed)

                if compiled == "speculative":
                    if s + max_new_tokens + draft_k > max_position:
                        raise ValueError(
                            "generate(compiled='speculative'): the "
                            "verify window can reach position "
                            f"{s + max_new_tokens + draft_k - 1} >= "
                            f"max_position ({max_position}) — lower "
                            "draft_k or max_new_tokens")

                step_fn = None
                if compiled:
                    # jitted prefill: whole prompt pass + cache padding
                    # to L in ONE dispatch (the eager prefill is a
                    # per-op round-trip storm on remote devices);
                    # speculative windows write up to draft_k slots past
                    # the last accepted position — pad the buffers so
                    # dynamic_update_slice can never clamp-shift
                    L = s + max_new_tokens
                    if compiled == "speculative":
                        L += draft_k + 1
                    params = dict(self.named_parameters())
                    pnames = sorted(params)
                    bnames_all = tuple(sorted(dict(self.named_buffers())))
                    pf, pf_bnames, pf_bufs = self._compiled_prefill_fn(
                        pnames, params,
                        (b, s, L, str(kv_dtype), tuple(pnames),
                         bnames_all),
                        b, s, L, nh, hd, kv_dtype)
                    p_list = [params[k2]._data for k2 in pnames]
                    b_list = [pf_bufs[k2]._data for k2 in pf_bnames]
                    last0, k_bufs, v_bufs = pf(p_list, b_list, ids)
                else:
                    # eager prefill: empty caches grow from zero-length
                    # k/v
                    empty = (T(jnp.zeros((b, 0, nh, hd), kv_dtype)),
                             T(jnp.zeros((b, 0, nh, hd), kv_dtype)))
                    caches = [empty for _ in self.blocks]
                    logits, caches = self.forward(T(ids), caches=caches)
                    last0 = logits._data[:, -1, :]

                def _truncate_at_eos(toks):
                    # match the eager loop: stop AFTER the first step
                    # where every row emitted eos (shared by the fused
                    # and speculative whole-decode paths)
                    if eos_token_id is None:
                        return toks
                    all_eos = jnp.all(toks == eos_token_id, axis=0)
                    if bool(jnp.any(all_eos)):
                        toks = toks[:, :int(jnp.argmax(all_eos)) + 1]
                    return toks

                if compiled == "speculative":
                    fn, sbnames, sbufs = self._spec_generate_fn(
                        pnames, params,
                        (b, L, max_new_tokens, int(draft_k),
                         int(lookup_ngram), str(kv_dtype),
                         str(ids.dtype), bool(do_sample),
                         float(temperature), int(top_k or 0),
                         float(top_p), tuple(pnames), bnames_all),
                        max_new=max_new_tokens, start_pos=s,
                        draft_k=int(draft_k), ngram=int(lookup_ngram),
                        out_dtype=ids.dtype, do_sample=do_sample,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p)
                    b_list = [sbufs[k2]._data for k2 in sbnames]
                    toks, n_fwd = fn(p_list, b_list, k_bufs, v_bufs,
                                     last0, ids, key)
                    self.last_spec_forwards = int(n_fwd)
                    return T(jnp.concatenate(
                        [ids, _truncate_at_eos(toks)], axis=1))

                if compiled == "fused":
                    fn, fbnames, fbufs = self._fused_generate_fn(
                        pnames, params,
                        (b, L, max_new_tokens, str(kv_dtype),
                         bool(do_sample), float(temperature),
                         int(top_k or 0), float(top_p), str(ids.dtype),
                         tuple(pnames), bnames_all),
                        n_steps=max_new_tokens, start_pos=s,
                        do_sample=do_sample, temperature=temperature,
                        top_k=top_k, top_p=top_p, out_dtype=ids.dtype)
                    b_list = [fbufs[k2]._data for k2 in fbnames]
                    toks = fn(p_list, b_list, k_bufs, v_bufs, last0, key)
                    return T(jnp.concatenate(
                        [ids, _truncate_at_eos(toks)], axis=1))

                if compiled:
                    step_fn, dec_bnames, dec_bufs = \
                        self._compiled_decode_fn(
                            pnames, params,
                            (b, L, str(kv_dtype), tuple(pnames),
                             bnames_all))
                    b_list = [dec_bufs[k2]._data for k2 in dec_bnames]

                def sample(last):
                    nonlocal key
                    last = last.astype(jnp.float32)
                    if do_sample:
                        last = self._filter_logits(last, temperature,
                                                   top_k, top_p)
                        key, sub = jax.random.split(key)
                        nxt = jax.random.categorical(sub, last, axis=-1)
                    else:
                        nxt = jnp.argmax(last, axis=-1)
                    return nxt.astype(ids.dtype).reshape(b, 1)

                last = last0
                for step in range(max_new_tokens):
                    nxt = sample(last)
                    out.append(nxt)
                    if eos_token_id is not None and bool(
                            jnp.all(nxt == eos_token_id)):
                        break
                    if step == max_new_tokens - 1:
                        break  # last token emitted; skip the dead forward
                    if compiled:
                        last, k_bufs, v_bufs = step_fn(
                            p_list, b_list, k_bufs, v_bufs, nxt,
                            jnp.asarray(s + step, jnp.int32))
                    else:
                        logits, caches = self.forward(
                            T(nxt), caches=caches,
                            position_offset=s + step)
                        last = logits._data[:, -1, :]
        finally:
            if was_training:
                self.train()
        return T(jnp.concatenate(out, axis=1))

    # -- the serving seam (models/programs.py) -------------------------
    def serving_spec(self):
        """What ``serving.Engine`` asks of this model: K and V rows of
        ``[H, hd]`` a layer in the dtype the attention projections
        compute in, the position table's length, and nothing it
        cannot honour (every engine option was written against this
        model)."""
        from ..ops.ragged_paged_attn import stream_rows
        attn0 = self.blocks[0].attn
        if attn0.use_mp:
            dtype = attn0.qkv_weight._data.dtype
        else:
            # compute_dtype first: a weight-only-int8 projection's
            # .weight property would materialize the dequantized matrix
            dtype = getattr(attn0.qkv_proj, "compute_dtype", None) \
                or attn0.qkv_proj.weight._data.dtype
        emb = self.embeddings
        return ServingSpec(
            decode_rows=functools.partial(
                walk_rows, row_width=2 * attn0.num_heads * attn0.head_dim),
            attn_core=self._attn_core,
            attn_kernel_check=self._attn_kernel_check,
            attn_kernel_rows=stream_rows,
            kv=KVRowSpec.heads(len(self.blocks), attn0.num_heads,
                               attn0.head_dim, dtype),
            max_positions=emb.position_embeddings.weight.shape[0],
            vocab_size=emb.word_embeddings.weight.shape[0],
            hidden_size=emb.word_embeddings.weight.shape[1],
            tensor_parallel=attn0.use_mp)

    def _attn_core(self, *, paged, quant, table_rows, block_size,
                   slots=None):
        """``ServingSpec.attn_core``: the form ``_slot_attn`` takes
        when the decode and verify programs are traced for such pools,
        by the rule it applies itself (``slot_attn_core``; the kernel's
        grid runs over any number of slots)."""
        attn0 = self.blocks[0].attn
        form, why = slot_attn_core(
            _backend(), paged=paged, quant=quant,
            head_dim=attn0.head_dim, mesh=_over_a_mesh(attn0.use_mp),
            table_rows=table_rows, block_size=block_size)
        return {"form": form, "why": why, "platform": _backend(),
                "head_dim": attn0.head_dim}

    def _attn_kernel_check(self, *, num_slots, block_size,
                           blocks_per_slot, num_blocks, dtype, spec_k,
                           device):
        """``ServingSpec.attn_kernel_check``: the streaming kernel at
        the decode window and the verify window."""
        from ..ops.ragged_paged_attn import compile_check
        attn0 = self.blocks[0].attn
        for window in sorted({1, (spec_k or 0) + 1}):
            try:
                compile_check(
                    num_slots=num_slots, window=window,
                    num_heads=attn0.num_heads, head_dim=attn0.head_dim,
                    block_size=block_size,
                    blocks_per_slot=blocks_per_slot,
                    num_blocks=num_blocks, dtype=dtype, device=device)
            except Exception as e:
                raise ValueError(f"at a window of {window}: {e}") from e

    def serving_linear_stacks(self):
        """The layers whose ``nn.Linear`` children weight-only int8
        serving relayouts: the transformer blocks (embeddings and the
        tied head stay)."""
        return list(self.blocks)

    def serving_lora_targets(self):
        """One ``nn.Linear`` a layer that a LoRA delta folds into: the
        attention output projection."""
        return [blk.attn.out_proj for blk in self.blocks]

    def _sync_decode_twin(self):
        """Unrolled twin for KV-cache decode of a scan_layers model:
        built once from the stored dense hyperparams, then every call
        re-points its tensors at the live weights DEVICE-SIDE — block
        leaves become lazy slices of the stacked arrays, non-block
        tensors are shared by reference (the ``param._data =``
        re-pointing idiom of ``parallel/pipeline.py
        unstack_block_params``; no host round-trip, unlike
        set_state_dict).  The twin lives in ``__dict__`` directly so it
        never registers as a sublayer — the scan model's
        state_dict/parameters stay twin-free.  Slice views cost a
        second set of block params in HBM while the twin is alive;
        drop it with ``del model.__dict__['_decode_twin_obj']``."""
        twin = self.__dict__.get("_decode_twin_obj")
        if twin is None:
            twin = GPTModel(**self._init_config, scan_layers=False)
            twin.eval()
            self.__dict__["_decode_twin_obj"] = twin
        L = int(self._init_config["num_layers"])
        twin_map = dict(twin.named_parameters())
        twin_map.update(dict(twin.named_buffers()))
        src_map = dict(self.named_parameters())
        src_map.update(dict(self.named_buffers()))
        synced = set()
        for k, v in src_map.items():
            if k.startswith("blocks."):
                rest = k[len("blocks."):]
                for i in range(L):
                    tk = f"blocks.{i}.{rest}"
                    twin_map[tk]._data = v._data[i]  # KeyError = loud
                    synced.add(tk)
            else:
                twin_map[k]._data = v._data
                synced.add(k)
        leftover = set(twin_map) - synced
        if leftover:
            raise RuntimeError(
                "decode twin has tensors the scan model never synced "
                f"(stale random init would decode garbage): "
                f"{sorted(leftover)[:5]}")
        return twin

    def to_tensor_parallel(self):
        """Build the TENSOR-PARALLEL twin of a dense model with the
        SAME weights: einsum-form attention projections carrying the
        head axis explicitly ([E,3,H,hd] / [H,hd,E] with 'mp'
        PartitionSpecs — see GPTAttention use_mp), Column/RowParallel
        MLP, VocabParallelEmbedding, and the column-parallel LM head
        (distributed/sharding.py).  The mapping is a pure relayout —
        ``qkv_proj.weight [E, 3E]`` reshapes to ``[E, 3, H, hd]``
        exactly as the dense forward's ``[b,s,3E] -> [b,s,3,H,hd]``
        reshape reads it, and ``out_proj.weight [H*hd, E]`` to
        ``[H, hd, E]`` — so the twin computes the same math
        modulo float summation order (XLA blocks the contractions
        differently), and greedy decode is token-identical in
        practice (asserted in tests/test_sharded_serving.py).  This
        is how ``Engine(mesh=...)`` gets a shardable serving model
        out of a dense checkpoint: pjit/GSPMD consumes the twin's
        PartitionSpecs and splits heads / FFN / vocab over the 'mp'
        mesh axis."""
        if getattr(self, "scan_layers", False):
            return self._sync_decode_twin().to_tensor_parallel()
        attn0 = self.blocks[0].attn
        if attn0.use_mp:
            return self  # already tensor-parallel
        for blk in self.blocks:
            # reject non-dense variants UP FRONT (the copy loop below
            # assumes plain GPTMLP/GPTAttention blocks; _init_config
            # deliberately drops moe/sp, so a silent conversion would
            # build a twin missing those layers)
            if not hasattr(blk.mlp, "fc1"):
                raise ValueError(
                    "to_tensor_parallel supports the dense GPT "
                    "variant only — MoE blocks already carry their "
                    "expert-parallel sharding")
            if blk.attn.use_sp:
                raise ValueError(
                    "to_tensor_parallel supports the dense GPT "
                    "variant only — sequence-parallel attention "
                    "shards the sequence axis, not heads")
        cfg = dict(self._init_config)
        tp = GPTModel(use_mp=True, **cfg)
        H, hd = attn0.num_heads, attn0.head_dim
        E = attn0.hidden_size
        emb_s, emb_t = self.embeddings, tp.embeddings
        emb_t.word_embeddings.weight._data = \
            emb_s.word_embeddings.weight._data
        emb_t.position_embeddings.weight._data = \
            emb_s.position_embeddings.weight._data
        for sb, tb in zip(self.blocks, tp.blocks):
            for ln in ("ln1", "ln2"):
                getattr(tb, ln).weight._data = \
                    getattr(sb, ln).weight._data
                getattr(tb, ln).bias._data = getattr(sb, ln).bias._data
            sa, ta = sb.attn, tb.attn
            ta.qkv_weight._data = sa.qkv_proj.weight._data.reshape(
                E, 3, H, hd)
            ta.qkv_bias._data = sa.qkv_proj.bias._data.reshape(
                3, H, hd)[:, None]
            ta.out_weight._data = sa.out_proj.weight._data.reshape(
                H, hd, E)
            ta.out_bias._data = sa.out_proj.bias._data
            for fc in ("fc1", "fc2"):
                getattr(tb.mlp, fc).weight._data = \
                    getattr(sb.mlp, fc).weight._data
                getattr(tb.mlp, fc).bias._data = \
                    getattr(sb.mlp, fc).bias._data
        tp.head.ln_f.weight._data = self.head.ln_f.weight._data
        tp.head.ln_f.bias._data = self.head.ln_f.bias._data
        tp.head.lm_head.weight._data = self.head.lm_head.weight._data
        tp.eval()
        return tp

    @classmethod
    def from_config(cls, name, **overrides):
        cfg = dict(GPT_CONFIGS[name])
        cfg.update(overrides)
        return cls(**cfg)


class GPTPretrainingCriterion(nn.Layer):
    """Next-token CE over shifted logits (PaddleNLP GPT criterion shape)."""

    def forward(self, logits, labels):
        b, s, v = logits.shape
        return F.cross_entropy(reshape(logits, [b * s, v]),
                               reshape(labels, [b * s]))


def gpt_pipe_model(name="gpt2-medium", **overrides):
    """Build the PipelineLayer form: pre=embeddings, blocks, post=head."""
    from ..distributed.fleet.meta_parallel import PipelineLayer
    cfg = dict(GPT_CONFIGS[name])
    cfg.update(overrides)
    num_layers = cfg.pop("num_layers")
    hidden = cfg.pop("hidden_size")
    heads = cfg.pop("num_heads")
    vocab = cfg.pop("vocab_size")
    max_pos = cfg.pop("max_position")
    dropout = cfg.pop("dropout", 0.1)
    use_mp = cfg.pop("use_mp", False)
    pre = GPTEmbeddings(vocab, hidden, max_pos, dropout, use_mp)
    blocks = [GPTBlock(hidden, heads, dropout, use_mp)
              for _ in range(num_layers)]
    post = GPTLMHead(hidden, vocab, use_mp)
    return PipelineLayer(pre=pre, blocks=blocks, post=post)


def packed_doc_inputs(doc_lens, seq):
    """Packed-sequence (multi-document-per-row) attention inputs.

    ``doc_lens`` [B, D] int (zero-padded document lengths per row,
    summing <= seq — enforced on the concrete path; the
    TokenBudgetBatchSampler/RaggedTensor layout).  Returns
    (position_ids [B, seq] — resetting to 0 at each document start;
    doc_segments [B, seq] int32 — the per-position document id consumed
    by attention as flash SegmentIds (long seq: block-diagonal masking
    inside the kernel, no S×S tensor) or a derived dense mask (short
    seq/CPU); label_keep [B, seq] bool — False at each document's last
    token and at padding, whose next-token target belongs to a
    different document).  Padding positions get the one-past id D,
    which matches no live document.  NEW capability vs the reference
    (packed pretraining is a post-snapshot LLM practice)."""
    import jax
    import jax.numpy as jnp

    dl = (doc_lens._data if isinstance(doc_lens, Tensor)
          else jnp.asarray(doc_lens)).astype(jnp.int32)
    if dl.ndim == 1:
        dl = dl[None, :]
    if not isinstance(dl, jax.core.Tracer):
        worst = int(jnp.max(jnp.sum(dl, axis=1)))
        if worst > seq:
            raise ValueError(
                f"packed_doc_inputs: doc_lens sum to {worst} > seq "
                f"{seq} — the tail would be silently truncated and its "
                "labels scored against phantom targets")
    splits = jnp.concatenate(
        [jnp.zeros((dl.shape[0], 1), jnp.int32),
         jnp.cumsum(dl, axis=1)], axis=1)              # [B, D+1]
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :]    # [1, seq]
    # document id per position; pos >= total implies pos >= every split,
    # so padding lands on the one-past id D with no extra masking
    doc_ids = jnp.sum(pos[:, :, None] >= splits[:, None, 1:],
                      axis=-1).astype(jnp.int32)       # [B, seq]
    total = splits[:, -1:]
    live = pos < total
    # splits is [B, D+1], so splits[doc_id] is the doc start even for
    # padding's one-past id (whose result the where() discards anyway)
    starts = jnp.take_along_axis(splits, doc_ids, axis=1)
    position_ids = jnp.where(live, pos - starts, 0)
    # keep a label iff its position AND the next position sit in the
    # same document (the next-token target stays in-document)
    nxt = jnp.broadcast_to(jnp.minimum(pos + 1, seq - 1),
                           doc_ids.shape)
    next_doc = jnp.take_along_axis(doc_ids, nxt, axis=1)
    label_keep = live & (doc_ids == next_doc) & (pos + 1 < total)
    return (Tensor(position_ids), Tensor(doc_ids), Tensor(label_keep))
