"""Decoder whose layers are of two kinds of attention in one model —
sliding-window layers with rotary positions and full-attention layers
with no positions at all — with a sigmoid gate on the attention's
output, four norms a block and sigmoid-routed experts of which this
chip may hold a share: the AFMoE family (Arcee Trinity Large), served
on ``serving.Engine``'s paged path.

Layer equations (d hidden, H query heads, K key/value heads, hd the
head size, W the window, E routed experts, k of them a token; RMSNorm,
no bias anywhere, untied head):

* embedding ``x_0 = Emb[id] * sqrt(d)`` where the config says
  ``mup_enabled``; head ``logits = RMSNorm(x_L) W_head``.
* block l of kind ``layer_types[l]``, FOUR norms ("sandwich"):
  ``x <- x + RMSNorm_post_attn(Attn(RMSNorm_in(x)))``, then
  ``x <- x + RMSNorm_post_mlp(FF(RMSNorm_pre_mlp(x)))``.
* ``Attn(a)``: ``q = a W_q`` [H, hd], ``k = a W_k``, ``v = a W_v``
  [K, hd], ``g = a W_g`` [H hd]; every head of q and of k is
  RMS-normalised over hd with a learned gain (``sdar_moe.GQAttention``:
  the same grouped-query layer, its cached row K and V flat in ONE pool
  a layer).  In a ``sliding_attention`` layer q and k are rotated
  (``rope``: dimension i pairs with i + hd/2, theta, no scaling) and
  position i sees j iff ``j <= i`` and ``i - j < W``: the window holds
  W keys, the query's own among them.  In a ``full_attention`` layer
  they are NOT rotated and i sees every ``j <= i``.  Query head h reads
  K/V head ``h // (H / K)``; scores ``q . k / sqrt(hd)`` in float32;
  ``o = softmax(scores) v``; ``Attn = (o * sigmoid(g)) W_o``.
* ``FF``, the first ``num_dense_layers`` layers: SwiGLU of width
  ``intermediate_size``.  The others: ``s = sigmoid(b W_r)`` in float32
  over all E experts; the k experts are the top k of ``s +
  expert_bias``; ``w = s[choice]``, normalised to sum to one
  (``route_norm``) and multiplied by ``route_scale``; ``FF(b) =
  Shared(b) + sum_j w_j E_{c_j}(b)``, every expert a SwiGLU of width
  ``moe_intermediate_size`` (``mla_moe.RoutedFFN``, ``distributed/moe``
  ``sigmoid_topk_routing``: the DeepSeek-V3 gate with one group).

**The chip's share.**  A deployment divides each expert layer over
several chips.  ``config["num_experts"]`` is how many experts THIS
model holds, ``experts_of`` the router's width and ``experts_first``
the first one held: the router and its bias keep ``experts_of``
columns, a pair whose expert lies elsewhere adds nothing here (its
chip adds it: no exchange is written and nothing stands in for it),
the shared expert is whole.  What the absent experts would have added
is left out of the layer's output, and that partial result goes on to
the next layer, in this model and in its reference alike.  The
vocabulary may be a slice the same way: a smaller vocabulary.

**The window in the walks.**  ``models/programs.py`` ``walk_plan``
builds one work list a KIND of layer (``reach`` W, or None: back to row
0), which XLA shares among the layers of that kind; the chunk program's
one-slot walk starts at the first chunk any of its queries sees; the
engine keeps ONE block table a slot, so a sliding layer's rows behind
the window stay held and are never read.

What is not here: grouped top-k (``n_group`` / ``topk_group`` other
than 1 are refused by name), rope scaling, the load-balance loss
(training's), the exchange between chips that hold different experts.
"""
from __future__ import annotations

import functools
import math

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from .mla_moe import MOE_COUNTERS, GatedMLP, RMSNorm, RoutedFFN, _lin
from .programs import (
    KVRowSpec, ServedModel, ServingSpec, _scoped, sample_lanes,
    slot_sample_keys, walk_chunk, walk_group, walk_plan)
from .sdar_moe import GQAttention, walk_kernel_check

KINDS = ("sliding_attention", "full_attention")

# this family's counters, after the routed layers' four, in the order
# of the vector the step programs return: the live pairs whose expert
# another chip holds; the cached rows the decode lanes' queries see and
# those the chunk program's do, summed over the layers (the necessary
# reads: ``min(p + 1, W)`` a sliding layer, ``p + 1`` a full one); the
# rows the walks fetched in the layers of each kind, padding items
# included (both programs)
AFMOE_COUNTERS = (("moe_pairs_elsewhere", "elsewhere"),
                  ("attn_rows_seen", None),
                  ("attn_rows_seen_chunk", None),
                  ("attn_rows_walked_sliding", None),
                  ("attn_rows_walked_full", None))


class GatedGQAttention(GQAttention):
    """``GQAttention`` under the causal mask with a sigmoid gate on the
    heads' output: ``(o * sigmoid(a W_g)) W_o``; ``reach`` the window
    of a sliding layer (which also rotates q and k), None for a full
    layer (which does not)."""

    def __init__(self, hidden, num_heads, num_kv_heads, head_dim,
                 rope_theta, eps, reach=None):
        super().__init__(hidden, num_heads, num_kv_heads, head_dim,
                         rope_theta, eps, block_length=1, reach=reach,
                         rotary=reach is not None)
        self.gate_proj = nn.Linear(hidden, num_heads * head_dim,
                                   bias_attr=False)

    def attend(self, *args):
        import jax
        with jax.named_scope("attn.full" if self.reach is None
                             else "attn.sliding"):
            return super().attend(*args)

    @_scoped("attn.gate")
    def output(self, ctx, h):
        import jax
        return _lin(self.o_proj, ctx * jax.nn.sigmoid(
            _lin(self.gate_proj, h)))

    def rows_walked(self, pos, slots, pool, table_rows):
        """Device twin of ``GQAttention.decode_rows``: the cache rows
        ``attend`` fetches from ``pool`` for ``slots`` slots whose rows
        start at ``pos`` [slots] (each kind of walk as ``attend`` picks
        it, a trip of the work list in the form ``core`` names: whole
        trips in XLA, the items alone in the kernel), int32 []."""
        import jax.numpy as jnp
        bs = pool.shape[1]
        chunk = walk_chunk(table_rows, bs)
        trips = -(-table_rows // chunk)
        if trips == 1:
            return jnp.int32(slots * table_rows)
        if slots > 1:
            group = walk_group(slots, pool.shape[2])
            _, _, valid, n_trips = walk_plan(pos, 0, table_rows, chunk,
                                             group, self.reach)
            if self.core(slots, pool, table_rows) == "kernel":
                return jnp.sum(valid, dtype=jnp.int32) * chunk
            return n_trips * (group * chunk)
        first, end = self.one_slot_span(pos, chunk, trips)
        return (end - first) * chunk

    def rows_seen(self, end):
        """Cached rows a query at position ``end - 1`` sees, its own
        among them (any shape)."""
        import jax.numpy as jnp
        return end if self.reach is None else jnp.minimum(end, self.reach)


class AfmoeBlock(nn.Layer):
    """One block under its four norms (module docstring); ``ffn`` is a
    ``GatedMLP`` (a leading dense layer) or a ``RoutedFFN`` holding
    ``held`` of the router's experts."""

    def __init__(self, cfg, kind, routed, held, experts_of):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.kind, self.routed = kind, routed
        self.input_norm = RMSNorm(d, eps)
        self.attn = GatedGQAttention(
            d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["rope_theta"], eps,
            reach=cfg["sliding_window"] if kind == KINDS[0] else None)
        self.post_attn_norm = RMSNorm(d, eps)
        self.pre_mlp_norm = RMSNorm(d, eps)
        self.post_mlp_norm = RMSNorm(d, eps)
        self.ffn = (RoutedFFN(d, cfg["moe_intermediate_size"], experts_of,
                              cfg["num_experts_per_tok"],
                              cfg["num_shared_experts"],
                              cfg["route_scale"],
                              cfg.get("route_norm", True), held=held)
                    if routed else GatedMLP(d, cfg["intermediate_size"]))

    @_scoped("mlp")
    def feed_forward(self, x, live):
        """x [B, S, D], live [B, S] -> (x + RMSNorm(FF(RMSNorm(x))),
        stats or None)."""
        h = self.pre_mlp_norm(x)
        if not self.routed:
            return x + self.post_mlp_norm(self.ffn(h)), None
        y, stats = self.ffn(h.reshape(-1, h.shape[-1]), live.reshape(-1))
        return x + self.post_mlp_norm(y.reshape(x.shape)), stats

    def step_slots_paged(self, x, pool, tables, pos, live):
        import jax.numpy as jnp
        a, pool = self.attn.step_slots_paged(
            self.input_norm(x), pool, tables, pos,
            jnp.where(live, pos, 0))
        return (*self.feed_forward(x + self.post_attn_norm(a),
                                   live[:, None]), pool)

    def prefill_chunk_paged(self, x, pool, table, pos, true_len,
                            scratch, live):
        a, pool = self.attn.prefill_chunk_paged(
            self.input_norm(x), pool, table, pos, true_len, scratch)
        return (*self.feed_forward(x + self.post_attn_norm(a),
                                   live[None, :]), pool)

    def forward(self, x):
        import jax.numpy as jnp
        x = x + self.post_attn_norm(self.attn(self.input_norm(x)))
        return self.feed_forward(x, jnp.ones(x.shape[:2], bool))[0]


class AfmoeModel(ServedModel, nn.Layer):
    """Decoder-only LM of the module's docstring.  ``config`` holds the
    published keys (``hidden_size``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
    ``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
    ``num_shared_experts``, ``num_dense_layers``, ``num_hidden_layers``,
    ``layer_types``, ``sliding_window``, ``route_norm``, ``route_scale``,
    ``mup_enabled``, ``vocab_size``, ``max_position_embeddings``,
    ``rms_norm_eps``, ``rope_theta``); the chip's share of the experts
    is the constructor's: ``config["num_experts"]`` held from
    ``experts_first`` of a router ``experts_of`` wide (None: every
    expert is held).  Build it under ``nn.LazyGuard()`` to declare the
    parameters without values."""

    def __init__(self, config, experts_first=0, experts_of=None):
        super().__init__()
        cfg = dict(config)
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("grouped top-k routing is not written: "
                             "n_group and topk_group have to be 1")
        if cfg.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError("the gate scores with a sigmoid")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not written")
        kinds = list(cfg["layer_types"])
        if len(kinds) != cfg["num_hidden_layers"] \
                or set(kinds) - set(KINDS):
            raise ValueError(
                f"layer_types has to name one of {KINDS} for each of "
                f"the {cfg['num_hidden_layers']} layers")
        held = None
        if experts_of is not None and (
                experts_first or experts_of != cfg["num_experts"]):
            held = (experts_first, cfg["num_experts"])
        self.config, self.held = cfg, held
        self.experts_of = experts_of or cfg["num_experts"]
        d = cfg["hidden_size"]
        self.embed_scale = math.sqrt(d) if cfg.get("mup_enabled") else 1.0
        self.embed = self.create_parameter(
            [cfg["vocab_size"], d],
            default_initializer=I.Normal(0.0, 0.02))
        self.blocks = nn.LayerList([
            AfmoeBlock(cfg, kind, i >= cfg["num_dense_layers"], held,
                       self.experts_of)
            for i, kind in enumerate(kinds)])
        self.norm = RMSNorm(d, cfg["rms_norm_eps"])
        self.lm_head = nn.Linear(d, cfg["vocab_size"], bias_attr=False)

    @property
    def routed_layers(self):
        return sum(1 for b in self.blocks if b.routed)

    def layers_of(self, kind):
        return sum(1 for b in self.blocks if b.kind == kind)

    def _embed(self, ids):
        x = self.embed._data[ids]
        return x * self.embed_scale if self.embed_scale != 1.0 else x

    @_scoped("lm_head")
    def _head(self, x):
        import jax.numpy as jnp
        return _lin(self.lm_head, self.norm(x)).astype(jnp.float32)

    def forward(self, input_ids):
        """Uncached logits [B, S, V] (float32)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else input_ids
        x = self._embed(ids)
        for blk in self.blocks:
            x = blk(x)
        return Tensor(self._head(x))

    def _counter_vector(self, stats, seen, seen_chunk, walked):
        """int32 [9]: the routed layers' four (``MOE_COUNTERS``: pairs
        computed here, held experts hit, held expert slots, the busiest
        expert's pairs), then ``AFMOE_COUNTERS``; ``walked`` maps a
        kind of layer to the rows its walks fetched."""
        import jax.numpy as jnp
        s = sum(stats) if stats else jnp.zeros((4,), jnp.int32)
        slots = self.routed_layers * self.config["num_experts"]
        away = s[3] if self.held is not None else 0
        return jnp.stack([
            s[0], s[1], jnp.int32(slots), s[2], away, seen, seen_chunk,
            *(walked[k] for k in KINDS)]).astype(jnp.int32)

    def _rows_walked(self, pos, slots, tables, pools):
        """{kind: rows the walks of the layers of that kind fetch} for
        rows that start at ``pos`` [slots]."""
        import jax.numpy as jnp
        table_rows = tables.shape[-1] * pools[0].shape[1]
        walked = {k: jnp.int32(0) for k in KINDS}
        for blk, pool in zip(self.blocks, pools):
            walked[blk.kind] = walked[blk.kind] + blk.attn.rows_walked(
                pos, slots, pool, table_rows)
        return walked

    def _rows_seen(self, end):
        """Rows a query at ``end - 1`` sees, summed over the layers."""
        return sum(blk.attn.rows_seen(end) for blk in self.blocks)

    # -- step programs -------------------------------------------------
    def _fused_decode_tick_slots(self, tok, pools, tables, pos, temp,
                                 top_k, top_p, seed_lo, seed_hi, ctr,
                                 eos, rem):
        """``MLAMoEModel._fused_decode_tick_slots`` over this family's
        pools: one token a slot through every block, sampling and the
        stop condition on the device, the same outputs, the counter
        vector last.  A lane without budget (``rem <= 0``: parked)
        stands at position 0, walks no cached row and is routed to no
        expert."""
        import jax.numpy as jnp
        live = rem > 0
        x = self._embed(tok[:, 0])[:, None, :]
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, st, pool = blk.step_slots_paged(x, pools[j], tables, pos,
                                               live)
            new_pools.append(pool)
            if st is not None:
                stats.append(st)
        last = self._head(x)[:, -1, :]
        L = tables.shape[1] * pools[0].shape[1]
        keys = slot_sample_keys(seed_lo, seed_hi, ctr)
        sampled = sample_lanes(last, temp, top_k, top_p, keys)
        ids = jnp.where(live, sampled, tok[:, 0])
        hit_eos = live & (eos >= 0) & (ids == eos)
        new_rem = jnp.where(live, jnp.where(hit_eos, 0, rem - 1), rem)
        done = jnp.packbits((new_rem <= 0).astype(jnp.uint8))
        new_pos = jnp.where(live, jnp.minimum(pos + 1, L - 1), pos)
        new_ctr = jnp.where(live, ctr + 1, ctr)
        return (ids, done, ids[:, None], new_pos, new_ctr, new_rem,
                new_pools, [], self._counter_vector(
                    stats,
                    jnp.sum(jnp.where(live, self._rows_seen(pos + 1), 0)),
                    0, self._rows_walked(jnp.where(live, pos, 0),
                                         tok.shape[0], tables, pools)))

    def _chunk_prefill_tick_paged(self, toks, pools, table, pos,
                                  true_len, scratch):
        """C prompt tokens of one slot through every block; the head
        runs on the last REAL position only.  Returns (last logits
        [1, V], pools, [], counters)."""
        import jax
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        live = jnp.arange(toks.shape[1]) < true_len
        x = self._embed(toks)
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, st, pool = blk.prefill_chunk_paged(
                x, pools[j], table, pos, true_len, scratch, live)
            new_pools.append(pool)
            if st is not None:
                stats.append(st)
        last_h = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        # the union of what the chunk's queries see: the first one's
        # rows and every later query's own
        return (self._head(last_h)[:, -1, :], new_pools, [],
                self._counter_vector(
                    stats, 0, self._rows_seen(pos + 1) + len(self.blocks)
                    * (true_len - 1), self._rows_walked(
                        jnp.reshape(pos, (1,)), 1, table, pools)))

    def _compiled_fused_decode_fn(self, pnames, params, cache_key,
                                  paged=False):
        """(p_list, b_list, pools, [], block_tables, tok, pos, temp,
        top_k, top_p, seed_lo, seed_hi, ctr, eos, rem) -> (ids, done,
        new_tok, new_pos, new_ctr, new_rem, pools, [], counters).
        Pools donated."""
        if not paged:
            raise NotImplementedError(
                "the K/V pools are paged: no contiguous decode")

        def body(pools, _v, tables, tok, pos, *lanes):
            return self._fused_decode_tick_slots(tok, pools, tables,
                                                 pos, *lanes)
        return self._program("fused_decode", cache_key, params, pnames,
                             body)

    def _compiled_paged_chunk_prefill_fn(self, pnames, params,
                                         cache_key):
        """(p_list, b_list, pools, [], ids [1, C], block_table, pos,
        true_len, scratch) -> (last logits [1, V], pools, [],
        counters).  Pools donated."""
        def body(pools, _v, ids, table, pos, true_len, scratch):
            return self._chunk_prefill_tick_paged(
                ids, pools, table, pos, true_len, scratch)
        return self._program("paged_chunk_prefill", cache_key, params,
                             pnames, body)

    # -- the serving seam ----------------------------------------------
    def decode_rows(self, pos, ahead, table_rows, block_size,
                    padded=True):
        """``ServingSpec.decode_rows`` (``attn_kernel_rows`` where not
        ``padded``): the rows one decode dispatch
        fetches in a layer, the mean over the layers of both kinds (so
        that over ``serving.decode_rows_table`` it stays a share of one
        layer's table; ``serving.attn_rows_walked_*`` have each kind's
        own, read on the device).  The step's own row comes from the
        step itself: the walk reads rows below ``pos``."""
        return sum(blk.attn.decode_rows(pos, ahead - 1, table_rows,
                                        block_size, padded)
                   for blk in self.blocks) // len(self.blocks)

    def serving_spec(self):
        from ..distributed.moe import grouped_matmul_impl
        cfg = self.config
        k_proj = self.blocks[0].attn.k_proj
        dtype = getattr(k_proj, "compute_dtype", None) \
            or k_proj.weight._data.dtype
        flat = "K and V lie flat in one [2 K hd] row: "
        return ServingSpec(
            kv=KVRowSpec(len(self.blocks), dtype, (("kv", (
                2 * cfg["num_key_value_heads"] * cfg["head_dim"],)),)),
            max_positions=cfg["max_position_embeddings"],
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            counters=MOE_COUNTERS + AFMOE_COUNTERS,
            kernels={"moe.experts": grouped_matmul_impl()},
            decode_rows=self.decode_rows,
            attn_kernel_rows=functools.partial(self.decode_rows,
                                               padded=False),
            attn_core=self.blocks[0].attn.serving_core,
            attn_kernel_check=functools.partial(
                walk_kernel_check, [b.attn for b in self.blocks], 1),
            attention={"window": cfg["sliding_window"], "layers": {
                "sliding": self.layers_of(KINDS[0]),
                "full": self.layers_of(KINDS[1])}},
            experts={"held": [self.held[0] if self.held else 0,
                              cfg["num_experts"]],
                     "of": self.experts_of},
            unsupported={
                "contiguous": "a contiguous [slots, L] K/V buffer and "
                              "its step / prefill programs",
                "unchunked_prefill": "the per-length paged prefill "
                                     "program under the window",
                "ragged": "a window and grouped K/V heads in "
                          "ops/ragged_paged_attn.py",
                "spec": "the verify-window program over two kinds of "
                        "layer",
                "kv_int8": flat + "quant.py's QuantKV scales are per "
                           "head",
                "mp": "grouped K/V heads over 'mp', an expert axis in "
                      "SERVING_SPECS and the exchange of rows between "
                      "chips that hold different experts are not "
                      "written",
                "lora": "LoRA banks fold into GPTAttention.out_proj; "
                        "o_proj here has no lane-gathered form",
                "offload": "HostBlockStore entries are (layers, 2, bs, "
                           "H, hd): no flat-row form",
                "migration": "the migration wire's (layers, K|V, "
                             "blocks, bs, H, hd) payload: no flat-row "
                             "form",
            })

    def serving_linear_stacks(self):
        return list(self.blocks)
