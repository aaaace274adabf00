"""Decoder with multi-head latent attention (MLA) and sigmoid-routed
experts — the DeepSeek-V3 layer family (Kimi-VL-A3B's language decoder,
Moonlight, DeepSeek-V2/V3), served on ``serving.Engine``'s paged path.

Layer equations (pre-norm residual blocks, RMSNorm, no position table,
untied head; d hidden, H heads, d_n / d_r the no-position and rotary
parts of a query/key head, d_v the value head, r the latent rank):

* attention, ``h = RMSNorm(x)``: ``q = h W_q`` (or, where the config
  gives ``q_lora_rank``, ``q = RMSNorm(h W_qa) W_qb``: the query is
  compressed too) -> per head
  ``[q_n (d_n); q_r (d_r)]``; ``[c; k_r] = h W_kva`` (r + d_r);
  ``c <- RMSNorm(c)``; ``k_r <- RoPE(k_r, pos)`` (ONE rotary key for
  all heads); ``q_r <- RoPE(q_r, pos)``.  **The cached row of a
  position is ``[c; k_r]``: r + d_r numbers a layer, no V.**
  Expanded form: ``[k_n,i; v_i] = c W_kvb,i``; ``s_ij = (q_n,i . k_n,ij
  + q_r,i . k_r,j) / sqrt(d_n + d_r)``; causal softmax;
  ``o_i = sum_j p_ij v_ij``; ``y = concat(o) W_o``.
  Absorbed form (the same numbers): ``q~_i = W_uk,i q_n,i`` (r),
  ``s_ij = (q~_i . c_j + q_r,i . k_r,j) / sqrt(d_n + d_r)``,
  ``o~_i = sum_j p_ij c_j``, ``o_i = o~_i W_uv,i`` with ``W_uk,i`` /
  ``W_uv,i`` the K and V halves of ``W_kvb`` for head i: the query is
  taken to the latent space, so a decode row never expands a cached
  position.
* the first ``first_k_dense`` layers: SwiGLU ``(silu(h W1) * (h W3))
  W2``.
* the others: ``s = sigmoid(h W_g)`` in float32; the k experts are the
  top k of ``s + b`` (``b`` a correction bias used to select only);
  ``w_e = scale * s_e / sum_selected s``; ``y = sum_e w_e E_e(h) +
  S(h)``, ``E_e`` a SwiGLU of the expert width, ``S`` one SwiGLU of
  ``n_shared`` times that width over every token.  No token is dropped
  (``distributed/moe.py`` ``dropless_experts``).

* the residual, where the config gives ``hc_mult`` n > 1
  (manifold-constrained hyper-connections, arXiv:2512.24880): a
  position's state is n streams ``X [n, d]``, every stream the
  embedding after the lookup, their sum before the final norm.  Around
  EACH sub-layer F (attention, feed-forward), with that sub-layer's
  own leaves: ``x' = RMSNorm(vec(X))`` over all n d numbers;
  ``[Hpre~; Hpost~; Hres~] = a * (Phi x') + b`` (``Phi`` [n (n + 2),
  n d], ``a`` one scalar a mapping, all float32); ``H_pre =
  sigmoid(Hpre~)``, ``H_post = 2 sigmoid(Hpost~)``, ``H_res`` the
  Sinkhorn projection of ``exp(clip(Hres~))`` (``hc_sinkhorn_iters``
  times columns, then rows, divided by their sums + ``hc_eps``: doubly
  stochastic); ``u = H_pre X``, ``X <- H_res X + H_post^T F(u)``
  (``HyperConnection``).

RoPE pairs dimension i with i + d_r/2 in STORED order (the published
code first de-interleaves; that is a fixed permutation of W_q's and
W_kva's columns); ``rope_scaling`` of type ``yarn`` interpolates the
slow frequencies and scales the softmax (``yarn_inv_freq``,
``yarn_mscale``).  What is not here: a vision tower (positions are
token positions), grouped top-k (one group), the next-token prediction
module (``num_nextn_predict_layers``: one more block that drafts token
t + 2; the main model's logits do not depend on it).

Serving: ``serving_spec()`` declares one pool a layer of ``[r + d_r]``
rows; ``serving_program`` offers the fused decode tick (absorbed form,
walking each slot's live blocks in the pool's dtype) and the paged
chunk prefill (absorbed or expanded by the chunk's shape), both
returning an int32 counter vector beside their outputs.
"""
from __future__ import annotations

import math

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from .programs import (
    KVRowSpec, ServedModel, ServingSpec, _scoped, sample_lanes,
    slot_sample_keys, walk_chunk, walk_group, walk_plan)

# counters of the routed layers, in the order of the vector the step
# programs return; (registry name under "serving.", dev.* span arg)
MOE_COUNTERS = (("moe_routed_pairs", "pairs"),
                ("moe_experts_hit", "experts_hit"),
                ("moe_expert_slots", None),
                ("moe_load_max", None))


def write_chunk_rows(pool, rows, table, pos, true_len, scratch):
    """One slot's chunk of cache rows into the blocks its table names:
    row i of ``rows`` [C, w] becomes row ``pos + i`` of the slot for
    ``i < true_len``, by one in-place update a block the chunk can
    touch — ``ceil((C + bs - 1) / bs)`` of them wherever ``pos`` lies
    in its block (17 for 256 rows in blocks of 16).  Each block is read
    as it lies, the rows that land in it are laid over it and it is
    written back, so nothing else changes: rows below ``pos`` and at or
    past ``pos + true_len`` (the pad lanes land nowhere), and the
    pool's lane padding (columns ``>= w``).  A block no row lands in —
    past the last real row, where the table may hold another chunk's
    worth of the slot's reservation, ``scratch``, or end — is taken to
    be ``scratch`` and gets back what it held; a table index past the
    table's end is never formed.  The blocks that are written are the
    slot's own: a prefix adopted from the cache lies wholly below
    ``pos``.

    Why not ``pool.at[blocks, offs, :w].set(rows)``, which says the
    same: the v5e compiler turns that scatter into a loop of one trip
    a row, seven small operations each with the pool in the carry,
    4.9 us a trip — 9.87 ms for the nine layers of a 256-row chunk,
    against 0.19 ms for these updates (0.13 ms for ``C // bs`` whole
    blocks written without being read, which holds only for a chunk
    that starts at a block's edge), and 31.4 -> 20.1 ms a chunk
    program in the serving cell (chip runs, PR 32;
    ``decode_slots_paged``'s 32-trip twin, PR 30).

    pool [NB, bs, W]; rows [C, w], w <= W (or, a row of several axes,
    pool [NB, bs, *W] and rows [C, *W]); table int32 [L // bs]; pos,
    true_len, scratch traced scalars.  Returns the pool."""
    import jax
    import jax.numpy as jnp
    (C, *w), bs = rows.shape, pool.shape[1]
    zeros = (0,) * len(w)
    n = (C + 2 * bs - 2) // bs
    first, off = pos // bs, pos % bs
    # the chunk as whole blocks: its rows start ``off`` rows into the
    # first one
    new = jax.lax.dynamic_update_slice(
        jnp.zeros((n * bs, *w), pool.dtype), rows.astype(pool.dtype),
        (off, *zeros)).reshape(n, bs, *w)
    at = jnp.arange(n * bs) - off
    lands = ((at >= 0) & (at < true_len)).reshape(n, bs, *(1 for _ in w))
    blocks = jnp.where(
        jnp.any(lands, axis=tuple(range(1, lands.ndim))),
        table[jnp.minimum(first + jnp.arange(n), table.shape[0] - 1)],
        scratch)
    for j in range(n):
        held = jax.lax.dynamic_slice(pool, (blocks[j], 0, *zeros),
                                     (1, bs, *w))
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(lands[j], new[j], held[0])[None],
            (blocks[j], 0, *zeros))
    return pool


def rms_norm(x, weight, eps):
    """``weight * x / sqrt(mean(x^2) + eps)``, the mean in float32."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + eps)
    return xf.astype(x.dtype) * weight.astype(x.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention scale ``0.1 mscale ln(factor) + 1`` (1 at a
    factor of at most 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim // 2`` rotary frequencies under ``rope_scaling`` of
    type ``yarn`` (the published ``DeepseekV3YarnRotaryEmbedding``):
    ``f_i = theta^(-2i/dim)``; a frequency that turns more than
    ``beta_fast`` times over the original context is kept, one that
    turns less than ``beta_slow`` times is divided by ``factor``, and
    between them a linear ramp over the index:
    ``corr(b) = dim ln(L0 / (2 pi b)) / (2 ln theta)``, ``low =
    floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, both
    clipped to ``[0, dim - 1]``, ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``, ``inv_freq_i = f_i (1 - ramp_i) + f_i / factor
    ramp_i``.  Returns float32 numpy [dim // 2]."""
    import numpy as np
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"rope_scaling {scaling!r}: only type 'yarn' "
                         "is written")
    L0 = scaling["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(L0 / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(scaling.get("beta_slow", 1))), dim - 1)
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (high - low if high != low else 0.001), 0.0, 1.0)
    return (freq * (1.0 - ramp)
            + freq / scaling["factor"] * ramp).astype(np.float32)


def rope(x, pos, theta, scaling=None):
    """Rotate ``x [..., d]`` to positions ``pos`` (broadcastable to
    ``x.shape[:-1]``): dimension i pairs with i + d/2, angle
    ``pos * theta^(-2i/d)`` (``scaling``: ``pos * yarn_inv_freq_i``,
    cos and sin times ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``), in float32."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    if scaling is None:
        inv, m = jnp.exp(-math.log(theta)
                         * jnp.arange(half, dtype=jnp.float32) / half), 1.0
    else:
        inv = jnp.asarray(yarn_inv_freq(2 * half, theta, scaling))
        m = (yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
             / yarn_mscale(scaling["factor"],
                           scaling.get("mscale_all_dim", 0)))
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _lin(layer, x):
    """An ``nn.Linear`` (or its weight-only int8 form) on an array."""
    return layer(Tensor(x))._data


class RMSNorm(nn.Layer):
    def __init__(self, size, eps):
        super().__init__()
        self.eps = float(eps)
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(1.0))

    def forward(self, x):
        return rms_norm(x, self.weight._data, self.eps)


class GatedMLP(nn.Layer):
    """SwiGLU: ``(silu(x W1) * (x W3)) W2``; W1 | W3 side by side in
    ``gate_up_proj``."""

    def __init__(self, hidden, width):
        super().__init__()
        self.width = width
        self.gate_up_proj = nn.Linear(hidden, 2 * width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden, bias_attr=False)

    def forward(self, x):
        import jax
        a = _lin(self.gate_up_proj, x)
        act = jax.nn.silu(a[..., :self.width]) * a[..., self.width:]
        return _lin(self.down_proj, act)


class RoutedFFN(nn.Layer):
    """Routed experts: sigmoid-scored with a selection bias and a
    shared expert (module docstring; ``n_shared`` 0: without one, and
    ``norm_eps`` what the family adds to the selected scores' sum,
    ``models/lfm2_moe.py``), or — ``gate="softmax"``, ``n_shared`` 0 —
    the softmax top-k layer that has neither (``models/sdar_moe.py``).
    ``forward`` returns ``(y, stats)``, ``stats`` int32 [3]: pairs
    computed, experts hit, the busiest expert's pairs.

    ``held = (first, count)`` is one chip's share of a layer that
    several chips hold by expert parallelism (``models/afmoe.py``):
    the router keeps its ``num_experts`` columns, the stacks hold the
    ``count`` experts from ``first``, the pairs that fall on them are
    computed here and the others add nothing (their chips add them),
    the shared expert is whole; ``stats`` gets a fourth number, the
    live pairs that fell elsewhere.  None: every expert is held."""

    def __init__(self, hidden, width, num_experts, top_k, n_shared,
                 scale, normalize=True, gate="sigmoid", held=None,
                 norm_eps=1e-20):
        super().__init__()
        if gate not in ("sigmoid", "softmax"):
            raise ValueError(f"gate {gate!r}: 'sigmoid' or 'softmax'")
        if held is not None and not (
                0 <= held[0] and 0 < held[1]
                and held[0] + held[1] <= num_experts):
            raise ValueError(f"held {held!r}: (first, count) inside "
                             f"the router's {num_experts} experts")
        self.held = None if held is None else (int(held[0]), int(held[1]))
        stacked = num_experts if held is None else self.held[1]
        self.num_experts, self.top_k = num_experts, top_k
        self.scale, self.normalize = float(scale), bool(normalize)
        self.gate, self.norm_eps = gate, float(norm_eps)
        init = I.Normal(0.0, 0.02)
        self.gate_weight = self.create_parameter(
            [hidden, num_experts], default_initializer=init)
        if gate == "sigmoid":
            self.gate_bias = self.create_parameter(
                [num_experts], is_bias=True)
        self.experts_in = self.create_parameter(
            [stacked, hidden, 2 * width], default_initializer=init)
        self.experts_out = self.create_parameter(
            [stacked, width, hidden], default_initializer=init)
        self.shared = (GatedMLP(hidden, n_shared * width) if n_shared
                       else None)

    @_scoped("moe.route")
    def route(self, x):
        import jax.numpy as jnp
        from ..distributed import moe
        logits = jnp.dot(x.astype(jnp.float32),
                         self.gate_weight._data.astype(jnp.float32),
                         precision="highest")
        if self.gate == "softmax":
            return moe.softmax_topk_routing(logits, self.top_k,
                                            self.normalize)
        return moe.sigmoid_topk_routing(logits, self.gate_bias._data,
                                        self.top_k, self.scale,
                                        self.normalize, self.norm_eps)

    @_scoped("moe.experts")
    def experts(self, x, choice, weights, live):
        from ..distributed.moe import dropless_experts
        return dropless_experts(x, choice, weights, live,
                                self.experts_in._data,
                                self.experts_out._data,
                                None if self.held is None else self.held[0])

    @_scoped("moe.held")
    def elsewhere(self, choice, live):
        """Live pairs whose expert another chip holds, int32 []."""
        import jax.numpy as jnp
        first, count = self.held
        away = (choice < first) | (choice >= first + count)
        return jnp.sum(away & live[:, None], dtype=jnp.int32)

    @_scoped("moe.shared")
    def shared_expert(self, x):
        return self.shared(x)

    def forward(self, x, live):
        """x [T, D]; live [T] bool, the rows that are tokens."""
        choice, weights = self.route(x)
        y, stats = self.experts(x, choice, weights, live)
        if self.held is not None:
            import jax.numpy as jnp
            stats = jnp.append(stats, self.elsewhere(choice, live))
        y = y.astype(x.dtype)
        if self.shared is not None:
            y = y + self.shared_expert(x)
        return y, stats


class MLAttention(nn.Layer):
    def __init__(self, hidden, num_heads, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, kv_lora_rank, rope_theta,
                 eps, q_lora_rank=None, rope_scaling=None):
        super().__init__()
        self.num_heads = num_heads
        self.d_n, self.d_r = qk_nope_head_dim, qk_rope_head_dim
        self.d_v, self.rank = v_head_dim, kv_lora_rank
        self.theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        # the scores' scale: 1 / sqrt(d_n + d_r), times YaRN's
        # attention scale squared where ``mscale_all_dim`` is given
        self.scale = 1.0 / math.sqrt(self.d_n + self.d_r)
        if self.rope_scaling and self.rope_scaling.get("mscale_all_dim"):
            self.scale *= yarn_mscale(
                self.rope_scaling["factor"],
                self.rope_scaling["mscale_all_dim"]) ** 2
        self.row = kv_lora_rank + qk_rope_head_dim
        q_width = num_heads * (self.d_n + self.d_r)
        if q_lora_rank is None:
            self.q_proj = nn.Linear(hidden, q_width, bias_attr=False)
        else:
            self.q_a_proj = nn.Linear(hidden, q_lora_rank,
                                      bias_attr=False)
            self.q_a_norm = RMSNorm(q_lora_rank, eps)
            self.q_b_proj = nn.Linear(q_lora_rank, q_width,
                                      bias_attr=False)
        self.q_lora_rank = q_lora_rank
        self.kv_a_proj = nn.Linear(hidden, self.row, bias_attr=False)
        self.kv_norm = RMSNorm(kv_lora_rank, eps)
        # [r, H * (d_n + d_v)]: a raw parameter, because the absorbed
        # form contracts its K and V halves separately
        self.kv_b = self.create_parameter(
            [kv_lora_rank, num_heads * (self.d_n + self.d_v)],
            default_initializer=I.Normal(0.0, 0.02))
        self.o_proj = nn.Linear(num_heads * self.d_v, hidden,
                                bias_attr=False)

    def _w_kvb(self):
        return self.kv_b._data.reshape(self.rank, self.num_heads,
                                       self.d_n + self.d_v)

    def project(self, h, pos):
        """h [B, S, D], pos [B, S] -> q_n [B, S, H, d_n], q_r
        [B, S, H, d_r] (rotated) and the row to cache [B, S, r + d_r]:
        the normed latent and the rotated shared key."""
        import jax.numpy as jnp
        B, S = h.shape[0], h.shape[1]
        if self.q_lora_rank is None:
            q = _lin(self.q_proj, h)
        else:
            q = _lin(self.q_b_proj,
                     self.q_a_norm(_lin(self.q_a_proj, h)))
        q = q.reshape(B, S, self.num_heads, self.d_n + self.d_r)
        q_n = q[..., :self.d_n]
        q_r = rope(q[..., self.d_n:], pos[:, :, None], self.theta,
                   self.rope_scaling)
        ckr = _lin(self.kv_a_proj, h)
        c = self.kv_norm(ckr[..., :self.rank])
        k_r = rope(ckr[..., self.rank:], pos, self.theta,
                   self.rope_scaling)
        return q_n, q_r, jnp.concatenate([c, k_r], axis=-1)

    def absorbed_wins(self, window):
        """Which form a window of ``window`` queries attends to its
        cached prefix in.  For every (query, cached position) pair
        absorbed costs ``2 H (r + d_r + r)`` operations (scores over
        the row, context over the latent); expanded costs
        ``2 H (d_n + d_r + d_v)`` and, once for each position a trip
        fetches, the expansion ``2 r H (d_n + d_v)``.  Absorbed wins
        while ``window * (absorbed - expanded) < expansion``, that is
        while ``window < r (d_n + d_v) / (2 r - d_n - d_v)``: the
        heads cancel, so the sizes that decide are the head's and the
        latent's alone.  At d_n 128, d_r 64, d_v 128, r 512 (16 heads
        of a 2,048 model: 34.8 against 10.2 kFLOP a pair and 4.19
        MFLOP a position; 32 heads of a 3,584 one: twice each) up to
        170 queries — a decode row (1) is absorbed, a 256-token chunk
        expanded, at 64 trips over 16 k cached rows as at 32 over
        8 k."""
        H = self.num_heads
        absorbed = 2 * H * (2 * self.rank + self.d_r)
        expanded = 2 * H * (self.d_n + self.d_r + self.d_v)
        expansion = 2 * self.rank * H * (self.d_n + self.d_v)
        return window * (absorbed - expanded) < expansion

    def attend(self, q_n, q_r, pool, tables, pos, absorbed=None):
        """Causal attention of a window of queries over each slot's
        cached rows, read through its block table ``walk_chunk`` rows
        at a time in the pool's dtype with float32 accumulation, one
        pass with a running maximum and denominator.

        Several slots (the decode program) are walked as a WORK LIST
        of (slot, chunk) items, ``walk_plan``: each slot is read to its
        OWN window's end, ``walk_group`` items a trip whatever slots
        they belong to, ``ceil(items / group)`` trips read on the
        device.  An item's masked scores give a partial (maximum,
        denominator, context); a trip folds its items' partials into
        their slots' running state, so the softmax is over exactly the
        visible rows (masked rows, the last trip's padding items and
        slots without an item contribute exactly 0).  A slot at
        position 0 has no item and returns zeros: that is where the
        engine parks a lane, and a lane that decodes stands at 1 or
        later.  One slot (the chunk program) walks its own
        ``ceil((pos + S) / chunk)`` chunks in turn — ``GPTAttention
        ._slot_attn``'s walk over latent rows.  A table of at most one
        chunk is read whole, without a loop.

        q_n [B, S, H, d_n], q_r [B, S, H, d_r]; pool [NB, bs, W], W >=
        r + d_r (the row, then the spec's lane padding, never written);
        tables int32 [B, L // bs]; pos int32 [B] (window start).  The
        query at offset s of slot b sees rows <= pos[b] + s.  Returns
        [B, S, H * d_v]."""
        import jax
        import jax.numpy as jnp
        B, S, H = q_n.shape[0], q_n.shape[1], q_n.shape[2]
        bs, r = pool.shape[1], self.rank
        if absorbed is None:
            absorbed = self.absorbed_wins(S)
        table_rows = tables.shape[1] * bs
        chunk = walk_chunk(table_rows, bs)
        w_kvb = self._w_kvb()
        scale = self.scale
        q_end = pos[:, None] + jnp.arange(S)[None, :]          # [B, S]
        highest = jax.lax.Precision.HIGHEST
        if absorbed:
            with jax.named_scope("mla.absorbed"):
                q_lat = jnp.einsum("bshn,rhn->bshr", q_n,
                                   w_kvb[..., :self.d_n],
                                   preferred_element_type=jnp.float32)
                # one query over the whole cached row [c; k_r] (and
                # zeros over the pool's lane padding)
                qq = jnp.concatenate(
                    [q_lat, q_r.astype(jnp.float32),
                     jnp.zeros(q_lat.shape[:-1]
                               + (pool.shape[2] - self.row,))],
                    axis=-1).astype(pool.dtype)
            queries, width = (qq,), r
        else:
            queries, width = (q_n, q_r), self.d_v

        def partial(qs, rows, visible):
            """Scores of queries ``qs`` ([b, S, H, .] each) over the
            blocks ``rows`` [b, chunk // bs, bs, W], masked by
            ``visible`` [b, S, chunk], and ``context(p)``: the float32
            context [b, S, H, width] of weights p [b, H, S, chunk]."""
            rows = rows.reshape(rows.shape[0], -1, pool.shape[2])
            if absorbed:
                sc = jnp.einsum("bshr,bkr->bhsk", qs[0], rows,
                                preferred_element_type=jnp.float32)
                values, form = rows[..., :r], "bhsk,bkr->bshr"
            else:
                kv = jnp.einsum("bkr,rhe->bkhe", rows[..., :r], w_kvb,
                                preferred_element_type=jnp.float32
                                ).astype(pool.dtype)
                sc = (jnp.einsum("bshn,bkhn->bhsk", qs[0],
                                 kv[..., :self.d_n],
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshr,bkr->bhsk", qs[1],
                                   rows[..., r:self.row],
                                   preferred_element_type=jnp.float32))
                values, form = kv[..., self.d_n:], "bhsk,bkhv->bshv"
            return (jnp.where(visible[:, None, :, :], sc * scale, -1e30),
                    lambda p: jnp.einsum(form, p,
                                         values.astype(jnp.float32),
                                         precision=highest))

        def per_ctx(a):                  # [b, H, S] -> [b, S, H, 1]
            return jnp.transpose(a, (0, 2, 1))[..., None]

        def trip(c, carry):
            top, den, acc = carry
            start = jnp.minimum(c * chunk, table_rows - chunk)
            at = start + jnp.arange(chunk)
            # rows below c * chunk were scored by an earlier trip (the
            # last trip of a table that is no whole number of chunks
            # starts early)
            sc, context = partial(
                queries, pool[jax.lax.dynamic_slice_in_dim(
                    tables, start // bs, chunk // bs, axis=1)],
                (at[None, None, :] <= q_end[:, :, None])
                & (at >= c * chunk)[None, None, :])
            new_top = jnp.maximum(top, jnp.max(sc, axis=-1))
            keep = jnp.exp(top - new_top)
            p = jnp.exp(sc - new_top[..., None])
            return (new_top, den * keep + jnp.sum(p, axis=-1),
                    acc * per_ctx(keep) + context(p))

        def walk_items(init):
            group = walk_group(B)
            slot_of, chunk_of, valid, n_trips = walk_plan(
                pos, S, table_rows, chunk, group)
            # What a trip needs of its items and no carry enters is
            # made for the whole list before the loop and cut a trip:
            # it depends on ``pos`` and the tables alone, so a
            # program's layers share one copy.  (A table that is no
            # whole number of items is filled up with block 0, whose
            # rows lie past every window.)
            n_chunks = -(-table_rows // chunk)
            whole = jnp.pad(tables, ((0, 0), (
                0, n_chunks * chunk // bs - tables.shape[1])))
            cols = whole.reshape(B * n_chunks, chunk // bs)[
                slot_of * n_chunks + chunk_of]           # [N, K // bs]
            at = (chunk_of * chunk)[:, None] + jnp.arange(chunk)[None, :]
            sees = ((at[:, None, :] <= q_end[slot_of][:, :, None])
                    & ((at < table_rows)
                       & valid[:, None])[:, None, :])      # [N, S, K]
            whose = ((slot_of[None, :] == jnp.arange(B)[:, None])
                     & valid[None, :])[..., None, None]  # [B, N, 1, 1]

            def item_trip(t, carry):
                top, den, acc = carry

                def cut(a, axis=0):
                    return jax.lax.dynamic_slice_in_dim(
                        a, t * group, group, axis)
                sl = cut(slot_of)
                sc, context = partial([q[sl] for q in queries],
                                      pool[cut(cols)], cut(sees))
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
                            "bghs,gshv->bshv", w, context(p),
                            precision=highest))

            _, den, acc = jax.lax.fori_loop(0, n_trips, item_trip, init)
            # a slot without an item: 0 / 1
            return jnp.where(den > 0, den, 1.0), acc

        init = (jnp.full((B, H, S), -1e30, jnp.float32),
                jnp.zeros((B, H, S), jnp.float32),
                jnp.zeros((B, S, H, width), jnp.float32))
        trips = -(-table_rows // chunk)
        with jax.named_scope("mla.absorbed" if absorbed
                             else "mla.expanded"):
            if trips == 1:
                _, den, acc = trip(0, init)
            elif B > 1:
                den, acc = walk_items(init)
            else:
                live = jnp.clip((jnp.max(pos) + S + chunk - 1) // chunk,
                                1, trips)
                _, den, acc = jax.lax.fori_loop(0, live, trip, init)
            ctx = acc / per_ctx(den)
            if absorbed:
                ctx = jnp.einsum("bshr,rhv->bshv",
                                 ctx.astype(pool.dtype),
                                 w_kvb[..., self.d_n:],
                                 preferred_element_type=jnp.float32)
        return ctx.astype(q_n.dtype).reshape(B, S, H * self.d_v)

    @_scoped("attention")
    def decode_slots_paged(self, h, pool, tables, pos):
        """One token a slot: write its row into the block that holds
        ``pos[b]``, attend in the absorbed form.  h [B, 1, D]; pool
        [NB, bs, r + d_r]; tables [B, L // bs]; pos [B].  Returns
        (out [B, 1, D], pool)."""
        import jax
        import jax.numpy as jnp
        q_n, q_r, row = self.project(h, pos[:, None])
        bs = pool.shape[1]
        # Written into the pool as it lies ([block, row in block]:
        # through a flattened view the v5e compiler copies the whole
        # pool every step, 264 MB a layer; chip run, PR 28), one
        # in-place update a slot.  The scatter that says the same
        # (``pool.at[blocks, offs, :row].set(new)``) is compiled to a
        # loop of one trip a row, small operations with the pool in
        # the carry: 32 trips here, 1.25 ms a step over nine layers
        # against 0.29 ms for these updates (chip run, PR 30); for the
        # chunk program's 256 trips see ``write_chunk_rows``.
        blocks = tables[jnp.arange(h.shape[0]), pos // bs]
        offs, new = pos % bs, row.astype(pool.dtype)
        for b in range(h.shape[0]):
            pool = jax.lax.dynamic_update_slice(
                pool, new[b:b + 1], (blocks[b], offs[b], 0))
        out = self.attend(q_n, q_r, pool, tables, pos, absorbed=True)
        return _lin(self.o_proj, out), pool

    @_scoped("attention")
    def prefill_chunk_paged(self, h, pool, table, pos, true_len,
                            scratch=0):
        """C prompt tokens of ONE slot at positions ``pos..pos+C-1``:
        the rows of the first ``true_len`` go into the slot's blocks
        (``write_chunk_rows``: the pool updated as it lies, a block at
        a time, as ``decode_slots_paged`` updates it a row at a time,
        and for the same reason — the scatter that says the same is
        compiled to a loop of one trip a row; the pad lanes are
        written nowhere), then the chunk attends causally over the
        slot's rows, the adopted prefix and itself included, in the
        form ``absorbed_wins`` picks for C.  ``pos`` may lie anywhere
        in its block (the engine starts a chunk where the last one
        ended, at a block's edge when C is a whole number of blocks).
        h [1, C, D]; table [L // bs]; pos / true_len / scratch traced
        scalars.  Returns (out [1, C, D], pool)."""
        import jax.numpy as jnp
        q_n, q_r, row = self.project(
            h, (pos + jnp.arange(h.shape[1]))[None, :])
        pool = write_chunk_rows(pool, row[0], table, pos, true_len,
                                scratch)
        out = self.attend(q_n, q_r, pool, table[None, :],
                          jnp.reshape(pos, (1,)))
        return _lin(self.o_proj, out), pool

    def forward(self, h, absorbed=False):
        """Uncached causal attention over a whole sequence, h
        [B, S, D], in either form (the CPU tests hold one against the
        other and both against the reference)."""
        import jax
        import jax.numpy as jnp
        B, S = h.shape[0], h.shape[1]
        pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        q_n, q_r, row = self.project(h, pos)
        c, k_r = row[..., :self.rank], row[..., self.rank:]
        w_kvb = self._w_kvb()
        if absorbed:
            q_lat = jnp.einsum("bshn,rhn->bshr", q_n,
                               w_kvb[..., :self.d_n])
            sc = jnp.einsum("bshr,bkr->bhsk", q_lat, c)
        else:
            kv = jnp.einsum("bkr,rhe->bkhe", c, w_kvb)
            sc = jnp.einsum("bshn,bkhn->bhsk", q_n, kv[..., :self.d_n])
        sc = (sc + jnp.einsum("bshr,bkr->bhsk", q_r, k_r)).astype(
            jnp.float32) * self.scale
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -1e30),
                           axis=-1).astype(h.dtype)
        if absorbed:
            ctx = jnp.einsum("bshr,rhv->bshv",
                             jnp.einsum("bhsk,bkr->bshr", p, c),
                             w_kvb[..., self.d_n:])
        else:
            ctx = jnp.einsum("bhsk,bkhv->bshv", p, kv[..., self.d_n:])
        return _lin(self.o_proj,
                    ctx.reshape(B, S, self.num_heads * self.d_v))


def sinkhorn(cells, n, iters, eps):
    """``cells``: the n x n numbers of ``exp(Hres~)`` row-major, each
    an array over the positions -> ``M[i][j]``: ``iters`` times every
    column and then every row divided by its sum + ``eps``, so rows sum
    to 1 and columns nearly.  A sum is n - 1 additions of arrays: the
    whole chain is element-wise over the positions."""
    m = [[cells[i * n + j] for j in range(n)] for i in range(n)]
    for _ in range(iters):
        inv = [1.0 / (sum(m[i][j] for i in range(n)) + eps)
               for j in range(n)]
        m = [[m[i][j] * inv[j] for j in range(n)] for i in range(n)]
        inv = [1.0 / (sum(m[i]) + eps) for i in range(n)]
        m = [[m[i][j] * inv[i] for j in range(n)] for i in range(n)]
    return m


def _mhc_maps_rows(rows, n, iters, eps, clamp):
    """The three mappings from their raw values, ``rows``: n (n + 2)
    arrays over the positions, pre (n), post (n), res (n x n) -> as
    many arrays: ``sigmoid``, ``2 sigmoid``, and the Sinkhorn
    projection of ``exp(clip(.))``."""
    import jax
    import jax.numpy as jnp
    pre = [jax.nn.sigmoid(r) for r in rows[:n]]
    post = [2.0 * jax.nn.sigmoid(r) for r in rows[n:2 * n]]
    res = sinkhorn([jnp.exp(jnp.clip(r, *clamp)) for r in rows[2 * n:]],
                   n, iters, eps)
    return pre + post + [c for row in res for c in row]


def mhc_maps(raw, n, iters, eps, clamp):
    """``raw [n (n + 2), T]`` (float32: ``Hpre~``, ``Hpost~``,
    ``Hres~`` of T positions, a position a lane) -> the mappings
    ``[n (n + 2), T]``.

    ONE Pallas kernel, named ``mhc_maps`` in a device trace, holds the
    ``2 iters`` normalisations (1.1 us a call on the v5e); off the TPU
    the same kernel runs interpreted, as every Pallas kernel of this
    repo does on the ``cpu`` platform.  Measured with a sub-layer's
    norm, projection, read and mix around it, 14 chained (chip run, PR
    37, ``_chip/xing_bench.py``): 14.8 us a sub-layer at 32 positions
    and 74.8 at 256, against 17.1 / 77.5 written in ``jax.numpy`` over
    separate arrays (the v5e compiler makes ~30 fusions a sub-layer of
    them, under names it shares with other operations), 16.9 / 77.2 as
    reductions over ``[..., n, n]`` and 16.7 / 76.0 as a device loop,
    which puts a ``while`` beside the walk's; read and mix alone are
    ~15.  The forms differ by a seventh of 1% of a step; the kernel is
    kept because it gives the trace one name for the mappings."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(raw_ref, out_ref):
        raw = raw_ref[...]
        out_ref[...] = jnp.concatenate(_mhc_maps_rows(
            [raw[k:k + 1, :] for k in range(raw.shape[0])], n, iters,
            eps, clamp), axis=0)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(raw.shape, jnp.float32),
        interpret=jax.default_backend() != "tpu", name="mhc_maps")(raw)


class HyperConnection(nn.Layer):
    """The three mappings of ONE sub-layer's residual over ``n``
    streams (the module docstring; manifold-constrained
    hyper-connections).  The streams are ``X [n, B, S, d]``: a stream
    is a whole ``[B, S, d]`` slab, so no small axis sits before the
    minor one.  Leaves: the stream norm's gain ``[n d]``; ``phi``
    ``[n (n + 2), n d]``, rows in the order pre (n), post (n), res
    (n x n, row-major), stored with the long axis minor (whole tiles);
    ``alpha`` ``[3]`` (pre, post, res); ``beta`` ``[n (n + 2)]``."""

    def __init__(self, hidden, n, iters, eps, norm_eps, clamp):
        super().__init__()
        self.n, self.iters, self.eps = int(n), int(iters), float(eps)
        self.clamp = (float(clamp[0]), float(clamp[1]))
        self.norm = RMSNorm(n * hidden, norm_eps)
        self.phi = self.create_parameter(
            [n * (n + 2), n * hidden],
            default_initializer=I.Normal(0.0, 0.02))
        self.alpha = self.create_parameter(
            [3], default_initializer=I.Constant(1.0))
        self.beta = self.create_parameter([n * (n + 2)], is_bias=True)

    @_scoped("mhc.maps")
    def maps(self, X):
        """X [n, B, S, d] -> (h_pre n x [B, S, 1], h_post n x
        [B, S, 1], h_res n x n x [B, S, 1]), float32."""
        import jax.numpy as jnp
        n = self.n
        xf = self.norm(jnp.concatenate(
            [X[i] for i in range(n)], axis=-1).astype(jnp.float32))
        raw = jnp.einsum("mk,bsk->mbs",
                         self.phi._data.astype(jnp.float32), xf,
                         precision="highest")
        a = jnp.repeat(self.alpha._data.astype(jnp.float32),
                       jnp.array([n, n, n * n]),
                       total_repeat_length=n * (n + 2))
        raw = (raw * a[:, None, None]
               + self.beta._data.astype(jnp.float32)[:, None, None])
        h = mhc_maps(raw.reshape(raw.shape[0], -1), n, self.iters,
                     self.eps, self.clamp).reshape(raw.shape)[..., None]
        return (list(h[:n]), list(h[n:2 * n]),
                [list(h[2 * n + i * n:2 * n + (i + 1) * n])
                 for i in range(n)])

    @_scoped("mhc.mix")
    def read(self, X, h_pre):
        """The sub-layer's input ``u = H_pre X`` [B, S, d]."""
        import jax.numpy as jnp
        u = sum(h_pre[i] * X[i].astype(jnp.float32)
                for i in range(self.n))
        return u.astype(X.dtype)

    @_scoped("mhc.mix")
    def mix(self, X, y, h_post, h_res):
        """``H_res X + H_post^T y`` -> the streams [n, B, S, d]."""
        import jax.numpy as jnp
        n = self.n
        xs = [X[j].astype(jnp.float32) for j in range(n)]
        yf = y.astype(jnp.float32)
        return jnp.stack([
            (sum(h_res[i][j] * xs[j] for j in range(n))
             + h_post[i] * yf).astype(X.dtype)
            for i in range(n)])


class MLAMoEBlock(nn.Layer):
    """``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``; ``ffn`` is a
    ``GatedMLP`` (dense layer) or a ``RoutedFFN``.  With ``hc_mult`` n
    > 1 the state is n streams ``[n, B, S, d]`` and each ``+=`` is a
    ``HyperConnection`` of its own around the sub-layer."""

    def __init__(self, cfg, routed):
        super().__init__()
        d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.input_norm = RMSNorm(d, eps)
        self.attn = MLAttention(
            d, cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["kv_lora_rank"], cfg["rope_theta"], eps,
            cfg.get("q_lora_rank"), cfg.get("rope_scaling"))
        self.post_norm = RMSNorm(d, eps)
        self.attn_hc = self.ffn_hc = None
        if cfg.get("hc_mult", 1) > 1:
            self.attn_hc, self.ffn_hc = (HyperConnection(
                d, cfg["hc_mult"], cfg["hc_sinkhorn_iters"],
                cfg["hc_eps"], eps,
                (cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
                for _ in range(2))
        self.routed = routed
        self.ffn = (RoutedFFN(d, cfg["moe_intermediate_size"],
                              cfg["n_routed_experts"],
                              cfg["num_experts_per_tok"],
                              cfg["n_shared_experts"],
                              cfg["routed_scaling_factor"],
                              cfg.get("norm_topk_prob", True))
                    if routed else GatedMLP(d, cfg["intermediate_size"]))

    @_scoped("mlp")
    def feed_forward(self, x, live):
        """x [B, S, D], live [B, S] -> (FFN(RMSNorm(x)), stats or
        None)."""
        h = self.post_norm(x)
        if not self.routed:
            return self.ffn(h), None
        y, stats = self.ffn(h.reshape(-1, h.shape[-1]),
                            live.reshape(-1))
        return y.reshape(x.shape), stats

    @staticmethod
    def _residual(hc, x, sublayer):
        """One sub-layer on the residual: ``x + F(x)``, or through the
        streams' mappings.  ``sublayer(u) -> (F(u), aux)``; returns
        (the new state, aux)."""
        if hc is None:
            y, aux = sublayer(x)
            return x + y, aux
        h_pre, h_post, h_res = hc.maps(x)
        y, aux = sublayer(hc.read(x, h_pre))
        return hc.mix(x, y, h_post, h_res), aux

    def decode_slots_paged(self, x, pool, tables, pos, live):
        x, pool = self._residual(
            self.attn_hc, x, lambda u: self.attn.decode_slots_paged(
                self.input_norm(u), pool, tables, pos))
        x, stats = self._residual(
            self.ffn_hc, x, lambda u: self.feed_forward(u, live[:, None]))
        return x, pool, stats

    def prefill_chunk_paged(self, x, pool, table, pos, true_len,
                            scratch, live):
        x, pool = self._residual(
            self.attn_hc, x, lambda u: self.attn.prefill_chunk_paged(
                self.input_norm(u), pool, table, pos, true_len, scratch))
        x, stats = self._residual(
            self.ffn_hc, x, lambda u: self.feed_forward(u, live[None, :]))
        return x, pool, stats

    def forward(self, x, absorbed=False):
        import jax.numpy as jnp
        x, _ = self._residual(
            self.attn_hc, x,
            lambda u: (self.attn(self.input_norm(u), absorbed), None))
        live = jnp.ones(x.shape[-3:-1], bool)
        return self._residual(
            self.ffn_hc, x, lambda u: self.feed_forward(u, live))[0]


class MLAMoEModel(ServedModel, nn.Layer):
    """Decoder-only LM of the module's docstring.  ``config`` holds the
    published keys (``hidden_size``, ``num_attention_heads``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``kv_lora_rank``, ``intermediate_size``, ``moe_intermediate_size``,
    ``n_routed_experts``, ``num_experts_per_tok``, ``n_shared_experts``,
    ``routed_scaling_factor``, ``first_k_dense_replace``,
    ``num_hidden_layers``, ``vocab_size``, ``max_position_embeddings``,
    ``rms_norm_eps``, ``rope_theta``; and where the model has them
    ``q_lora_rank``, ``rope_scaling`` of type ``yarn``, ``hc_mult`` with
    ``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` /
    ``_max``); build it under ``nn.LazyGuard()`` to declare the
    parameters without values."""

    def __init__(self, config):
        super().__init__()
        cfg = dict(config)
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("grouped top-k routing is not written: "
                             "n_group and topk_group have to be 1")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError("the gate scores with a sigmoid")
        self.config = cfg
        self.streams = int(cfg.get("hc_mult", 1))
        d = cfg["hidden_size"]
        self.embed = self.create_parameter(
            [cfg["vocab_size"], d],
            default_initializer=I.Normal(0.0, 0.02))
        self.blocks = nn.LayerList([
            MLAMoEBlock(cfg, routed=i >= cfg["first_k_dense_replace"])
            for i in range(cfg["num_hidden_layers"])])
        self.norm = RMSNorm(d, cfg["rms_norm_eps"])
        self.lm_head = nn.Linear(d, cfg["vocab_size"], bias_attr=False)

    @property
    def routed_layers(self):
        return sum(1 for b in self.blocks if b.routed)

    def _counter_vector(self, stats):
        """The int32 [4] the step programs return: pairs, experts hit,
        expert slots (routed layers x experts, once a run), the busiest
        expert's pairs — each summed over the routed layers."""
        import jax.numpy as jnp
        slots = self.routed_layers * self.config["n_routed_experts"]
        if not stats:
            return jnp.zeros((4,), jnp.int32)
        s = sum(stats)
        return jnp.stack([s[0], s[1], jnp.int32(slots), s[2]])

    def _widen(self, x):
        """The residual's first state from the embedding [B, S, d]:
        itself, or with n streams every stream the embedding
        [n, B, S, d]."""
        import jax.numpy as jnp
        if self.streams > 1:
            x = jnp.broadcast_to(x[None], (self.streams,) + x.shape)
        return x

    @_scoped("lm_head")
    def _head(self, x):
        """The final norm and the head over the residual's last state
        (n streams: over their sum)."""
        import jax.numpy as jnp
        if self.streams > 1:
            x = jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)
        return _lin(self.lm_head, self.norm(x)).astype(jnp.float32)

    def forward(self, input_ids, absorbed=False):
        """Uncached logits [B, S, V] (float32)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else input_ids
        x = self._widen(self.embed._data[ids])
        for blk in self.blocks:
            x = blk(x, absorbed)
        return Tensor(self._head(x))

    # -- step programs -------------------------------------------------
    def _fused_decode_tick_slots(self, tok, pools, tables, pos, temp,
                                 top_k, top_p, seed_lo, seed_hi, ctr,
                                 eos, rem):
        """``GPTModel._fused_decode_tick_slots`` over latent pools:
        one token a slot through every block, sampling and the stop
        condition on the device, the same outputs, and the counter
        vector last.  Only lanes with budget left (``rem > 0``) are
        routed to experts: a parked lane's row runs through the shared
        expert, as it does for GPT, but walks no cached row (it stands
        at position 0: ``MLAttention.attend``) and hits no expert's
        weights."""
        import jax.numpy as jnp
        live = rem > 0
        x = self._widen(self.embed._data[tok[:, 0]][:, None, :])
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, pool, st = blk.decode_slots_paged(x, pools[j], tables,
                                                 pos, live)
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
                new_pools, [], self._counter_vector(stats))

    def _chunk_prefill_tick_paged(self, toks, pools, table, pos,
                                  true_len, scratch):
        """C prompt tokens of one slot through every block; the head
        runs on the last REAL position only.  Returns (last logits
        [1, V], pools, [], counters)."""
        import jax
        import jax.numpy as jnp
        pos = jnp.asarray(pos, jnp.int32)
        C = toks.shape[1]
        live = jnp.arange(C) < true_len
        x = self._widen(self.embed._data[toks])
        new_pools, stats = [], []
        for j, blk in enumerate(self.blocks):
            x, pool, st = blk.prefill_chunk_paged(
                x, pools[j], table, pos, true_len, scratch, live)
            new_pools.append(pool)
            if st is not None:
                stats.append(st)
        last_h = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1,
                                              axis=x.ndim - 2)
        return (self._head(last_h)[:, -1, :], new_pools, [],
                self._counter_vector(stats))

    def _compiled_fused_decode_fn(self, pnames, params, cache_key,
                                  paged=False):
        """(p_list, b_list, pools, [], block_tables, tok, pos, temp,
        top_k, top_p, seed_lo, seed_hi, ctr, eos, rem) -> (ids, done,
        new_tok, new_pos, new_ctr, new_rem, pools, [], counters).
        Pools donated."""
        if not paged:
            raise NotImplementedError(
                "the latent cache is paged: no contiguous decode")

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
    def serving_spec(self):
        from ..distributed.moe import grouped_matmul_impl
        cfg, attn0 = self.config, self.blocks[0].attn
        dtype = getattr(attn0.kv_a_proj, "compute_dtype", None) \
            or attn0.kv_a_proj.weight._data.dtype
        latent = "latent rows [r + d_rope] have no head axis: "
        return ServingSpec(
            kv=KVRowSpec(len(self.blocks), dtype,
                         (("latent", (attn0.row,)),)),
            max_positions=cfg["max_position_embeddings"],
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            counters=MOE_COUNTERS,
            kernels={"moe.experts": grouped_matmul_impl()},
            residual=({"streams": self.streams,
                       "sinkhorn_iters": cfg["hc_sinkhorn_iters"]}
                      if self.streams > 1 else None),
            unsupported={
                "contiguous": "a contiguous [slots, L] latent buffer "
                              "and its decode / prefill programs",
                "unchunked_prefill": "the per-length paged prefill "
                                     "program over latent rows",
                "ragged": "a ragged paged-attention kernel over latent "
                          "rows (ops/ragged_paged_attn.py takes "
                          "[H, hd] K and V)",
                "spec": "the verify-window program in the absorbed "
                        "form",
                "kv_int8": latent + "quant.py's QuantKV scales are "
                           "per head",
                "mp": latent + "MLA heads over 'mp' and an expert axis "
                      "in SERVING_SPECS are not written",
                "lora": "LoRA banks fold into GPTAttention.out_proj; "
                        "o_proj here has no lane-gathered form",
                "offload": "HostBlockStore entries are (layers, 2, bs, "
                           "H, hd): no latent form",
                "migration": "the migration wire's (layers, K|V, "
                             "blocks, bs, H, hd) payload: no latent "
                             "form",
            })

    def serving_linear_stacks(self):
        return list(self.blocks)
