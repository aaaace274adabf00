"""Mixture-of-Experts with expert parallelism over the 'ep' mesh axis.

Reference parity: **new capability** — the reference has no MoE ops
(SURVEY.md §2.4 "EP: ABSENT").  Designed TPU-first in the GShard/Switch
style: top-k gating with capacity, einsum-based dispatch/combine, expert
weights stacked on a leading E dim sharded over 'ep'.  With tokens sharded
over 'dp' and experts over 'ep', XLA lowers the dispatch einsums to the
all-to-alls the reference would have hand-written against NCCL.

Components:
- ``top_k_gating``  — router probs, expert assignment, capacity dropping,
  load-balancing aux loss (Switch §2.2 / GShard aux).
- ``ExpertFFN``     — E stacked FFNs, weights [E, ...] sharded ('ep', ...).
- ``MoELayer``      — drop-in FFN replacement for a transformer block.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.dispatch import primitive
from ..core.tensor import Tensor
from ..nn.layer.base import Layer
from ..nn import initializer as I
from . import mesh as mesh_mod
from .sharding import _constraint


def top_k_gating(logits, k, capacity, dtype=jnp.float32):
    """Route each token to its top-k experts subject to per-expert capacity.

    logits: [T, E].  Returns (dispatch [T, E, C] one-hot-ish float,
    combine [T, E, C] probability-weighted, aux_loss scalar).
    Capacity is enforced per expert by position-in-expert cumsum; overflow
    tokens are dropped (Switch Transformer semantics).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # aux load-balance loss: E * sum_e (fraction_tokens_e * mean_prob_e)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32),
                           axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    dispatch = jnp.zeros((t, e, capacity), dtype)
    combine = jnp.zeros((t, e, capacity), dtype)
    remaining = probs
    # k rounds of argmax routing; each round claims capacity slots in order
    used = jnp.zeros((e,), jnp.int32)  # slots consumed by earlier rounds
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)            # [T]
        gate = jnp.take_along_axis(remaining, choice[:, None],
                                   axis=-1)[:, 0]          # [T]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [T, E]
        # position of each token within its chosen expert's queue
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T, E]
        pos = jnp.sum(pos_in_e * onehot, axis=-1) + used[choice]
        keep = pos < capacity
        slot = jnp.clip(pos, 0, capacity - 1)
        upd = (jax.nn.one_hot(choice, e, dtype=dtype)[:, :, None]
               * jax.nn.one_hot(slot, capacity, dtype=dtype)[:, None, :]
               * keep[:, None, None].astype(dtype))
        dispatch = dispatch + upd
        combine = combine + upd * gate[:, None, None].astype(dtype)
        used = used + jnp.sum(
            onehot * keep[:, None].astype(jnp.int32), axis=0)
        remaining = remaining * (1.0 - jax.nn.one_hot(choice, e))
    return dispatch, combine, aux


def top_k_routing(logits, k, capacity):
    """Sort-based top-k routing: O(T·k) state instead of the [T, E, C]
    one-hot dispatch tensors (top_k_gating) — scales to real T·E.

    Returns (choice [T, k] expert ids, pos [T, k] slot within expert,
    keep [T, k] bool, gates [T, k] router probs, aux scalar).  Capacity
    priority matches top_k_gating: round r of every token claims slots
    before round r+1 (round-major ordering within each expert's queue).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32),
                           axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    gates, choice = jax.lax.top_k(probs, k)              # [T, k]
    # round-major flatten => stable sort groups by expert, then round,
    # then token — exactly the dense path's slot-claim order
    flat_choice = choice.T.reshape(-1)                   # [k*T]
    order = jnp.argsort(flat_choice, stable=True)
    sorted_e = flat_choice[order]
    idx = jnp.arange(t * k)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_sorted = idx - group_start
    pos_flat = jnp.zeros((t * k,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32))
    pos = pos_flat.reshape(k, t).T                       # [T, k]
    keep = pos < capacity
    return choice, pos, keep, gates, aux


class ExpertFFN(Layer):
    """E stacked feed-forward experts; weights sharded over 'ep'."""

    def __init__(self, num_experts, d_model, d_hidden, weight_attr=None):
        super().__init__()
        self.num_experts = num_experts
        init = I.Normal(0.0, 0.02)
        self.w1 = self.create_parameter([num_experts, d_model, d_hidden],
                                        attr=weight_attr,
                                        default_initializer=init)
        self.b1 = self.create_parameter([num_experts, d_hidden],
                                        is_bias=True)
        self.w2 = self.create_parameter([num_experts, d_hidden, d_model],
                                        attr=weight_attr,
                                        default_initializer=init)
        self.b2 = self.create_parameter([num_experts, d_model],
                                        is_bias=True)
        for p, spec in ((self.w1, PartitionSpec("ep", None, None)),
                        (self.b1, PartitionSpec("ep", None)),
                        (self.w2, PartitionSpec("ep", None, None)),
                        (self.b2, PartitionSpec("ep", None))):
            p.partition_spec = spec
            p.is_distributed = True


class MoELayer(Layer):
    """Drop-in MoE FFN (replaces GPTMLP in a block).

    x [B, S, D] -> gate -> dispatch einsum -> per-expert FFN -> combine.
    Expert compute is sharded over 'ep'; the dispatched activations get a
    sharding constraint ('ep' on the expert dim) so XLA materializes the
    token shuffle as an all-to-all over ICI.
    """

    def __init__(self, d_model, d_hidden=None, num_experts=4, k=2,
                 capacity_factor=2.0, aux_weight=0.01, name=None):
        super().__init__()
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.aux_weight = aux_weight
        d_hidden = d_hidden or 4 * d_model
        self.gate = self.create_parameter(
            [d_model, num_experts],
            default_initializer=I.Normal(0.0, 0.02))
        self.experts = ExpertFFN(num_experts, d_model, d_hidden)
        self._last_aux = None

    def forward(self, x):
        e = self.num_experts
        cap_f, k = self.capacity_factor, self.k

        def fn(xa, gate_w, w1, b1, w2, b2):
            b, s, d = xa.shape
            t = b * s
            capacity = max(1, int(cap_f * t * k / e))
            tokens = xa.reshape(t, d)
            logits = tokens @ gate_w.astype(xa.dtype)
            choice, pos, keep, gates, aux = top_k_routing(
                logits, k, capacity)
            # scatter tokens into the [E, C, D] expert-major buffer
            # (mode='drop' discards over-capacity slots) — O(T·k·D) work,
            # no [T, E, C] one-hot materialization
            slot = choice * capacity + pos                    # [T, k]
            slot_f = jnp.where(keep, slot, e * capacity).reshape(-1)
            tok_f = jnp.broadcast_to(jnp.arange(t)[:, None],
                                     (t, k)).reshape(-1)
            xs = jnp.zeros((e * capacity, d), xa.dtype).at[slot_f].add(
                tokens[tok_f], mode="drop")
            xs = xs.reshape(e, capacity, d)
            # sharded over 'ep': XLA materializes the token shuffle as an
            # all-to-all over ICI
            xs = _constraint(xs, "ep", None, None)
            h = jax.nn.gelu(
                jnp.einsum("ecd,edh->ech", xs, w1.astype(xa.dtype))
                + b1[:, None, :].astype(xa.dtype))
            ys = (jnp.einsum("ech,ehd->ecd", h, w2.astype(xa.dtype))
                  + b2[:, None, :].astype(xa.dtype))
            ys = _constraint(ys, "ep", None, None)
            # combine: gather each (token, round)'s slot, weight by gate
            got = ys.reshape(e * capacity, d)[
                jnp.clip(slot_f, 0, e * capacity - 1)]
            wts = (gates.astype(xa.dtype).reshape(-1) *
                   keep.reshape(-1).astype(xa.dtype))
            out = (got * wts[:, None]).reshape(t, k, d).sum(axis=1)
            # aux loss folded into output via straight-through trick is
            # wrong; expose it as a side output instead
            return out.reshape(b, s, d), aux.astype(xa.dtype)

        prim = primitive(name="moe_ffn", has_aux=False)(fn)
        out, aux = prim(x, self.gate, self.experts.w1, self.experts.b1,
                        self.experts.w2, self.experts.b2)
        self._last_aux = aux
        return out

    def aux_loss(self):
        """Load-balancing loss of the last forward (scaled).

        Returns None when the stored value is a tracer from a finished jit
        trace (it is only meaningful *inside* that trace — e.g. when the
        train-step builder calls this while tracing); keeping it would leak
        the trace and crash any later eager use."""
        if self._last_aux is None:
            return None
        import jax
        from ..ops.math import multiply
        try:
            return multiply(self._last_aux, self.aux_weight)
        except jax.errors.UnexpectedTracerError:
            # Stale tracer from a completed trace — drop it.
            self._last_aux = None
            return None


def collect_moe_aux_loss(layer: Layer):
    """Sum aux losses over every MoELayer in a model (call after forward)."""
    total = None
    for sub in layer.sublayers(include_self=True):
        if isinstance(sub, MoELayer):
            a = sub.aux_loss()
            if a is not None:
                total = a if total is None else total + a
    return total


# -- dropless routing (serving) ------------------------------------------
#
# The capacity path above drops what overflows and moves dense
# [E, C, D] buffers, which is what training over an 'ep' axis wants.
# Serving computes every (token, selected expert) pair: pairs are sorted
# by expert and each projection is ONE grouped product over the sorted
# rows, so a step reads the weights of the experts that were hit and of
# no other.

def sigmoid_topk_routing(logits, bias, k, scale=1.0, normalize=True,
                         norm_eps=1e-20):
    """Sigmoid-scored top-k with a selection-only correction bias (the
    ``noaux_tc`` gate of DeepSeek-V3's published code, one group):
    ``s = sigmoid(logits)`` in float32; the ``k`` experts are the top
    ``k`` of ``s + bias``; the weights are the UNBIASED scores of the
    selected, normalised to sum to one (``normalize``: over their sum
    ``+ norm_eps``, 1e-20 in DeepSeek-V3's code and 1e-6 in LFM2's) and
    multiplied by ``scale``.  logits [T, E] -> (choice [T, k] int32,
    weights [T, k] float32)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, choice = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], k)
    w = jnp.take_along_axis(s, choice, axis=-1)
    if normalize and k > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return choice.astype(jnp.int32), w * scale


def softmax_topk_routing(logits, k, normalize=True):
    """Softmax-scored top-k (the Qwen3-MoE lineage's gate, which SDAR's
    routed layers keep): ``p = softmax(logits)`` over all experts in
    float32; the ``k`` experts are the top ``k`` of ``p``; the weights
    are their probabilities, renormalised over the selected to sum to
    one where ``normalize`` (``norm_topk_prob``).  No bias, no scale.
    logits [T, E] -> (choice [T, k] int32, weights [T, k] float32)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, choice = jax.lax.top_k(p, k)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return choice.astype(jnp.int32), w


def sort_pairs_by_expert(choice, live, num_experts, first=None):
    """Sort the T x k (token, expert) pairs by expert.  ``live`` [T]
    marks the rows that are real tokens: the pairs of the others go to
    the end and belong to no group, so they hit no expert.  ``first``
    (None: every expert the router knows is here) says the groups are
    the experts ``[first, first + num_experts)`` of a wider router:
    a pair routed outside them goes to the end as a dead row's does.
    Returns (order [P] pair indices in sorted order, group_sizes [E]
    int32); the token of sorted row i is ``order[i] // k``."""
    t, k = choice.shape
    here = live[:, None]
    if first is not None:
        choice = choice - first
        here = here & (choice >= 0) & (choice < num_experts)
    flat = jnp.where(here, choice, num_experts).reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((num_experts + 1,), jnp.int32).at[flat].add(1)
    return order.astype(jnp.int32), sizes[:num_experts]


# numbers of one weight tile [tk, tn] of the kernel at most: twice a
# bf16 tile of 2,048 x 1,536 (6.3 MB) is what fits fast memory beside
# the rows and the accumulator; 2,048 x 1,792 (7.3 MB) does not at any
# row tile (PR 35 on the chip; PR 46 by the compile for the described
# v5e, which refuses it)
_GMM_WEIGHT_TILE = 2048 * 1536


def _gmm_tiling(m, k, n):
    """Tile sizes of the megablox kernel for an [m, k] x [E, k, n]
    grouped product: all of a short m (under 256 rows) in one tile (a
    decode step's pairs: every hit expert then costs one pass over its
    own weights), 128 rows otherwise; the whole contraction in one k
    tile (up to 2,048, and up to 3,072 beside an n tile of at most 768,
    PR 44); the whole of n in one tile where the contraction's tile
    is at most 1,024 and n at most 2,048, else the widest n tile of
    those tried that divides n and keeps the weight tile inside
    ``_GMM_WEIGHT_TILE``.  Chip run, PR 28, 40 experts hit, m 192:
    [2,048 -> 2,816] 0.65 ms at (192, 2048, 1408) against 0.72 at
    (192, 1024, 1408), [1,408 -> 2,048] 0.34 ms at (192, 1408, 1024)
    against 0.60 at (192, 128, 1024) and 0.42 at (192, 1408, 256): 87%
    and 83% of the weights' time at 819 GB/s.  Chip run, PR 35
    (``_chip/gmm_bench.py``), 128 experts hit, m 1,024 / 2,048:
    [2,048 -> 1,536] 1.166 / 1.226 ms at (128, 2048, 1536) against
    1.188 / 1.249 at n tile 768, 1.178 / 1.251 at 512, 1.276 / 1.359
    at k tile 1,024, 1.234 / 1.280 at (256, 2048, 768), and no fit in
    fast memory at (256, 2048, 1536); [768 -> 2,048] 0.619 / 0.672 ms
    at (128, 768, 2048) against 0.660 / 0.718 at n tile 1,024, 0.691 /
    0.756 at 512, 0.647 / 0.683 at (256, 768, 2048): 84% and 79% of the
    weights' time; ``ragged_dot`` 2.74 and 1.65 ms.  Chip run, PR 37
    (``_chip/xing_bench.py``), 30 of 64 experts hit by 44 pairs in m
    128 / all 64 by 1,024: [3,584 -> 2,048] (contraction tiles of 512:
    3,584 = 7 x 512) 0.664 / 1.399 ms at (128, 512, 2048) against 0.727
    / 1.539 at n tile 1,024, 0.662 / 1.400 at k tile 896, and no fit at
    (128, 3584, 1024) or (128, 1792, 2048); [1,024 -> 3,584] 0.337 /
    0.717 ms at (128, 1024, 1792) against 0.343 / 0.744 at n tile 512,
    0.346 / 0.734 at (128, 512, 3584), no fit at (128, 1024, 3584): 81%
    / 82% and 80% / 80% of the weights' time by the host's clock;
    ``ragged_dot`` 0.907 / 3.279 and 0.469 / 1.706 ms.  Chip run, PR 44
    (``_chip/gmm_bench.py``), 32 held experts of a router of 256, a
    contraction of 3,072, m 128 with 8 pairs on 7 experts / m 1,024
    with 128 pairs on all 32: the whole contraction in one tile beside
    an n tile of 768 fits and wins, [3,072 -> 6,144] 0.391 / 1.644 ms at
    (m, 3072, 768) against 0.418 / 1.776 at the rule's (m, 1024, 1536),
    0.403 / 1.710 at (m, 512 or 768, 3072); [3,072 -> 3,072] 0.223 /
    0.839 against 0.235 / 0.905: 83% / 90% and 72% / 88% of the
    weights' time; n tiles of 512 and 1,024 within 1% of 768;
    ``ragged_dot`` 0.579 / 4.167 and 0.348 / 2.095 ms.  Chip run, PR 46
    (``_chip/gmm_bench.py``), 32 experts, m 256 (64 slots x 4) with 16
    pairs on 14 experts / 112 on 31, and m 1,024 with all 32 hit:
    [2,048 -> 3,584], whose rule-made (m, 2048, 1792) fits no fast
    memory (``_GMM_WEIGHT_TILE``): 0.318 / 0.648 / 0.711 ms at (128,
    2048, 896) against 0.328 / 0.687 at (256, 2048, 896), 0.321 / 0.658
    / 0.726 at n tile 512, 0.336 / 0.696 / 0.730 at (128, 1024, 1792);
    [1,792 -> 2,048] 0.244 / 0.346 / 0.383 ms at (128, 1792, 1024)
    against 0.255 / 0.359 at (256, 1792, 1024) and 0.234 / 0.343 /
    0.383 at n tile 512: a row tile of 128 wins at m 256 (two row
    tiles of a hit expert cost less than the wider one), 79% / 86% /
    81% and 51% / 80% / 75% of the weights' time; ``ragged_dot`` 0.562
    / 1.129 / 1.673 and 0.373 / 0.730 / 1.011 ms."""
    tm = m if m < 256 else 128
    if 2048 < k <= 3072:
        return tm, k, next(t for t in (768, 512, 256, 128, n)
                           if n % t == 0)
    tk = k if k <= 2048 else next(
        t for t in (2048, 1024, 512, 256, 128, k) if k % t == 0)
    tn = n if tk <= 1024 and n <= 2048 else next(
        t for t in (1792, 1536, 1408, 1024, 896, 512, 256, 128, n)
        if n % t == 0 and (tk * t <= _GMM_WEIGHT_TILE or t <= 128))
    return tm, tk, tn


def grouped_matmul_impl():
    """What ``grouped_matmul`` runs where no ``impl`` is named: the
    megablox kernel on a TPU, ``ragged_dot`` elsewhere (the kernel's
    interpret mode is slow and the CPU tests need the arithmetic, not
    the schedule; on the v5e ``ragged_dot`` takes 2.2 x the kernel's
    time, PERF.md, PR 28).  A served model reports it
    (``ServingSpec.kernels``), so ``/healthz`` says which one a
    replica serves on."""
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def grouped_matmul(lhs, rhs, group_sizes, impl=None):
    """Rows of ``lhs`` [m, k], sorted by group, each times its group's
    matrix of ``rhs`` [E, k, n] -> float32 [m, n].  Rows past
    ``sum(group_sizes)`` belong to no group and come back as zeros.

    ``impl``: ``"gmm"`` is the megablox Pallas kernel, which visits the
    (row tile, group) pairs that hold rows and so reads each hit
    group's matrix once; ``"ragged_dot"`` is XLA's own.  Default:
    ``grouped_matmul_impl()``."""
    if impl is None:
        impl = grouped_matmul_impl()
    m = lhs.shape[0]
    if impl == "ragged_dot":
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    elif impl == "gmm":
        # (the package's own ``gmm`` attribute is its custom_vjp
        # wrapper, which takes no keywords: name the kernel's module)
        import importlib
        gmm = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
        pad = -m % 8 if m <= 256 else -m % 128
        if pad:
            lhs = jnp.concatenate(
                [lhs, jnp.zeros((pad, lhs.shape[1]), lhs.dtype)])
        out = gmm(lhs, rhs, group_sizes,
                  preferred_element_type=jnp.float32,
                  tiling=_gmm_tiling(m + pad, rhs.shape[1],
                                     rhs.shape[2]))[:m]
    else:
        raise ValueError(f"grouped_matmul impl {impl!r}")
    # the kernel never visits the rows of no group: whatever memory
    # holds there must not reach the sum over a token's pairs
    in_group = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(in_group[:, None], out, 0.0)


def dropless_experts(x, choice, weights, live, w_in, w_out, first=None):
    """Every (token, selected expert) pair through its expert's gated
    feed-forward, nothing dropped: ``y_t = sum_j weights[t, j] *
    E_choice[t, j](x_t)`` with ``E_e(x) = (silu(x W1_e) * (x W3_e))
    W2_e``.  With ``first`` the stacks hold the experts ``[first,
    first + E)`` of a wider router (this chip's share under expert
    parallelism): the pairs that fall on them are computed, the others
    add nothing, and nothing stands in for the chips that hold them.

    x [T, D]; choice / weights [T, k]; live [T] bool (rows that are
    tokens); w_in [E, D, 2F] holds W1 | W3 side by side, w_out
    [E, F, D].  Returns (y [T, D] float32, stats int32 [3]: pairs
    computed, experts hit, the busiest expert's pairs)."""
    t, k = choice.shape
    e, f = w_in.shape[0], w_out.shape[1]
    order, sizes = sort_pairs_by_expert(choice, live, e, first)
    rows = x[order // k]                                   # [P, D]
    a = grouped_matmul(rows, w_in, sizes)                  # [P, 2F]
    act = (jax.nn.silu(a[:, :f]) * a[:, f:]).astype(x.dtype)
    o = grouped_matmul(act, w_out, sizes)                  # [P, D]
    o = o * weights.reshape(t * k)[order][:, None]
    y = jnp.zeros((t, x.shape[1]), jnp.float32).at[order // k].add(o)
    stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                       jnp.max(sizes)]).astype(jnp.int32)
    return y, stats
