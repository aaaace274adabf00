"""RaggedTensor — true variable-length sequence semantics, TPU-static.

Reference parity: ``paddle/fluid/framework/lod_tensor.h:114`` (LoDTensor:
a flat value tensor + level-0 offsets) and ``operators/sequence_ops/``
computing directly on those offsets.  This closes the representational
gap COVERAGE.md's dense+lengths reduction left open — while keeping
every shape STATIC for XLA:

* ``values`` [capacity, ...]: the flat row-major concatenation of all
  sequences, zero-padded up to a fixed ``capacity`` (pick it from the
  bucketing ladder, exactly like the padded-dense path picks L);
* ``row_splits`` [B+1]: the LoD level-0 offsets;
* positions ≥ ``row_splits[-1]`` belong to a TRASH segment, so every
  segment op runs as one ``jax.ops.segment_*`` with ``num_segments =
  B + 1`` and drops the last row — no data-dependent shapes anywhere,
  one compile per capacity bucket.

Compute on the flat layout does real work proportional to ``capacity``
(total tokens), not ``B × L_max`` — the padded-dense path's cost.  At
the skew measured in round 3 (median 166 / max 2048) that
is the difference between 17% and 85% waste.

Ops are differentiable (segment_sum/scatter have VJPs); conversion
helpers bridge to the framework's padded+lengths convention.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .tensor import Tensor
from .dispatch import ensure_tensor


def _arr(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class RaggedTensor:
    """Flat ``values`` + ``row_splits`` (+ static ``capacity``).

    Multi-level (nested) LoD — reference ``lod_tensor.h:114`` where LoD
    is a *vector* of offset levels (paragraphs→sentences→words) — is
    carried as ``outer_lods``: a tuple of offset vectors, outermost
    first, each indexing the rows of the next level; ``row_splits``
    stays the bottom level (the one indexing ``values``), so every
    existing single-level consumer is untouched.  ``lod()`` /
    ``recursive_sequence_lengths()`` match the reference LoDTensor
    accessors."""

    __slots__ = ("values", "row_splits", "capacity", "outer_lods")

    def __init__(self, values, row_splits, outer_lods=()):
        self.values = ensure_tensor(values)
        self.row_splits = ensure_tensor(row_splits)
        self.capacity = int(self.values.shape[0])
        self.outer_lods = tuple(ensure_tensor(s) for s in outer_lods)

    # -- construction -----------------------------------------------------
    @classmethod
    def from_padded(cls, dense, lengths, capacity=None):
        """[B, L, ...] + lengths -> ragged.  ``capacity`` defaults to
        B*L (lossless); pass a bucket size to bound compile variants."""
        dense = ensure_tensor(dense)
        lengths = ensure_tensor(lengths)
        d = dense._data
        lens = lengths._data.astype(jnp.int32)
        B, L = d.shape[0], d.shape[1]
        cap = int(capacity or B * L)
        if not isinstance(lens, jax.core.Tracer):
            total = int(jnp.sum(lens))
            if total > cap:
                raise ValueError(
                    f"RaggedTensor.from_padded: capacity {cap} < total "
                    f"tokens {total} — the scatter would silently drop "
                    "data (pick the bucket like io/bucketing.py does); "
                    "under jit, bounding totals is the CALLER's "
                    "contract")
        splits = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(lens)])
        # scatter each valid (b, t) to its flat slot; padding -> trash
        pos = splits[:-1][:, None] + jnp.arange(L)[None, :]
        valid = jnp.arange(L)[None, :] < lens[:, None]
        slot = jnp.where(valid, pos, cap)            # trash slot = cap
        flat = jnp.zeros((cap + 1,) + d.shape[2:], d.dtype)
        flat = flat.at[slot.reshape(-1)].set(
            d.reshape((B * L,) + d.shape[2:]))
        return cls(Tensor(flat[:cap]), Tensor(splits))

    @staticmethod
    def pack_rows_numpy(rows, capacity=None):
        """Pure-numpy packing -> (flat [cap, ...], row_splits [B+1]).
        DataLoader collate fns use THIS (workers must never touch jax —
        io/worker.py's fork-safety contract)."""
        rows = [np.asarray(r) for r in rows]
        lens = np.array([len(r) for r in rows], np.int32)
        total = int(lens.sum())
        cap = int(capacity or total)
        if cap < total:
            raise ValueError(
                f"RaggedTensor: capacity {cap} < total length {total}")
        tail = rows[0].shape[1:] if rows else ()
        flat = np.zeros((cap,) + tail, rows[0].dtype if rows
                        else np.float32)
        off = 0
        for r in rows:
            flat[off:off + len(r)] = r
            off += len(r)
        splits = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
        return flat, splits

    @classmethod
    def from_rows(cls, rows, capacity=None):
        """list of per-row numpy/array values -> ragged (host-side)."""
        flat, splits = cls.pack_rows_numpy(rows, capacity)
        return cls(Tensor(flat), Tensor(splits))

    @classmethod
    def from_nested_rows(cls, nested, capacity=None):
        """Arbitrary-depth nested lists of row arrays -> ragged with
        ``lod_level == depth`` (reference: creating a LoDTensor from
        recursive_sequence_lengths).  Rows must be numpy arrays —
        grouping levels above them are python lists/tuples (a bare
        list-of-scalars row is ambiguous with a grouping level; wrap it
        in np.asarray, or use ``from_rows`` for depth 1)."""
        lods = []
        level = list(nested)
        while level and isinstance(level[0], (list, tuple)):
            counts = np.array([len(g) for g in level], np.int64)
            lods.append(np.concatenate(
                [[0], np.cumsum(counts)]).astype(np.int32))
            level = [item for g in level for item in g]
        flat, splits = cls.pack_rows_numpy(level, capacity)
        return cls(Tensor(flat), Tensor(splits),
                   outer_lods=tuple(Tensor(s) for s in lods))

    # -- views ------------------------------------------------------------
    @property
    def lod_level(self):
        return len(self.outer_lods) + 1

    def lod(self):
        """Offset form, outermost level first — reference
        ``LoDTensor.lod()``."""
        return [list(np.asarray(s.numpy())) for s in self.outer_lods] + \
            [list(np.asarray(self.row_splits.numpy()))]

    def recursive_sequence_lengths(self):
        """Length form per level — reference
        ``LoDTensor.recursive_sequence_lengths()``."""
        out = []
        for off in self.lod():
            a = np.asarray(off)
            out.append(list(a[1:] - a[:-1]))
        return out

    @property
    def nrows(self):
        return int(self.row_splits.shape[0]) - 1

    def lengths(self):
        s = self.row_splits._data
        return Tensor(s[1:] - s[:-1])

    def segment_ids(self):
        """[capacity] int32: row of each flat slot; trash slots get B
        (one past the last row) — THE enabler for segment ops."""
        s = self.row_splits._data
        ids = jnp.searchsorted(s, jnp.arange(self.capacity),
                               side="right") - 1
        total = s[-1]
        return jnp.where(jnp.arange(self.capacity) < total, ids,
                         self.nrows)

    def to_padded(self, max_len, pad_value=0.0):
        """ragged -> ([B, max_len, ...], lengths).  Raises (concrete
        path) when a row exceeds ``max_len`` — silent truncation with
        un-clamped lengths would poison every dense+lengths consumer."""
        v = self.values._data
        s = self.row_splits._data
        B = self.nrows
        lens = s[1:] - s[:-1]
        if not isinstance(lens, jax.core.Tracer) and B:
            longest = int(jnp.max(lens))
            if longest > max_len:
                raise ValueError(
                    f"to_padded: a row has {longest} tokens > max_len "
                    f"{max_len} — raise max_len or slice rows upstream")
        pos = s[:-1][:, None] + jnp.arange(max_len)[None, :]
        valid = jnp.arange(max_len)[None, :] < lens[:, None]
        gathered = v[jnp.clip(pos, 0, self.capacity - 1)]
        dense = jnp.where(
            valid.reshape(valid.shape + (1,) * (v.ndim - 1)), gathered,
            jnp.asarray(pad_value, v.dtype))
        return Tensor(dense), Tensor(lens)

    def to_padded_nested(self, max_rows, max_len, pad_value=0.0):
        """Nested (lod_level >= 2) -> ([G, max_rows, max_len, ...],
        row_lengths [G, max_rows]) using the innermost outer level; for
        deeper nests apply per remaining level.  Reference analogue:
        padding a 2-level LoDTensor batch (sentences per doc, words per
        sentence)."""
        if not self.outer_lods:
            raise ValueError(
                "to_padded_nested: lod_level is 1 — use to_padded")
        dense, lens = self.to_padded(max_len, pad_value)
        d, ln = dense._data, lens._data
        so = self.outer_lods[-1]._data
        B = self.nrows
        G = int(so.shape[0]) - 1
        grp_lens = so[1:] - so[:-1]
        if not isinstance(grp_lens, jax.core.Tracer) and G:
            widest = int(jnp.max(grp_lens))
            if widest > max_rows:
                raise ValueError(
                    f"to_padded_nested: a group has {widest} rows > "
                    f"max_rows {max_rows}")
        pos = so[:-1][:, None] + jnp.arange(max_rows)[None, :]
        valid = jnp.arange(max_rows)[None, :] < grp_lens[:, None]
        g = d[jnp.clip(pos, 0, B - 1)]          # [G, max_rows, L, ...]
        mask = valid.reshape(valid.shape + (1,) * (g.ndim - 2))
        g = jnp.where(mask, g, jnp.asarray(pad_value, g.dtype))
        row_lens = jnp.where(valid, ln[jnp.clip(pos, 0, B - 1)], 0)
        return Tensor(g), Tensor(row_lens)

    def rows(self):
        """Host-side list of per-row numpy arrays (debug/IO)."""
        v = np.asarray(self.values.numpy())
        s = np.asarray(self.row_splits.numpy())
        return [v[s[i]:s[i + 1]] for i in range(len(s) - 1)]

    def nested_rows(self):
        """Host-side nested lists mirroring ``lod_level`` (debug/IO) —
        the inverse of ``from_nested_rows``."""
        out = self.rows()
        for s in reversed(self.outer_lods):
            off = np.asarray(s.numpy())
            out = [out[off[i]:off[i + 1]] for i in range(len(off) - 1)]
        return out


# ---------------------------------------------------------------------------
# segment-compute sequence ops (reference: operators/sequence_ops/*)

def _masked_values(rt):
    """values with trash slots zeroed (so sums ignore them)."""
    v = rt.values._data
    total = rt.row_splits._data[-1]
    live = (jnp.arange(rt.capacity) < total)
    return v * live.reshape((-1,) + (1,) * (v.ndim - 1)).astype(v.dtype)


def sequence_pool(rt: RaggedTensor, pool_type: str, pad_value=0.0):
    """-> [B, ...] (reference: sequence_pool_op.h; SUM/MEAN/SQRT/MAX/
    LAST/FIRST).  Empty rows produce ``pad_value`` like the reference."""
    ids = rt.segment_ids()
    B = rt.nrows
    v = _masked_values(rt)
    lens = rt.lengths()._data.astype(v.dtype)
    ptype = pool_type.lower()
    ptype = {"average": "mean", "avg": "mean"}.get(ptype, ptype)
    if ptype in ("sum", "mean", "sqrt"):
        s = jax.ops.segment_sum(v, ids, num_segments=B + 1)[:B]
        if ptype == "mean":
            s = s / jnp.maximum(lens, 1).reshape(
                (-1,) + (1,) * (v.ndim - 1))
        elif ptype == "sqrt":
            s = s / jnp.sqrt(jnp.maximum(lens, 1)).reshape(
                (-1,) + (1,) * (v.ndim - 1))
        out = s
    elif ptype in ("max", "min"):
        info = jnp.finfo if jnp.issubdtype(v.dtype, jnp.floating) \
            else jnp.iinfo
        fill = info(v.dtype).min if ptype == "max" else \
            info(v.dtype).max
        vm = jnp.where((ids < B).reshape(
            (-1,) + (1,) * (v.ndim - 1)), rt.values._data, fill)
        seg = jax.ops.segment_max if ptype == "max" else \
            jax.ops.segment_min
        out = seg(vm, ids, num_segments=B + 1)[:B]
    elif ptype in ("first", "last"):
        s = rt.row_splits._data
        idx = s[:-1] if ptype == "first" else jnp.maximum(s[1:] - 1, 0)
        out = rt.values._data[jnp.clip(idx, 0, rt.capacity - 1)]
    else:
        raise ValueError(
            f"sequence_pool: unknown pool_type {pool_type!r} "
            "(sum/mean|average/sqrt/max/min/first/last)")
    empty = (rt.lengths()._data == 0).reshape(
        (-1,) + (1,) * (v.ndim - 1))
    out = jnp.where(empty, jnp.asarray(pad_value, out.dtype), out)
    if rt.outer_lods:
        # nested LoD: pooling consumes the bottom level; the result is
        # ragged over the remaining levels (reference: pooling words ->
        # sentence vectors, still LoD-organized by paragraph)
        return RaggedTensor(Tensor(out), rt.outer_lods[-1],
                            outer_lods=rt.outer_lods[:-1])
    return Tensor(out)


def sequence_softmax(rt: RaggedTensor):
    """Row-wise softmax over 1-D-per-step values (reference:
    sequence_softmax_op)."""
    ids = rt.segment_ids()
    B = rt.nrows
    v = rt.values._data
    neg = jnp.finfo(v.dtype).min
    vm = jnp.where(ids < B, v, neg)
    mx = jax.ops.segment_max(vm, ids, num_segments=B + 1)
    live = (ids < B)
    # mask INSIDE exp: exp of the raw (v - finfo.min) would be inf on
    # the untaken branch and the where-VJP's 0*inf turns gradients at
    # trash slots into NaN (the classic jnp.where grad trap)
    ex = live.astype(v.dtype) * jnp.exp(
        jnp.where(live, v - mx[ids], 0.0))
    den = jax.ops.segment_sum(ex, ids, num_segments=B + 1)
    # 1e-38 is denormal — XLA's FTZ would flush it to 0 and
    # make the trash slots 0/0=NaN; stay in normal range
    out = ex / jnp.maximum(den[ids], 1e-30)
    return RaggedTensor(Tensor(out), rt.row_splits,
                        outer_lods=rt.outer_lods)


def sequence_reverse(rt: RaggedTensor):
    """Reverse each row in place (reference: sequence_reverse_op)."""
    ids = rt.segment_ids()
    B = rt.nrows
    s = rt.row_splits._data
    pos = jnp.arange(rt.capacity)
    ids_c = jnp.clip(ids, 0, B - 1)
    # mirror within the row: start + end-1 - pos
    src = s[ids_c] + (s[ids_c + 1] - 1) - pos
    src = jnp.where(ids < B, src, pos)
    out = rt.values._data[jnp.clip(src, 0, rt.capacity - 1)]
    return RaggedTensor(Tensor(out), rt.row_splits,
                        outer_lods=rt.outer_lods)


def _level_splits(rt: RaggedTensor, level):
    """Offset vector of a LoD level (0 = outermost, -1 = bottom)."""
    all_lods = rt.outer_lods + (rt.row_splits,)
    return all_lods[level]._data


def sequence_expand(rt: RaggedTensor, ref: RaggedTensor, ref_level=-1,
                    capacity=None, max_out_rows=None, one_step=None):
    """Reference ``sequence_expand_op.cc``: repeat x's row i
    ``ref_len[i]`` times, where ``ref_len`` are the lengths of ref's
    LoD level ``ref_level``.

    Two regimes, matching the reference's two uses:

    * all x rows are single-step and ``ref_level`` is the bottom level
      — the broadcast/expand_as pattern (CTR models): x's step i is
      broadcast across ref's row i; output has ref's LoD.
    * general whole-row repeat (nested beam-search/NMT pattern): each
      x ROW is copied ``ref_len[i]`` times; the output gains an outer
      LoD level grouping the copies (lod_level 2, mirroring the
      reference where out LoD = ref-level offsets over x's LoD).
      Shapes stay static: pass ``capacity`` (total out steps bound) and
      ``max_out_rows`` under jit; both default to the exact concrete
      totals outside jit.

    Under jit the x row lengths are traced, so the two regimes cannot
    be told apart: pass ``one_step=True`` to assert the broadcast
    pattern, or ``capacity``/``max_out_rows`` for the whole-row repeat.
    Neither raises — a silent one-step fallback on multi-step rows
    would return only each row's first step.
    """
    rl_splits = _level_splits(ref, ref_level)
    rl = (rl_splits[1:] - rl_splits[:-1]).astype(jnp.int32)
    N = int(rl.shape[0])
    if rt.nrows != N:
        raise ValueError(
            f"sequence_expand: x has {rt.nrows} rows but ref level "
            f"{ref_level} has {N} entries")
    x_lens = rt.lengths()._data
    lens_traced = isinstance(x_lens, jax.core.Tracer)
    if not lens_traced:
        concrete_one = bool(jnp.all(x_lens == 1))
        if one_step and not concrete_one:
            raise ValueError(
                "sequence_expand: one_step=True but x has multi-step "
                "rows")
        one_step = concrete_one
    elif one_step is None:
        if capacity is None and max_out_rows is None:
            raise ValueError(
                "sequence_expand: x row lengths are traced (jit) and no "
                "bounds were given — pass one_step=True for the "
                "broadcast/expand_as pattern, or capacity/max_out_rows "
                "for the whole-row repeat (a silent one-step fallback "
                "would return only each row's first step)")
        one_step = False
    if one_step and ref_level in (-1, ref.lod_level - 1):
        # broadcast fast path: one gather, output keeps ref's LoD
        ids = ref.segment_ids()
        B = ref.nrows
        x_first = rt.values._data[
            jnp.clip(rt.row_splits._data[:-1], 0, rt.capacity - 1)]
        out = x_first[jnp.clip(ids, 0, B - 1)]
        live = (ids < B).reshape((-1,) + (1,) * (out.ndim - 1))
        out = out * live.astype(out.dtype)
        return RaggedTensor(Tensor(out), ref.row_splits,
                            outer_lods=ref.outer_lods)

    # general whole-row repeat, static-shaped
    r_cum = jnp.cumsum(rl)
    r_total = r_cum[-1]
    if max_out_rows is None:
        if isinstance(r_total, jax.core.Tracer):
            raise ValueError(
                "sequence_expand: pass max_out_rows under jit — the "
                "repeated row count is data-dependent")
        max_out_rows = int(r_total)
    elif not isinstance(r_total, jax.core.Tracer) and \
            int(r_total) > max_out_rows:
        raise ValueError(
            f"sequence_expand: max_out_rows {max_out_rows} < actual "
            f"repeated row count {int(r_total)} — the result would "
            "silently drop rows")
    r = jnp.arange(max_out_rows)
    grp = jnp.searchsorted(r_cum, r, side="right")     # x row per out row
    grp_c = jnp.clip(grp, 0, N - 1)
    live_row = r < r_total
    sx = rt.row_splits._data
    out_len = jnp.where(live_row, sx[grp_c + 1] - sx[grp_c], 0)
    out_splits = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(out_len)]).astype(jnp.int32)
    total_steps = out_splits[-1]
    if capacity is None:
        if isinstance(total_steps, jax.core.Tracer):
            raise ValueError(
                "sequence_expand: pass capacity under jit — the total "
                "output step count is data-dependent")
        capacity = int(total_steps)
    elif not isinstance(total_steps, jax.core.Tracer) and \
            int(total_steps) > capacity:
        raise ValueError(
            f"sequence_expand: capacity {capacity} < actual output "
            f"step count {int(total_steps)} — the scatter would "
            "silently drop data (pick the bucket like io/bucketing.py)")
    p = jnp.arange(capacity)
    row_of_p = jnp.searchsorted(out_splits, p, side="right") - 1
    row_c = jnp.clip(row_of_p, 0, max_out_rows - 1)
    local = p - out_splits[row_c]
    src = sx[jnp.clip(grp[row_c], 0, N - 1)] + local
    vals = rt.values._data[jnp.clip(src, 0, rt.capacity - 1)]
    live = (p < total_steps).reshape((-1,) + (1,) * (vals.ndim - 1))
    vals = vals * live.astype(vals.dtype)
    outer = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), r_cum]).astype(jnp.int32)
    return RaggedTensor(Tensor(vals), Tensor(out_splits),
                        outer_lods=(Tensor(outer),))


def sequence_concat(a: RaggedTensor, b: RaggedTensor):
    """Row-wise concat: out row i = a row i ++ b row i (reference:
    sequence_concat_op).  Nested inputs must agree on their outer
    levels; the output carries them unchanged (bottom-level concat
    leaves the grouping structure intact)."""
    if a.nrows != b.nrows:
        raise ValueError("sequence_concat: row counts differ")
    if len(a.outer_lods) != len(b.outer_lods):
        raise ValueError("sequence_concat: lod_level mismatch")
    for sa_, sb_ in zip(a.outer_lods, b.outer_lods):
        da, db = sa_._data, sb_._data
        if not (isinstance(da, jax.core.Tracer)
                or isinstance(db, jax.core.Tracer)):
            if da.shape != db.shape or not bool(jnp.all(da == db)):
                raise ValueError(
                    "sequence_concat: outer LoD levels differ between "
                    "inputs")
    sa, sb = a.row_splits._data, b.row_splits._data
    la, lb = sa[1:] - sa[:-1], sb[1:] - sb[:-1]
    splits = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(la + lb)]).astype(jnp.int32)
    cap = a.capacity + b.capacity
    B = a.nrows

    def scatter(src_vals, src_splits, dst, base_off):
        ids = jnp.searchsorted(
            src_splits, jnp.arange(src_vals.shape[0]),
            side="right") - 1
        total = src_splits[-1]
        live = jnp.arange(src_vals.shape[0]) < total
        ids_c = jnp.clip(ids, 0, B - 1)
        local = jnp.arange(src_vals.shape[0]) - src_splits[ids_c]
        slot = splits[ids_c] + base_off[ids_c] + local
        slot = jnp.where(live, slot, cap)
        return dst.at[slot].set(src_vals)

    tail = a.values._data.shape[1:]
    dst = jnp.zeros((cap + 1,) + tail, a.values._data.dtype)
    dst = scatter(a.values._data, sa, dst, jnp.zeros(B, jnp.int32))
    dst = scatter(b.values._data, sb, dst, la.astype(jnp.int32))
    return RaggedTensor(Tensor(dst[:cap]), Tensor(splits),
                        outer_lods=a.outer_lods)
