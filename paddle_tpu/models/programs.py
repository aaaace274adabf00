"""What a served model offers ``serving.Engine`` — the seam between
``models/`` and ``serving/``.

The engine owns slots, blocks and ticks.  A model offers:

``serving_spec()``   a ``ServingSpec``: what it keeps per cached
                     position and layer (``KVRowSpec``), its
                     longest sequence, its vocabulary, the integer
                     counters its step programs return beside the
                     sampled tokens, and which engine options it
                     cannot honour yet (each with the missing piece,
                     so the engine refuses them by name at
                     construction);
``serving_program(kind, pnames, params, cache_key, *shape, **opts)``
                     the jitted step program of that ``kind`` over the
                     engine's pools (``"fused_decode"``,
                     ``"paged_chunk_prefill"``, ...), built once a
                     ``cache_key`` and wrapped by ``_compile_probe``;
``serving_linear_stacks()``   the layers whose ``nn.Linear`` children
                     weight-only int8 serving relayouts.

``ServedModel`` is the mixin the model files inherit: the compile
listeners, the probe, and the default ``serving_program`` that finds
the builder ``_compiled_<kind>_fn`` on the model.
"""
from __future__ import annotations

import functools
import threading
import time


def _jit_named(kind, pure, **jit_kwargs):
    """``jax.jit(pure)`` under the name of the program's
    ``_compile_probe`` kind, so that a device trace's ``XLA Modules``
    line reads ``jit_gpt_fused_decode(...)`` where it read
    ``jit_pure(...)`` for every program alike.  (The module name is
    part of the persistent compile cache's key.)"""
    import jax
    pure.__name__ = pure.__qualname__ = "gpt_" + kind
    return jax.jit(pure, **jit_kwargs)


def _scoped(name):
    """Run the decorated method under ``jax.named_scope(name)``: the
    scope shows in the op metadata of a device trace, so a program's
    time splits into attention / mlp / lm_head / sampling."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            import jax
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def filter_logits_lanes(last, temperature, top_k, top_p):
    """PER-LANE sampling filters on f32 logits [B, V]: temperature
    / top_k / top_p are [B] arrays — one independent request per
    batch row (the serving slot pool), every parameter traced, so
    ONE compiled program serves any per-slot mix.  Same filter
    sequence and masking values as ``GPTModel._filter_logits`` (temperature
    -> top-k -> top-p over the already-masked row), just with the
    scalars lifted to lanes; ``top_k == 0`` / ``top_p == 1``
    disable their filter lane-wise, and a ``temperature == 0``
    greedy-sentinel lane passes through at temperature 1 (its
    filtered row is discarded — ``sample_lanes`` argmaxes the raw
    logits instead)."""
    import jax
    import jax.numpy as jnp
    V = last.shape[-1]
    t_eff = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    x = last / t_eff
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    k_eff = jnp.clip(top_k, 1, V).astype(jnp.int32)
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    x = jnp.where((top_k > 0)[:, None] & (x < kth), -1e9, x)
    p_eff = jnp.maximum(top_p, 1e-9)[:, None]
    srt2 = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < p_eff
    cutoff = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where((top_p < 1.0)[:, None] & (x < cutoff), -1e9, x)


def slot_sample_keys(seed_lo, seed_hi, ctr):
    """Per-slot sampling keys for the fused dispatches: fold the
    emitted-token counter into each request's seed-derived key
    (core/rng.request_key over the uint32 seed words), so token i
    of a request always draws from fold(request_key, i) — the same
    stream whether it is emitted by a one-token tick, a verify-
    window lane, or the eager first-token pick after prefill.
    seed_lo/seed_hi uint32 [B], ctr int32 [B] -> keys [B]."""
    import jax
    from ..core import rng as rng_mod
    return jax.vmap(lambda lo, hi, c: jax.random.fold_in(
        rng_mod.request_key(lo, hi), c))(seed_lo, seed_hi, ctr)


@_scoped("sampling")
def sample_lanes(last, temperature, top_k, top_p, keys):
    """One token per slot row from [B, V] logits with PER-SLOT
    sampling params and keys: lanes with ``temperature == 0`` (the
    greedy sentinel) take the raw argmax — bit-identical to the
    host path's ``np.argmax`` on the same logits — and sampling
    lanes draw categorically from the lane-filtered distribution.
    The filter/draw pipeline (two [B, V] sorts + categorical) sits
    behind a runtime ``lax.cond``: an all-greedy batch — the
    serving default — skips it entirely instead of computing both
    sides of a where, while staying ONE compiled program.
    Returns int32 [B]."""
    import jax
    import jax.numpy as jnp
    last = last.astype(jnp.float32)
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)

    def draw(_):
        filt = filter_logits_lanes(last, temperature, top_k, top_p)
        sampled = jax.vmap(jax.random.categorical)(keys, filt)
        return jnp.where(temperature > 0, sampled,
                         greedy).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temperature > 0), draw,
                        lambda _: greedy, None)


class KVRowSpec:
    """What a model keeps for one cached position in one layer — the
    one place block bytes, pool shapes and the geometry that
    ``/healthz``, ``/debug/requests``, the migration wire and the host
    tier report are computed from.

    ``rows``: ``((name, shape), ...)``, one pool a layer for each: two
    of ``[H, hd]`` (``"k"``, ``"v"``) for full multi-head attention,
    one of ``[r + d_rope]`` (``"latent"``) for latent attention, whose
    row is the compressed KV and the shared rotary key and which keeps
    no V, one of ``[2 K hd]`` (``"kv"``) for grouped-query attention,
    whose row is K and V of its K heads flat, head-major
    (``models/sdar_moe.py``).  ``dtype`` is the dtype the model
    computes attention in (the pools hold it unless the engine
    quantizes them).  ``heads_axis`` says the first axis of every row
    is attention heads — what a tensor-parallel mesh shards and what
    an int8 pool hangs its scales on; a row without it can do neither.

    THE RULE a row has to keep: **a pool's last two axes must fill
    whole tiles of its dtype** — ``(8, 128)`` of 4 bytes, ``(16, 128)``
    of bf16, stored ``T(8,128)(2,1)`` — or every walk pays for it.
    The lane half: a TPU keeps the minor axis in tiles of ``LANES``
    whatever the program says, and given a minor axis wider than one
    tile that is no multiple of 128 its runtime picks a layout with
    the BLOCK axis minor instead, which every step then transposes
    there and back (a 264 MB copy a layer: PERF.md, PR 28); so a pool
    stores such a row padded up to whole tiles (576 -> 640), which
    costs the same bytes and keeps the pool row-major and updated in
    place.  The sublane half: the axis before the minor one must be
    the block's rows, not a small axis of the row — a ``[4, 128]``
    bf16 row is tiled ``T(4,128)(2,1)``, a quarter of a register, and
    the walk's gather of such blocks ran at 255 GB/s of the memory's
    819, where the same bytes as one flat ``[1024]`` row are fetched
    at 675 and the latent model's 640-wide row at 540 (chip runs,
    PR 36, 35 and 30; ``[16, 128]``, GPT-3 1.3B's row, is exactly one
    tile already).  So a model with few K/V heads declares its row
    flat, and reads a head as a 128-lane slice of the fetched rows.
    WHO READS THE ROWS, by ``slot_attn_core``: on one TPU, for paged
    floating-point pools at heads of 128, a Pallas kernel copies the
    pages where they lie (``"k"``/``"v"`` rows: the whole decode core,
    ``ops/ragged_paged_attn.py``; a flat ``"kv"`` row: a trip of the
    work list, ``ops/gq_walk_trip.py``, a head a static 128-lane slice
    of the VMEM buffer); everywhere else, and for the ``"latent"`` row
    on every platform, XLA's walk gathers whole blocks.
    Block and position bytes count what is stored, padding included:
    they are what a budget buys and what the gauges report
    (``geometry()["rows"]`` has the row as the model wrote it).

    ``block_rows``: ``((name, width), ...)``, what a model keeps once a
    BLOCK and not once a position: ONE pool ``[num_blocks, width]`` for
    each, indexed by the same layer-invariant block id as the cached
    rows (``n_layers`` then counts only the layers that keep ``rows``;
    layers that share a pool lay their parts side by side in its
    width).  The state of a layer whose memory does not grow with the
    context lives there (``models/lfm2_moe.py``: a short convolution's
    last ``L - 1`` inputs, kept as **the tail row of the block that
    holds the position before** — a full block's tail is final and is
    what a prefix hit continues from; a partial block's is its slot's
    own), as an int8 pool's scale row does: it travels with its block
    id through admission, adoption, eviction, preemption and resume,
    and nothing a slot is added.  Such a row is ONE flat axis, so that
    the pool's last two axes are ``(blocks, width)`` and fill whole
    tiles by the rule above; the engine passes the pools as the second
    list every step program takes and returns (where full attention
    has its V pools), donated as the first; a block's bytes count
    them."""

    LANES = 128

    def __init__(self, n_layers, dtype, rows, heads_axis=False,
                 block_rows=()):
        import numpy as np
        self.n_layers = int(n_layers)
        self.dtype = dtype
        self.rows = tuple((str(n), tuple(int(d) for d in shape))
                          for n, shape in rows)
        self.heads_axis = bool(heads_axis)
        if not self.rows:
            raise ValueError("a KVRowSpec keeps at least one row")
        self._row_elems = sum(int(np.prod(self._stored(shape)))
                              for _, shape in self.rows)
        self.block_rows = tuple((str(n), int(width))
                                for n, width in block_rows)
        if self.block_rows and (len(self.rows) != 1 or self.heads_axis):
            raise ValueError(
                "per-block rows take the pools' second list: beside "
                "them a layer keeps ONE flat row a position, not "
                f"{self.rows}")
        self._block_elems = sum(self._stored((width,))[0]
                                for _, width in self.block_rows)

    @classmethod
    def heads(cls, n_layers, num_heads, head_dim, dtype):
        """K and V rows of ``[num_heads, head_dim]``."""
        shape = (int(num_heads), int(head_dim))
        return cls(n_layers, dtype, (("k", shape), ("v", shape)),
                   heads_axis=True)

    @classmethod
    def _stored(cls, shape):
        """``shape`` as a pool stores it (the class docstring)."""
        minor = shape[-1]
        if minor > cls.LANES and minor % cls.LANES:
            minor += -minor % cls.LANES
        return shape[:-1] + (minor,)

    @property
    def num_heads(self):
        return self.rows[0][1][0] if self.heads_axis else None

    @property
    def head_dim(self):
        return self.rows[0][1][1] if self.heads_axis else None

    def pool_shapes(self, leading):
        """Shape of each of a layer's pools behind the ``leading``
        axes (``(num_blocks, block_size)`` paged, ``(num_slots,
        max_seq_len)`` contiguous)."""
        return [tuple(leading) + self._stored(shape)
                for _, shape in self.rows]

    def block_pool_shapes(self, num_blocks):
        """Shape of every per-block pool, in the order of
        ``block_rows``."""
        return [(int(num_blocks),) + self._stored((width,))
                for _, width in self.block_rows]

    def position_bytes(self, dtype=None):
        """Bytes one cached position takes over all layers, as
        stored."""
        import numpy as np
        return (self.n_layers * self._row_elems
                * np.dtype(dtype or self.dtype).itemsize)

    def block_bytes(self, block_size, mp=1, dtype=None,
                    scale_dtype=None):
        """PER-SHARD bytes of ONE logical block across every layer and
        pool (see ``per_shard_block_bytes``).  ``dtype`` is the STORED
        row dtype when it differs from the compute dtype (int8 pools);
        ``scale_dtype`` adds a per-block per-head scale for each
        pool."""
        import numpy as np
        mp = int(mp)
        if mp != 1 or scale_dtype is not None:
            if not self.heads_axis:
                raise ValueError(
                    f"rows {self.rows} have no head axis to shard "
                    "over mp or to scale per head")
            if mp < 1 or self.num_heads % mp:
                raise ValueError(
                    f"num_heads ({self.num_heads}) must divide by mp "
                    f"({mp})")
        total = (int(block_size) * self.position_bytes(dtype)) // mp
        # (per-block rows stay in the model's dtype, as a scale row
        # stays float32)
        total += self._block_elems * np.dtype(self.dtype).itemsize
        if scale_dtype is not None:
            total += (self.n_layers * len(self.rows)
                      * (self.num_heads // mp)
                      * np.dtype(scale_dtype).itemsize)
        return total

    def geometry(self, block_size):
        """The block geometry two peers must agree on before blocks
        move between them, and what the debug surfaces report."""
        geo = {"block_size": int(block_size)}
        if self.heads_axis:
            geo.update(num_heads=self.num_heads, head_dim=self.head_dim)
        else:
            geo["rows"] = [[n, list(shape)] for n, shape in self.rows]
        geo["n_layers"] = self.n_layers
        if self.block_rows:
            geo["block_rows"] = [[n, width]
                                 for n, width in self.block_rows]
        return geo


# cache rows one item of a slot walk fetches (a whole number of
# blocks): at 256 the v5e keeps an item's rows on chip (PERF.md, PR 26)
_WALK_ROWS = 256
# (slot, chunk) items one trip of a decode walk takes, never more than
# there are slots: 32 x 256 rows are what a trip over all 32 slots
# fetched, and a trip of narrow rows costs the same whatever its items
# (PERF.md, PR 30)
_WALK_GROUP = 32
# cached numbers the items of one trip hold at most: 32 items of 256
# rows 1,024 wide.  Past that a trip's time is its rows' way through
# the vector unit, item for item, so a model whose rows are wider takes
# fewer items a trip and pads its last trip with less (PERF.md, PR 43)
_WALK_TRIP_SIZE = 8 << 20


def walk_chunk(table_rows, block_size=None):
    """Rows of one item of a model's walk over a slot's cached rows: a
    whole number of blocks, of the order of ``_WALK_ROWS``, at most the
    table (``block_size`` None: the contiguous layout, which has no
    blocks)."""
    bs = block_size or 1
    return min(table_rows, bs * max(1, _WALK_ROWS // bs))


def walk_group(slots, row_width=None):
    """(slot, chunk) items one trip of the decode walk takes over
    ``slots`` slots whose cache keeps ``row_width`` numbers a position
    (None: rows narrow enough for the whole group)."""
    fit = _WALK_GROUP if row_width is None \
        else max(1, _WALK_TRIP_SIZE // (_WALK_ROWS * row_width))
    return min(_WALK_GROUP, fit, slots)


def _backend():
    """The platform the step programs are traced for (the process's
    default backend)."""
    import jax
    return jax.default_backend()


def _over_a_mesh(use_mp=False):
    """Whether the program now being traced spans several devices: the
    einsum form whose weights carry ``'mp'`` specs, or a mesh its
    builder or the process published (``Engine(mesh=...)``)."""
    from ..distributed import mesh as mesh_mod
    return bool(use_mp) or mesh_mod.program_devices() > 1


def slot_attn_core(platform, *, paged, quant, head_dim, mesh, table_rows,
                   block_size, slots=None):
    """Which form the core of a decode attention's walk takes, from
    what the code can see, and why: ``("kernel", reason)``, a Pallas
    kernel that streams pages through VMEM under its arithmetic, or
    ``("walk", reason)``, the XLA work list.  ONE algorithm, an online
    softmax over each slot's own rows, whose implementation follows
    the platform and the shapes.  Two walks ask: ``GPTAttention
    ._slot_attn`` (the whole core as ``ops/ragged_paged_attn.py``) and
    ``GQAttention.attend`` (a trip of its work list as
    ``ops/gq_walk_trip.py``; it gives its ``slots``: one slot, the
    chunk program, walks its own chunks in turn).  A kernel is compiled
    by Mosaic, so it needs a TPU, paged pools of plain floating point,
    heads of whole 128-lane tiles and a program on one device (GSPMD
    cannot partition a Mosaic call); a table of at most one chunk is
    read whole by either."""
    if not paged:
        return "walk", "contiguous cache: no pages to stream"
    if quant:
        return "walk", "int8 pools: the walk dequantizes at the gather"
    if head_dim % 128:
        return "walk", f"head size {head_dim}: not whole 128-lane tiles"
    if platform != "tpu":
        return "walk", f"platform {platform}: Mosaic compiles for a TPU"
    if mesh:
        return "walk", "a program over a mesh: GSPMD cannot partition " \
            "a Mosaic call"
    if table_rows <= walk_chunk(table_rows, block_size):
        return "walk", "a table of one chunk is read whole"
    if slots is not None and slots < 2:
        return "walk", "one slot walks its own chunks in turn"
    return "kernel", "paged floating-point pools on one TPU"


def walk_first(pos, reach, chunk):
    """The first chunk a slot's walk fetches where a query sees only
    the ``reach`` rows that end with its own: the earliest query of the
    window stands at ``pos`` and sees rows ``> pos - reach``.  Works on
    traced and on host integers alike."""
    return (pos + 1 - reach).clip(0) // chunk


def walk_plan(pos, window, table_rows, chunk, group, reach=None):
    """The decode walk's work list, built on the device from ``pos``
    alone: slot b gets ``n_b = ceil((pos_b + window) / chunk)`` items,
    one for each ``chunk`` rows its queries see (rows ``< pos_b +
    window``), and none at position 0 (a parked lane); the items lie
    slot by slot in chunk order, by a cumulative sum.  With a
    ``reach`` (a sliding-window layer: a query sees the ``reach`` rows
    that end with its own) slot b's items start at chunk
    ``walk_first(pos_b)`` and not at 0, and the list is as much
    shorter.

    pos int32 [B]; the rest static.  Returns ``(slot_of, chunk_of,
    valid, n_trips)``: three arrays of the static length ``B *
    ceil(table_rows / chunk)`` (with a ``reach``: ``B`` times the most
    chunks ``reach + window - 1`` rows can touch) rounded up to whole
    trips of ``group`` items (item i is chunk ``chunk_of[i]`` of slot
    ``slot_of[i]``; items past the list's end are not ``valid``), and
    the data trip count ``ceil(sum(n_b) / group)``.  One program for
    every list."""
    import jax.numpy as jnp
    n_chunks = -(-table_rows // chunk)
    n = jnp.where(pos > 0, jnp.minimum(
        (pos + window + chunk - 1) // chunk, n_chunks), 0).astype(jnp.int32)
    if reach is not None:
        first = jnp.minimum(walk_first(pos, reach, chunk), n)
        n = n - first
        n_chunks = min(n_chunks, (reach + window - 2) // chunk + 2)
    ends = jnp.cumsum(n)                                          # [B]
    size = pos.shape[0] * n_chunks
    item = jnp.arange(size + -size % group, dtype=jnp.int32)
    valid = item < ends[-1]
    # the slot of item i is the first whose items end past i
    slot_of = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1,
                                  dtype=jnp.int32), pos.shape[0] - 1)
    chunk_of = jnp.where(valid, item - (ends - n)[slot_of], 0)
    if reach is not None:
        chunk_of = jnp.where(valid, chunk_of + first[slot_of], 0)
    return slot_of, chunk_of, valid, (ends[-1] + group - 1) // group


def walk_rows(pos, ahead, table_rows, block_size, row_width=None,
              reach=None, padded=True):
    """Host twin of ``walk_plan`` for the engine's counters
    (``ServingSpec.decode_rows``): the cache rows one decode dispatch
    fetches over all slots when slot b's window ends at ``pos[b] +
    ahead`` — ``trips x group x chunk``, the last trip's padding items
    included (``row_width`` as ``walk_group`` takes it; ``reach`` as
    ``walk_plan`` does), or ``items x chunk`` where not ``padded`` (a
    trip that is a kernel copies nothing for a padding item,
    ``ops/gq_walk_trip.py``); a table of at most one chunk is read
    whole by every slot."""
    import numpy as np
    pos = np.asarray(pos, np.int64)
    chunk = walk_chunk(table_rows, block_size)
    if table_rows <= chunk or len(pos) == 1:
        # one trip over every slot; one slot walks as the chunk
        # program does, to the end of its own window
        end = max(1, min(int(pos.max()) + ahead, table_rows))
        last = -(-end // chunk)
        if reach is not None and table_rows > chunk:
            last -= min(int(walk_first(pos.max(), reach, chunk)), last)
        return len(pos) * min(table_rows, last * chunk)
    group = walk_group(len(pos), row_width)
    live = pos[pos > 0]
    n = -(-np.minimum(live + ahead, table_rows) // chunk)
    if reach is not None:
        n = n - np.minimum(walk_first(live, reach, chunk), n)
    items = int(n.sum())
    return (-(-items // group) * group if padded else items) * chunk


class StepSpec:
    """What one run of a model's ``fused_decode`` program does to a
    lane, where that is not "one row in, one token out" (a model whose
    ``ServingSpec.step`` is None keeps that contract, and its prefill
    picks token 0 from the last prompt position's logits).

    ``rows``     rows a live lane carries through the step: the width
                 of the device's ``tok`` state ``[slots, rows]``, and
                 the most tokens a lane yields a step (0..rows)
    ``align``    positions come in groups of ``align``: a prefill
                 covers ``n // align * align`` prompt tokens and yields
                 NO token, chunks and prefix hits start at multiples of
                 it (``kv_block_size`` and ``prefill_chunk`` are
                 refused otherwise), and rows below a lane's ``pos``
                 are final, so a finished or preempted request's whole
                 blocks below it enter the prefix cache
    ``open``     ``(tail ids) -> (tok row [rows], flags)``: the lane's
                 state when its first step starts at ``pos = n // align
                 * align`` with the ``n % align`` prompt tokens the
                 prefill left; ``flags`` is one int32 a lane that the
                 program keeps (0 = a lane that is not stepping: parked
                 or prefilling) and the engine mirrors without reading
    ``report``   what ``/healthz`` says of the step

    The program takes ``flags`` [slots] after ``rem`` and returns its
    new value last.  Its first output is the lane's report, int32
    ``[slots, rows + 4]``: the lane's ``rows`` ids after the step, then
    ``first`` and ``count`` (ids ``[first, first + count)`` are newly
    final in position order with every position before them: the
    tokens to send, the device having cut them at the budget and at an
    EOS), the lane's new ``pos`` and its new ``flags``.  ``rem`` and
    ``ctr`` move by ``count``."""

    def __init__(self, rows, align, open, report=None):
        self.rows, self.align = int(rows), int(align)
        self.open = open
        self.report = dict(report or {})


class ServingSpec:
    """A model's answer to the engine's questions.

    ``kv``             ``KVRowSpec``: pools a layer and
                       the row each keeps for a position
    ``max_positions``  longest sequence the model can place
    ``vocab_size``, ``hidden_size``
    ``tensor_parallel``  the model is the einsum form whose parameters
                       carry ``'mp'`` partition specs
    ``counters``       ``((counter, span_arg | None), ...)``: names of
                       the int32 vector the decode and chunk programs
                       return last; the engine adds each into
                       ``serving.<counter>`` and puts ``span_arg`` on
                       the ``dev.*`` span
    ``unsupported``    ``{feature: the missing piece}`` for the engine
                       features this model cannot honour yet
    ``kernels``        ``{scope: implementation}`` where the model
                       picks one by the backend it finds; ``/healthz``
                       reports it, so that a replica serving on a
                       fallback says so
    ``decode_rows``    ``(pos, ahead, table_rows, block_size) -> int``:
                       the cache rows one XLA decode / verify dispatch
                       fetches, summed over slots, when slot b's window
                       ends at row ``pos[b] + ahead`` of its
                       ``table_rows``-row table (``pos`` the host's
                       mirror, 0 a parked slot; ``block_size`` None for
                       the contiguous layout) — the host twin of the
                       trip count the program reads on the device,
                       behind ``serving.decode_rows_walked``.  Left
                       out: ``walk_rows``, the work list of (slot,
                       chunk) items that reads each slot to its own
                       window's end
    ``attn_core``      ``(paged, quant, table_rows, block_size, slots)
                       -> dict`` of a model whose decode attention has
                       two forms and picks one when it traces
                       (``{"form": "kernel" | "walk", "why": ...,
                       "platform": ..., "head_dim": ...}``): the engine
                       asks it at construction for ``/healthz``
                       ``attn_core`` and
                       ``serving.attn_kernel_dispatches``; None for one
                       that always walks
    ``attn_kernel_check``  ``(num_slots, block_size, blocks_per_slot,
                       num_blocks, dtype, spec_k, device)``: has Mosaic
                       compile the model's kernel for ``device`` at the
                       shapes its decode (and verify) programs will
                       use, running nothing, and raises the compiler's
                       words where it refuses; the engine calls it at
                       construction where ``attn_core`` says "kernel"
    ``attn_kernel_rows``   ``decode_rows``' twin for the dispatches
                       whose core is the kernel, which fetches less
                       than the XLA walk (GPT: ``stream_rows``, a live
                       slot's own pages; a grouped-query model: the
                       work list's items and no padding item)
    ``step``           ``StepSpec`` of a model whose step is not one
                       row and one token a lane; None for one that is
    ``residual``       what ``/healthz`` says of a residual that is not
                       one ``[hidden_size]`` row a position (``{"streams":
                       n, "sinkhorn_iters": k}``: the streams live
                       inside the step programs, the engine never sees
                       them); None for one that is
    ``attention``      what ``/healthz`` says of a model whose layers
                       do not all see the whole context (``{"window":
                       w, "layers": {"sliding": n, "full": m}}``: the
                       window lives in the step programs' walks and
                       masks, the engine keeps every row of every
                       layer in ONE block table a slot); None for one
                       whose layers are of one kind
    ``experts``        what ``/healthz`` says of a model that holds a
                       share of its routed experts (``{"held": [first,
                       count], "of": the router's width}``); None for
                       one that holds them all or has none
    ``state``          what ``/healthz`` says (``layer_state``: ``state``
                       is the replica's own, "ok" / "draining") of a
                       model some of whose layers keep a state and no
                       cached row (``{"conv":
                       [L - 1, d], "layers": {"conv": n, "attention":
                       m}, "per": "block"}``: the state lives in
                       ``kv.block_rows``' pools, the engine moves block
                       ids and never sees it); None for one whose
                       layers all keep rows
    """

    def __init__(self, kv, max_positions, vocab_size, hidden_size,
                 tensor_parallel=False, counters=(), unsupported=None,
                 kernels=None, decode_rows=None, step=None,
                 residual=None, attention=None, experts=None,
                 state=None, attn_core=None, attn_kernel_check=None,
                 attn_kernel_rows=None):
        self.kv = kv
        self.max_positions = int(max_positions)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.tensor_parallel = bool(tensor_parallel)
        self.counters = tuple(counters)
        self.unsupported = dict(unsupported or {})
        self.kernels = dict(kernels or {})
        self.decode_rows = decode_rows or walk_rows
        self.attn_core = attn_core
        self.attn_kernel_check = attn_kernel_check
        self.attn_kernel_rows = attn_kernel_rows
        self.step = step
        self.residual = dict(residual) if residual else None
        self.attention = dict(attention) if attention else None
        self.experts = dict(experts) if experts else None
        self.state = dict(state) if state else None


class ServedModel:
    """Mixin of a model the engine can serve (see the module's
    docstring)."""

    def serving_spec(self):
        raise NotImplementedError

    def serving_linear_stacks(self):
        raise NotImplementedError

    def serving_program(self, kind, *args, **kwargs):
        """The jitted program of ``kind``: ``(fn, bnames, mbuffers)``
        from the builder ``_compiled_<kind>_fn``."""
        build = getattr(self, f"_compiled_{kind}_fn", None)
        if build is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no {kind!r} step program")
        return build(*args, **kwargs)

    def _program(self, kind, cache_key, params, pnames, body,
                 donate=(2, 3)):
        """Build once a ``cache_key`` the jitted ``body`` run with the
        traced parameters and buffers swapped in."""
        from ..core import autograd
        from ..jit import _swapped
        cache = self.__dict__.setdefault("_program_cache", {})
        if (kind, cache_key) in cache:
            return cache[kind, cache_key]
        mbuffers = dict(self.named_buffers())
        bnames = sorted(mbuffers)

        def pure(p_list, b_list, *args):
            with _swapped(params, dict(zip(pnames, p_list))), \
                    _swapped(mbuffers, dict(zip(bnames, b_list))):
                with autograd.no_grad():
                    return body(*args)

        fn = _jit_named(kind, pure, donate_argnums=donate)
        if len(cache) >= 8:
            cache.pop(next(iter(cache)))
        cache[kind, cache_key] = (
            self._compile_probe(kind, cache_key, fn), bnames, mbuffers)
        return cache[kind, cache_key]

    def add_compile_listener(self, cb):
        """Register ``cb(kind, cache_key, wall_s)`` to fire right after
        the FIRST call of each freshly built jitted program (the call
        where jax traces and XLA compiles it).  Production-side
        compile-thrash detector: the serving engine turns every event
        into a trace span plus the ``serving.compiles_total`` counter,
        so a traffic shape that defeats the program caches is visible
        in /metrics instead of only as mystery latency.  A callback
        that returns False (or raises) is deregistered — the engine
        registers a weakref'd method so a collected engine drops off
        this list by itself."""
        listeners = getattr(self, "_compile_listeners", None)
        if listeners is None:
            listeners = self._compile_listeners = []
        listeners.append(cb)
        return cb

    def remove_compile_listener(self, cb):
        try:
            getattr(self, "_compile_listeners", []).remove(cb)
        except ValueError:
            pass

    def _compile_probe(self, kind, cache_key, fn):
        """Wrap a freshly jitted dispatch so its first call is timed
        and announced to ``add_compile_listener`` subscribers; later
        calls pay one truthiness check.  The wall time covers trace +
        XLA compile + the first execution — on a cache-warm process the
        event simply never fires, which is exactly the signal: events
        appearing in steady state mean the program cache is thrashing."""
        done = []
        first_lock = threading.Lock()
        model = self

        def probed(*args):
            if done:
                return fn(*args)
            t0 = time.perf_counter()
            out = fn(*args)
            wall = time.perf_counter() - t0
            with first_lock:
                if done:
                    # two threads raced the same cold program (sibling
                    # engines over one model): exactly ONE fires the
                    # event — the loser piggybacked on jax's compile
                    # lock and must not double-count the compile
                    return out
                done.append(True)
            listeners = getattr(model, "_compile_listeners", None)
            if listeners:
                for cb in list(listeners):
                    try:
                        alive = cb(kind, cache_key, wall)
                    except Exception:
                        alive = False
                    if alive is False:
                        try:
                            listeners.remove(cb)
                        except ValueError:
                            pass
            return out

        probed.kind = kind  # the engine labels its dev.* spans with it
        probed.__name__ = "gpt_" + kind
        return probed
