"""Paged KV-cache: block pool, refcounted copy-on-write, prefix reuse.

PR 1's engine reserves one contiguous ``max_seq_len`` K/V row per slot:
HBM is held for the worst case of every request, and identical prompt
prefixes (system prompts, few-shot headers) are recomputed and stored
once PER REQUEST.  This module is the block-granular fix (the Ragged
Paged Attention direction, PAPERS.md 2604.15464): the engine's K/V
pools are carved into fixed-size blocks, a slot's logical cache row is
the gather of its BLOCK TABLE, identical prefixes share physical
blocks (refcounted, copy-on-write), and finished prompts stay resident
in a token-trie ``PrefixCache`` so later requests skip prefill for the
shared span — with LRU eviction returning blocks under pool pressure.

Host-side METADATA only: the engine owns the device arrays (the same
split as Scheduler vs Engine), and block ids here are row indices into
the engine's per-layer ``[num_blocks, block_size, H, hd]`` pools (one
id indexes every layer — the table is layer-invariant).  Everything is
driven from the single engine loop thread, so no locking (``submit``
never touches the cache).

Reference protocol (who holds how many refs on a block):

* ``alloc`` hands blocks out at refcount 1 — the allocating slot's ref.
* ``PrefixCache.insert`` takes ONE extra ref per newly registered
  block (the cache's own); already-cached spans are left alone.
* ``PrefixCache.match`` takes one ref per matched block ON BEHALF OF
  the adopting slot.
* Slot eviction decrefs every block in the slot's table exactly once;
  blocks that were cached drop to the cache's ref and stay resident,
  decode-span blocks drop to 0 and return to the free list.
* ``evict`` drops cache refs (LRU, unreferenced leaves first) until
  enough blocks free up.

Cursor-rewind invariant (speculative decoding, serving/spec.py): a
slot's KV WRITE CURSOR (``Slot.pos``) may move backward relative to
rows already written — the verify window writes k+1 rows but the
engine advances the cursor only over accepted lanes.  The block layer
stays entirely out of that loop BY CONSTRUCTION: the admission gate
reserves the worst case INCLUDING the ``spec_k`` window margin, so
every window position (rejected lanes included) lands in blocks the
slot already owns, rejected rows are plain garbage inside an owned
block that the next window overwrites, and rollback therefore never
allocs, frees, or refcounts a block.  Nothing here tracks a cursor —
which is the invariant: no pool state can go stale on a rewind.

Scratch-block / spec-margin writes under ``attn_impl="ragged"``: the
XLA dispatches enforce the invariants above with three separate
mechanisms (parked slots' all-zero tables route writes to the
reserved scratch block — physical row 0; the spec margin absorbs
rejected verify lanes; chunked prefill's ``true_len`` masks pad
lanes into row 0).  The ragged Pallas path folds all three into ONE
KERNEL-SIDE MASKING RULE: every window lane ``s >= width[slot]``
scatters into physical row 0, where ``width`` is the per-slot REAL
window width carried as kernel data (0 for a parked slot, the chunk
length for a prefill lane, k+1 for a verify window whose rejected
lanes still land inside the reserved margin).  The pool-layer
contract is unchanged — no live request ever reads row 0, and no
write ever touches a block the slot does not own — it is simply
enforced in one place (``GPTAttention.ragged_window_paged`` +
ops/ragged_paged_attn.py) instead of three.  The kernel's READ side
walks a slot's table only up to the lane's causal horizon
``ceil((pos + width) / block_size)`` and masks per streamed block;
scratch-row writes, the spec margin, and block ownership are enforced
BEFORE the kernel by the width mask, so the kernel body never decides
which blocks are written or which garbage is visible.

Cross-replica block migration (PR 13): because blocks are fixed-size,
refcounted, and layer-invariant, moving a live stream between replicas
is a BLOCK-TABLE REWRITE plus a bytes transfer — ``export_blocks``
gathers the named rows out of the per-layer device pools into one host
array (only the exported blocks cross d2h, never the pool), and
``import_blocks`` scatters them into freshly allocated rows on the
destination, whose pool/trie then adopt the refs through the normal
``alloc`` / ``PrefixCache.insert`` protocol.  ``payload_to_json`` /
``payload_from_json`` are the wire codec (base64 over the HTTP
transport).  The engine-side choreography — ring drain, slot freeze,
resume snapshot — lives in serving/engine.py (``migrate_out`` /
``migrate_in``); this module stays pure bytes + ids.

Quantized pools (PR 16, ``Engine(kv_dtype="int8")``): each per-layer
pool becomes a ``serving/quant.py`` ``QuantKV`` — int8 codes
``[num_blocks, block_size, H, hd]`` plus a PARALLEL SCALE POOL of
per-block per-head f32 dequant multipliers ``[num_blocks, H]``.  The
scale pool obeys three invariants on top of the protocol above:

* ONE scale row per physical block per layer per K/V — the scale is
  block metadata, indexed by the same layer-invariant block id as the
  codes, so nothing in BlockPool/PrefixCache changes (they track ids,
  not bytes).
* Scales TRAVEL WITH their block: copy-on-write copies the scale row
  alongside the code rows, and the migration wire carries both
  (``export_blocks`` returns ``(codes, scales)`` for quantized pools;
  ``import_blocks`` scatters both; the JSON codec base64s each).
* Shared blocks are never re-quantized: writes only land in a slot's
  own fresh blocks (the same full-block-adoption rule that makes cow
  degenerate to no-copy), so a block's scale is IMMUTABLE while its
  refcount is shared — adopters always read exactly the scale the
  producer wrote.

Per-block rows (PR 46, ``models/programs.py`` ``KVRowSpec.block_rows``):
a served model some of whose layers keep a state and no cached row
(``models/lfm2_moe.py``: a short convolution's last inputs) keeps it the
same way, as block metadata: ``[num_blocks, width]`` pools in the
engine's second list, ONE tail row a physical block a layer under the
same layer-invariant block id.  The state before position p is the tail
of the block that holds p - 1; a full block's tail is final and immutable
while shared (what an adopter continues from), a partial block is its
slot's own.  BlockPool and PrefixCache are unchanged: they track ids.
``export_blocks`` / ``import_blocks`` and the host tier move rows only,
so such a model refuses migration and offload by name; a caller of
``BlockPool.cow`` that copies a block's rows copies its tail with them.

``import_blocks`` raises ``KVDtypeMismatch`` when the payload and the
destination pools disagree about quantization (codes into fp pools,
fp rows into quantized pools) BEFORE any geometry check — a
dtype-mismatched migration must adopt nothing, with a reason the
HTTP layer can surface machine-readably.

The invariant tests live in tests/test_kvcache.py (pool/trie),
tests/test_ragged_attn.py (kernel-side masking), and
tests/test_quant_serving.py (scale-pool parity + migration).
"""
from __future__ import annotations

from ..models.programs import KVRowSpec  # noqa: F401  (the row spec
#   is the model's to give; block bytes are counted there)


class KVDtypeMismatch(ValueError):
    """Migration payload and destination pools disagree about KV
    quantization (int8 codes vs fp rows) — the import must adopt
    nothing.  Subclasses ValueError so pre-quantization callers that
    caught geometry errors keep working; the HTTP layer maps it to a
    machine-readable ``reason: "kv_dtype_mismatch"``."""


def _is_quant_pool(pool):
    return hasattr(pool, "codes") and hasattr(pool, "scale")


def export_blocks(k_pools, v_pools, block_ids):
    """Gather the device rows of ``block_ids`` from the engine's
    per-layer paged pools into ONE host array — the bytes half of a
    migration (``Engine.migrate_out`` wraps it with the request's
    resume snapshot).

    ``k_pools`` / ``v_pools``: per-layer pool arrays, each
    ``[num_blocks, block_size, H, hd]``.  ``block_ids``: the
    layer-invariant physical rows to export, in table order (a slot's
    FULL blocks only — the partial tail is recomputed by the
    destination's own prefill).  Returns a numpy array of shape
    ``(n_layers, 2, n_blocks, block_size, H, hd)`` with axis 1 = (K,
    V); the row indexing runs ON DEVICE so only the exported blocks
    cross the d2h boundary, never the whole pool.

    Quantized pools (``QuantKV``) return a ``(codes, scales)`` PAIR:
    the int8 codes in the shape above plus their per-block per-head
    scales ``(n_layers, 2, n_blocks, H)`` — scales travel with their
    blocks, in the same table order."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ids = jnp.asarray([int(b) for b in block_ids], jnp.int32)
    if _is_quant_pool(k_pools[0]):
        codes = [jnp.stack((jnp.take(kp.codes, ids, axis=0),
                            jnp.take(vp.codes, ids, axis=0)))
                 for kp, vp in zip(k_pools, v_pools)]
        scales = [jnp.stack((jnp.take(kp.scale, ids, axis=0),
                             jnp.take(vp.scale, ids, axis=0)))
                  for kp, vp in zip(k_pools, v_pools)]
        return (np.asarray(jax.device_get(jnp.stack(codes))),
                np.asarray(jax.device_get(jnp.stack(scales))))
    parts = [jnp.stack((jnp.take(kp, ids, axis=0),
                        jnp.take(vp, ids, axis=0)))
             for kp, vp in zip(k_pools, v_pools)]
    return np.asarray(jax.device_get(jnp.stack(parts)))


def import_blocks(k_pools, v_pools, block_ids, data, scales=None):
    """Scatter an ``export_blocks`` array into rows ``block_ids`` of
    the destination's per-layer pools.  Returns new ``(k_pools,
    v_pools)`` lists — jax arrays are immutable, so the engine
    reassigns its pool references (safe between dispatches: the
    decode/prefill programs re-bind the pools at every dispatch).
    Raises ``KVDtypeMismatch`` when payload and pools disagree about
    quantization (checked FIRST — int8 codes must never be scattered
    into fp pools as if they were activations, nor fp rows adopted
    without scales), and plain ValueError when the geometry does not
    match (block size / heads / head_dim / layer count) — either way
    the caller rolls its fresh allocation back, adopting NOTHING.

    ``scales``: the per-block per-head scale array that
    ``export_blocks`` returned alongside quantized codes,
    ``(n_layers, 2, n_blocks, H)``; required iff the destination
    pools are ``QuantKV``."""
    import jax.numpy as jnp
    import numpy as np
    quant = _is_quant_pool(k_pools[0])
    if quant and scales is None:
        raise KVDtypeMismatch(
            "destination pools are int8-quantized (kv_dtype='int8') "
            "but the migration payload carries no scales — refusing "
            "to adopt fp rows into a quantized pool")
    if not quant and scales is not None:
        raise KVDtypeMismatch(
            "migration payload carries int8 codes + scales but the "
            "destination pools are fp (kv_dtype mismatch between "
            "peers) — refusing to adopt")
    data = np.asarray(data)
    ids = [int(b) for b in block_ids]
    want = (len(k_pools), 2, len(ids)) + tuple(k_pools[0].shape[1:])
    if tuple(data.shape) != want:
        raise ValueError(
            f"migration payload shape {tuple(data.shape)} does not "
            f"match destination pools (want {want}: layers x (K,V) x "
            "blocks x block_size x heads x head_dim)")
    idx = jnp.asarray(ids, jnp.int32)
    if quant:
        from .quant import QuantKV
        scales = np.asarray(scales)
        want_s = (len(k_pools), 2, len(ids), k_pools[0].shape[2])
        if tuple(scales.shape) != want_s:
            raise ValueError(
                f"migration scale shape {tuple(scales.shape)} does "
                f"not match destination scale pools (want {want_s}: "
                "layers x (K,V) x blocks x heads)")
        new_k, new_v = [], []
        for li, (kp, vp) in enumerate(zip(k_pools, v_pools)):
            new_k.append(QuantKV(
                kp.codes.at[idx].set(
                    jnp.asarray(data[li, 0], kp.codes.dtype)),
                kp.scale.at[idx].set(
                    jnp.asarray(scales[li, 0], kp.scale.dtype))))
            new_v.append(QuantKV(
                vp.codes.at[idx].set(
                    jnp.asarray(data[li, 1], vp.codes.dtype)),
                vp.scale.at[idx].set(
                    jnp.asarray(scales[li, 1], vp.scale.dtype))))
        return new_k, new_v
    new_k, new_v = [], []
    for li, (kp, vp) in enumerate(zip(k_pools, v_pools)):
        new_k.append(kp.at[idx].set(jnp.asarray(data[li, 0], kp.dtype)))
        new_v.append(vp.at[idx].set(jnp.asarray(data[li, 1], vp.dtype)))
    return new_k, new_v


def payload_to_json(payload):
    """JSON-encode a migration payload for the HTTP wire: the
    ``kv["data"]`` numpy array becomes base64 bytes + dtype + shape
    (``data_b64`` / ``data_dtype`` / ``data_shape``), and a quantized
    payload's ``kv["scales"]`` likewise (``scales_b64`` / ...) —
    scales travel with their blocks over the wire.  Everything else
    in the payload is already JSON-shaped.  ``payload_from_json``
    inverts exactly."""
    import base64
    import numpy as np
    out = {k: v for k, v in payload.items() if k != "kv"}
    kv = payload.get("kv")
    if kv is not None:
        kv = dict(kv)
        for field in ("data", "scales"):
            arr = kv.pop(field, None)
            if arr is not None:
                arr = np.ascontiguousarray(arr)
                kv[f"{field}_b64"] = base64.b64encode(
                    arr.tobytes()).decode("ascii")
                kv[f"{field}_dtype"] = str(arr.dtype)
                kv[f"{field}_shape"] = list(arr.shape)
        out["kv"] = kv
    return out


def payload_from_json(obj):
    """Decode a ``payload_to_json`` wire dict back into the in-memory
    payload form (``kv["data"]`` — and ``kv["scales"]`` for
    quantized payloads — as writable numpy arrays)."""
    import base64
    import numpy as np
    out = {k: v for k, v in obj.items() if k != "kv"}
    kv = obj.get("kv")
    if kv is not None:
        kv = dict(kv)
        for field in ("data", "scales"):
            b64 = kv.pop(f"{field}_b64", None)
            if b64 is not None:
                dtype = np.dtype(kv.pop(f"{field}_dtype"))
                shape = tuple(kv.pop(f"{field}_shape"))
                kv[field] = np.frombuffer(
                    base64.b64decode(b64),
                    dtype=dtype).reshape(shape).copy()
        out["kv"] = kv
    return out


def per_shard_block_bytes(block_size, num_heads, head_dim, dtype,
                          n_layers, mp=1, scale_dtype=None):
    """PER-SHARD HBM cost of ONE logical KV block across every layer:
    ``n_layers * 2 (K and V) * block_size * (num_heads/mp) * head_dim
    * itemsize``.  Under a tensor-parallel mesh (Engine(mesh=...))
    the pools shard on the head axis, so each device stores only its
    ``num_heads/mp`` heads' slice of every block — which is why a
    fixed per-chip budget (``Engine(kv_budget_mb=...)``) buys ``mp``x
    the logical blocks: KV capacity, the HBM ceiling on concurrent
    slots, scales with the mesh.  ``num_heads`` must divide by ``mp``
    (attention shards whole heads).

    ``dtype`` is the STORED row dtype — int8 for a quantized pool
    (``Engine(kv_dtype="int8")``), in which case ``scale_dtype``
    (f32) adds the parallel scale pool's ``n_layers * 2 *
    (num_heads/mp)`` per-block per-head multipliers, so the quoted
    cost is the block's TRUE footprint and the int8/f32 capacity
    ratio works out to ``4 / (1 + 4/(block_size*head_dim))`` (~3.8x
    for the small test geometries, ~4x at real ones) instead of a
    flattering byte-only 4x.

    The K/V-heads case of ``KVRowSpec.block_bytes``, which is where
    the bytes are counted."""
    return KVRowSpec.heads(n_layers, num_heads, head_dim,
                           dtype).block_bytes(
        block_size, mp=mp, scale_dtype=scale_dtype)


class NoFreeBlocks(RuntimeError):
    """The pool cannot satisfy an allocation (even after eviction)."""


def _as_ids(blocks):
    if isinstance(blocks, int):
        return (blocks,)
    return blocks


class BlockPool:
    """Fixed-size-block allocator over the engine's K/V pool rows.

    ``reserved_blocks`` low ids are never handed out — the engine pins
    row 0 as the scratch block that parked (inactive) slots harmlessly
    read and write through.

    ``fault_hook`` (optional): called with the request size before
    every ``alloc`` — the deterministic chaos harness
    (serving/faults.py) threads its "pool_exhaust" site through it,
    raising ``NoFreeBlocks`` on scheduled ticks so recovery paths are
    exercised against pool pressure that composes with other
    failures.  None (default) costs nothing.

    ``shards`` (data-parallel serving, ``Engine(mesh=(mp, dp))``): the
    pool rows divide into ``shards`` CONTIGUOUS equal ranges, one per
    'dp' mesh shard — shard ``d`` owns global rows ``[d*rps,
    (d+1)*rps)`` where ``rps = num_blocks // shards`` — and every
    range reserves its own ``reserved_blocks`` leading rows (shard
    ``d``'s scratch row is ``scratch_row(d) = d*rps``), so a parked
    slot's masked writes stay INSIDE its own shard's pool slice (the
    shard_map kernel instance cannot address another shard's rows).
    ``alloc(n, shard=d)`` draws only from shard ``d``'s free list and
    ``decref`` returns a freed block to its OWN shard; a block never
    migrates between shards because the device pool is physically
    split at exactly these row boundaries.  ``shards=1`` (default) is
    bit-identical to the unsharded pool.
    """

    def __init__(self, num_blocks, block_size, reserved_blocks=0,
                 fault_hook=None, shards=1):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        shards = int(shards)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if num_blocks % shards:
            raise ValueError(
                f"num_blocks ({num_blocks}) must divide into {shards} "
                "equal dp shard ranges")
        rps = num_blocks // shards
        if rps - reserved_blocks < 1:
            raise ValueError(
                f"pool needs at least one allocatable block per shard "
                f"({num_blocks} total / {shards} shard(s), "
                f"{reserved_blocks} reserved each)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.reserved_blocks = int(reserved_blocks)
        self.shards = shards
        self.rows_per_shard = rps
        # pop() from the tail hands out low ids first (stable tests)
        self._free = [list(range(d * rps + rps - 1,
                                 d * rps + reserved_blocks - 1, -1))
                      for d in range(shards)]
        self._ref = [0] * self.num_blocks
        self._fault_hook = fault_hook

    @property
    def managed_blocks(self):
        return self.num_blocks - self.shards * self.reserved_blocks

    def shard_of(self, block):
        """The dp shard whose pool range holds global row ``block``."""
        return int(block) // self.rows_per_shard

    def scratch_row(self, shard=0):
        """Global row id of ``shard``'s reserved scratch block (the
        first row of its range) — parked slots' tables point here."""
        if not self.reserved_blocks:
            raise ValueError("pool has no reserved scratch rows")
        return int(shard) * self.rows_per_shard

    def free_count(self, shard=None):
        if shard is None:
            return sum(len(f) for f in self._free)
        return len(self._free[shard])

    def in_use(self):
        return self.managed_blocks - self.free_count()

    def refcount(self, block):
        return self._ref[block]

    def alloc(self, n, shard=0):
        """Take ``n`` blocks off ``shard``'s free list at refcount 1."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if self._fault_hook is not None:
            self._fault_hook(n)  # chaos harness: may raise NoFreeBlocks
        free = self._free[shard]
        if n > len(free):
            raise NoFreeBlocks(
                f"need {n} blocks, only {len(free)} free of "
                f"{self.managed_blocks // self.shards} on dp shard "
                f"{shard} (evict cached prefixes first)")
        out = [free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks):
        for b in _as_ids(blocks):
            if self._ref[b] < 1:
                raise RuntimeError(
                    f"incref on free block {b} — a reference can only "
                    "be shared from a live one")
            self._ref[b] += 1

    def decref(self, blocks):
        """Drop one reference per block; blocks reaching refcount 0
        return to the free list.  Returns the freed ids."""
        freed = []
        for b in _as_ids(blocks):
            if self._ref[b] < 1:
                raise RuntimeError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free[self.shard_of(b)].append(b)
                freed.append(b)
        return freed

    def cow(self, block):
        """Copy-on-write: make the caller's reference to ``block``
        privately writable.  Sole owner -> the block itself (no copy).
        Shared -> the caller's ref moves to a fresh block and the
        caller must copy the device rows (and the block's scale row or
        tail, where the pools have one: module docstring); returns
        ``(writable_block, needs_copy)``.  Raises NoFreeBlocks with the original ref
        intact if the pool is empty (evict, then retry).

        The serving engine adopts cached prefixes at FULL-block
        granularity and writes only into freshly allocated blocks, so
        its steady state never needs the copy — this is the general
        primitive (partial-block adoption, future mutation paths).
        """
        if self._ref[block] < 1:
            raise RuntimeError(f"cow of free block {block}")
        if self._ref[block] == 1:
            return block, False
        # before decref: failure leaves the shared ref untouched; the
        # replacement comes from the block's OWN shard range
        new = self.alloc(1, shard=self.shard_of(block))[0]
        self._ref[block] -= 1
        return new, True


class _TrieNode:
    __slots__ = ("key", "block", "parent", "children", "last_used")

    def __init__(self, key, block, parent, last_used):
        self.key = key
        self.block = block
        self.parent = parent
        self.children = {}
        self.last_used = last_used


class PrefixCache:
    """Token-trie over FULL blocks of previously-seen prompts.

    Each node covers one block's worth of token ids; node depth i
    means "positions [i*bs, (i+1)*bs) of some prompt", and its block
    holds the K/V computed for exactly that token prefix — so an
    adopter walking the trie from the root gets blocks whose content
    is what its own prefill would have produced for the shared span.
    Partial blocks are never cached (the engine trims matches to block
    boundaries), which keeps adoption pure sharing: writes always land
    in the adopter's own fresh blocks (``BlockPool.cow`` degenerates
    to the no-copy case).

    ``evict_hook`` (optional): called as ``hook(tokens, block)`` for
    every node ``evict`` is about to drop, BEFORE the pool reference
    — ``tokens`` is the node's full token prefix (root through the
    dying block, reconstructed from the parent chain), so the hook can
    demote the block's device rows to a content-addressed host tier
    (serving/offload.py) while they are still resident.  Exceptions
    are swallowed: a failed demote must free the block normally, never
    wedge eviction mid-walk (``clear`` — the engine-reset path whose
    device pools may already be gone — never calls it).

    Data-parallel pools (``BlockPool(shards=dp)``) get ONE TRIE PER
    SHARD: a slot can only gather blocks inside its own dp shard's
    pool range, so a cached prefix is only adoptable by slots of the
    shard that computed it.  ``match(tokens, shard=d)`` walks shard
    ``d``'s trie; ``match(tokens)`` (shard=None) probes every shard
    and adopts from the one with the longest cached span (the
    cross-shard lookup the prefix-warm service uses).  ``insert``
    routes to the trie of the shard that owns ``blocks[0]``.
    """

    def __init__(self, pool, evict_hook=None):
        self.pool = pool
        self.block_size = pool.block_size
        self.evict_hook = evict_hook
        # one root per dp pool shard: key tuple -> _TrieNode
        self._roots = [dict()
                       for _ in range(getattr(pool, "shards", 1))]
        self._clock = 0       # LRU stamp (monotonic counter)

    def _tick(self):
        self._clock += 1
        return self._clock

    @staticmethod
    def _iter_root(root):
        stack = list(root.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            yield node

    def _iter_nodes(self):
        for root in self._roots:
            yield from self._iter_root(root)

    def cached_blocks(self):
        return sum(1 for _ in self._iter_nodes())

    def _walk(self, tokens, root, limit, stamp=None):
        blocks = []
        children = root
        for i in range(limit):
            key = tuple(int(x) for x in
                        tokens[i * self.block_size:
                               (i + 1) * self.block_size])
            node = children.get(key)
            if node is None:
                break
            if stamp is not None:
                node.last_used = stamp
            blocks.append(node.block)
            children = node.children
        return blocks

    def match(self, tokens, shard=None, keep=1):
        """Longest cached prefix of ``tokens`` in full blocks, capped
        so at least ``keep`` tokens are left for the adopter's own
        prefill: ONE where admission needs a last-position logit to
        sample from, none where the served model's prefill yields no
        token (``models/programs.py`` ``StepSpec``).
        Takes one pool reference per returned block on behalf of the
        caller — release with ``pool.decref`` at slot eviction.
        ``shard`` names the dp shard whose trie to walk (the adopting
        slot's); None probes every shard and adopts from the longest.
        Returns ``(block_ids, matched_token_count)``."""
        limit = (len(tokens) - keep) // self.block_size
        if shard is None:
            shard = 0
            if len(self._roots) > 1:
                shard = max(
                    range(len(self._roots)),
                    key=lambda d: len(self._walk(tokens,
                                                 self._roots[d],
                                                 limit)))
        blocks = self._walk(tokens, self._roots[shard], limit,
                            stamp=self._tick())
        self.pool.incref(blocks)
        return blocks, len(blocks) * self.block_size

    def insert(self, tokens, blocks):
        """Register ``blocks[i]`` as the cached K/V of ``tokens``'s
        i-th FULL block.  Existing nodes win (a duplicate block —
        two same-prefix requests prefilled in the same tick — stays
        slot-private and frees at eviction); each NEW node takes the
        cache's own pool reference.  The target trie is the one of
        the dp shard that owns the blocks (all of one slot's blocks
        live in one shard range by construction)."""
        bs = self.block_size
        if not blocks:
            return
        children = self._roots[self.pool.shard_of(blocks[0])
                               if len(self._roots) > 1 else 0]
        parent = None
        t = self._tick()
        n = min(len(blocks), len(tokens) // bs)
        for i in range(n):
            key = tuple(int(x) for x in tokens[i * bs:(i + 1) * bs])
            node = children.get(key)
            if node is None:
                node = _TrieNode(key, blocks[i], parent, t)
                self.pool.incref(blocks[i])
                children[key] = node
            node.last_used = t
            parent = node
            children = node.children

    @staticmethod
    def _prefix_of(node):
        """The full token prefix ``node``'s block encodes — every
        ancestor's key plus its own, root-first — i.e. the content a
        demote hook must hash to address the block."""
        keys = []
        while node is not None:
            keys.append(node.key)
            node = node.parent
        out = []
        for key in reversed(keys):
            out.extend(key)
        return tuple(out)

    def evict(self, n, shard=None):
        """Free at least ``n`` blocks by dropping least-recently-used
        UNREFERENCED cached prefixes, deepest first (a node with live
        children or an active adopter — pool refcount > 1 — is never
        evicted; evicting a leaf exposes its parent as the next
        candidate).  One trie walk + a heap, not a rescan per freed
        block — eviction runs inside the engine's step loop and must
        not stall decode ticks under sustained pressure.  ``shard``
        restricts the walk to one dp shard's trie (pressure on shard
        ``d`` can only be relieved by shard ``d``'s blocks); None
        evicts across all shards.  Returns the freed block ids (may
        be shorter than ``n`` when nothing evictable remains)."""
        import heapq
        freed = []
        roots = (self._roots if shard is None
                 else [self._roots[shard]])
        heap = [(node.last_used, id(node), node, root)
                for root in roots
                for node in self._iter_root(root)
                if not node.children
                and self.pool.refcount(node.block) == 1]
        heapq.heapify(heap)
        while heap and len(freed) < n:
            _, _, node, root = heapq.heappop(heap)
            if node.children or self.pool.refcount(node.block) != 1:
                continue              # state changed since enqueue
            owner = (node.parent.children if node.parent else root)
            if owner.get(node.key) is not node:
                continue              # already detached
            owner.pop(node.key)
            if self.evict_hook is not None:
                try:
                    self.evict_hook(self._prefix_of(node), node.block)
                except Exception:
                    pass  # failed demote: free normally, never wedge
            freed.extend(self.pool.decref(node.block))
            parent = node.parent
            if parent is not None and not parent.children \
                    and self.pool.refcount(parent.block) == 1:
                heapq.heappush(
                    heap,
                    (parent.last_used, id(parent), parent, root))
        return freed

    def clear(self):
        """Drop every cached prefix (engine reset); returns freed ids."""
        freed = []
        for node in list(self._iter_nodes()):
            freed.extend(self.pool.decref(node.block))
        self._roots = [dict() for _ in self._roots]
        return freed
