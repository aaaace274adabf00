"""The blocked slot-window attention (``GPTAttention._slot_attn``).

The XLA serving path reads each slot's cache a chunk of rows at a
time, in the dtype the cache holds, and each slot only as far as its
OWN window: a work list of (slot, chunk) items built on the device
(``models/programs.py`` ``walk_plan``, the rule the routed models
walk by).  Held here against an independent float32 one-shot oracle
over fully gathered rows (the form the function had before it was
blocked), for every source the four callers hand it: contiguous
buffers, paged block tables, ``QuantKV`` pools.  (What the v5e's
compiler makes of the decode program is held in
``tests/test_ragged_attn.py``, the one file that describes that
chip.)

TOLERANCE (stated before the first run): the blocked walk computes
the same products as the oracle — bf16 x bf16 is exact in float32 —
and differs only in the order of float32 sums (per item, then across
a slot's items), so float32 outputs agree to ``2e-5`` absolute on O(1)
values; a bf16 query makes the output bf16, one rounding of 2**-8
relative, held to ``2e-2``.
"""
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel, programs
from paddle_tpu.models.programs import (
    walk_chunk, walk_group, walk_plan, walk_rows)
from paddle_tpu.serving import Engine
from paddle_tpu.serving.quant import QuantKV, paged_gather, quantize_blocks

B, H, HD, BS = 4, 4, 16, 8
CHUNK = walk_chunk(2048, BS)                 # 256
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.fixture(scope="module")
def attn():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m.blocks[0].attn


def _oracle(attn, qa, k_rows, v_rows, pos):
    """Float32 one-shot attention over fully gathered [B, L, H, hd]
    rows, then the layer's own output projection."""
    qf = np.asarray(qa, np.float32)
    kf = np.asarray(k_rows, np.float32)
    vf = np.asarray(v_rows, np.float32)
    S, L = qf.shape[1], kf.shape[1]
    sc = np.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(qf.shape[3])
    vis = (np.arange(L)[None, None, :]
           <= (np.asarray(pos)[:, None] + np.arange(S)[None, :])[:, :, None])
    sc = np.where(vis[:, None], sc, -1e30).astype(np.float32)
    e = np.exp(sc - sc.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    ctx = np.einsum("bhqk,bkhd->bqhd", probs, vf).astype(np.float32)
    ctx = jnp.asarray(ctx).astype(qa.dtype)
    out = attn.out_proj(paddle.Tensor(ctx.reshape(qf.shape[0], S, -1)))
    return np.asarray(out._data, np.float32)


def _tables(L, rng, slots=B):
    """Distinct physical blocks per slot, shuffled; block 0 is the
    scratch block no table names."""
    nbt = L // BS
    ids = rng.permutation(slots * nbt) + 1
    return ids.reshape(slots, nbt).astype(np.int32), slots * nbt + 1


def _pools(L, rng, dtype, slots=B):
    tables, nb = _tables(L, rng, slots)
    k = rng.standard_normal((nb, BS, H, HD)).astype(np.float32)
    v = rng.standard_normal((nb, BS, H, HD)).astype(np.float32)
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables))


def _gathered(pool, tables):
    return np.asarray(pool, np.float32)[np.asarray(tables)].reshape(
        tables.shape[0], -1, H, HD)


def _run(attn, qa, k_src, v_src, tables, pos):
    out = jax.jit(lambda q, k, v, t, p: attn._slot_attn(
        q, k, v, t, p)._data)(qa, k_src, v_src, tables, pos)
    return np.asarray(out, np.float32)


def _parked_out(attn):
    """What a slot without an item returns: the projection of zeros."""
    return np.asarray(attn.out_proj.bias._data, np.float32)


def _lanes(kind, L, S):
    """``mixed``: a parked lane at 0, a short lane, a lane just past a
    chunk edge, and a lane whose window ends on the table's last row.
    ``parked``: one live lane among parked ones.  ``one``: a batch of
    one slot, which walks its own chunks in a plain loop (and at
    position 0 still sees row 0: no lane of one is parked)."""
    return jnp.asarray({"mixed": [0, 37, CHUNK + 1, L - S],
                        "parked": [0, 0, CHUNK + 70, 0],
                        "one": [CHUNK + 1]}[kind], jnp.int32)


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", ["paged", "contiguous", "quant"])
@pytest.mark.parametrize("L,lanes", [
    (3 * CHUNK, "mixed"), (2 * CHUNK + 40, "mixed"),
    (3 * CHUNK, "parked"), (2 * CHUNK + 40, "one")],
    ids=["whole_chunks", "ragged_tail", "parked_lanes", "batch_of_one"])
def test_blocked_matches_oneshot_oracle(attn, L, lanes, layout, dtype, S):
    rng = np.random.default_rng(L + S)
    dt = jnp.dtype(dtype)
    pos = _lanes(lanes, L, S)
    slots = pos.shape[0]
    qa = jnp.asarray(rng.standard_normal((slots, S, H, HD)), dt)
    k_pool, v_pool, tables = _pools(L, rng, dt, slots)
    if layout == "contiguous":
        k_src = jnp.asarray(_gathered(k_pool, tables), dt)
        v_src = jnp.asarray(_gathered(v_pool, tables), dt)
        k_rows, v_rows, tables = k_src, v_src, None
    elif layout == "quant":
        k_src = QuantKV(*quantize_blocks(k_pool.astype(jnp.float32)))
        v_src = QuantKV(*quantize_blocks(v_pool.astype(jnp.float32)))
        k_rows, v_rows = (paged_gather(k_src, tables),
                          paged_gather(v_src, tables))
    else:
        k_src, v_src = k_pool, v_pool
        k_rows, v_rows = _gathered(k_pool, tables), _gathered(v_pool, tables)
    got = _run(attn, qa, k_src, v_src, tables, pos)
    want = _oracle(attn, qa, k_rows, v_rows, pos)
    live = np.asarray(pos) > 0 if slots > 1 else np.ones(1, bool)
    assert live.any()
    np.testing.assert_allclose(got[live], want[live], atol=TOL[dtype],
                               rtol=0)
    # a parked lane has no item: the projection of zeros, exactly
    np.testing.assert_allclose(
        got[~live], np.broadcast_to(_parked_out(attn), got[~live].shape),
        atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("longest", [CHUNK - 1, CHUNK, 2 * CHUNK - 1, 700],
                         ids=["below_edge", "on_edge", "last_of_chunk",
                              "third_chunk"])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_dead_rows_are_not_read(attn, layout, longest):
    """Every block past EACH slot's own bound holds NaN in K and V, a
    short slot beside the long one and every block of the parked one:
    the result is finite and equal to the clean one (a read to the
    longest window gives 0 x NaN = NaN in the short slots).
    ``longest`` is the long lane's LAST visible row, so ``on_edge``
    (row 256 = the first row of the second chunk) must walk two chunks
    of it and ``below_edge`` only one."""
    L, S = 4 * CHUNK, 1
    rng = np.random.default_rng(longest)
    qa = jnp.asarray(rng.standard_normal((B, S, H, HD)), jnp.float32)
    lanes = [0, 5, longest, 17]
    pos = jnp.asarray(lanes, jnp.int32)
    k_pool, v_pool, tables = _pools(L, rng, jnp.float32)
    bounds = [-(-(p + S) // CHUNK) * CHUNK if p else 0 for p in lanes]
    assert bounds[1] == bounds[3] == CHUNK <= bounds[2] < L
    dead = np.concatenate([np.asarray(tables)[b, bound // BS:]
                           for b, bound in enumerate(bounds)])
    k_nan = k_pool.at[dead].set(jnp.nan)
    v_nan = v_pool.at[dead].set(jnp.nan)
    if layout == "contiguous":
        srcs = [(jnp.asarray(_gathered(k, tables)),
                 jnp.asarray(_gathered(v, tables)), None)
                for k, v in ((k_pool, v_pool), (k_nan, v_nan))]
    else:
        srcs = [(k_pool, v_pool, tables), (k_nan, v_nan, tables)]
    clean, dirty = (_run(attn, qa, k, v, t, pos) for k, v, t in srcs)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    # the row at the bound's last position IS read: poison it instead
    live = np.asarray(tables)[2, longest // BS]
    out = _run(attn, qa, k_pool.at[live].set(jnp.nan), v_pool, tables,
               pos)
    assert np.isnan(out[2]).all()


def test_one_chunk_table_keeps_one_shot_form(attn):
    """A table no longer than a chunk has no loop in its program and
    equals the oracle bit for bit in float32 on this backend."""
    L, S = 64, 1
    rng = np.random.default_rng(3)
    qa = jnp.asarray(rng.standard_normal((B, S, H, HD)), jnp.float32)
    pos = jnp.asarray([0, 9, 33, L - 1], jnp.int32)
    k_pool, v_pool, tables = _pools(L, rng, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, p: attn._slot_attn(
        q, k, v, t, p)._data)(qa, k_pool, v_pool, tables, pos)
    assert "while" not in str(jaxpr)
    got = _run(attn, qa, k_pool, v_pool, tables, pos)
    want = _oracle(attn, qa, _gathered(k_pool, tables),
                   _gathered(v_pool, tables), pos)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


@pytest.mark.parametrize("lanes,S,L,bs,width", [
    ([0, 37, 257, 767], 1, 768, 8, None), ([0, 0, 0, 0], 1, 768, 8, None),
    ([5] * 32, 1, 2048, 16, None), ([2047] * 32, 1, 2048, 16, None),
    ([900] * 16 + [0] * 16, 1, 2048, 16, None),
    ([1, 255, 256, 548], 4, 552, 8, None),
    (list(range(0, 2048, 50)), 3, 2048, None, None),
    ([5000, 3], 1, 600, None, None),    # past the table: clipped
    # rows as wide as gpt3-1.3b's K and V: 8 items a trip
    ([900] * 16 + [0] * 16, 1, 2048, 16, 4096),
    ([700, 40, 0, 1030] + [0] * 28, 1, 2048, 16, 4096),
], ids=["mixed", "all_parked", "short", "full", "half_live", "verify_ragged",
        "contiguous", "clipped", "wide_half_live", "wide_few_live"])
def test_rows_walked_host_twin(lanes, S, L, bs, width):
    """``walk_rows``, which the engine counts by, against the trip
    count the device reads: ``sum(ceil((pos + S) / chunk))`` items over
    the slots that hold a position, every (slot, chunk) pair once and
    in order, ``ceil(items / group)`` trips of ``group`` items."""
    chunk, group = walk_chunk(L, bs), walk_group(len(lanes), width)
    assert chunk == 256 and group == (8 if width else min(32, len(lanes)))
    slot_of, chunk_of, valid, n_trips = (np.asarray(a) for a in walk_plan(
        jnp.asarray(lanes, jnp.int32), S, L, chunk, group))
    n = [min(-(-(p + S) // chunk), -(-L // chunk)) if p else 0
         for p in lanes]
    assert int(valid.sum()) == sum(n)
    assert int(n_trips) == -(-sum(n) // group)
    assert list(zip(slot_of[valid], chunk_of[valid])) == [
        (b, c) for b, k in enumerate(n) for c in range(k)]
    assert walk_rows(np.asarray(lanes), S, L, bs, width) \
        == int(n_trips) * group * chunk
    # a table of one chunk or less is read whole whatever is live, and
    # one slot walks to the end of its own window
    assert walk_rows(np.asarray(lanes), S, 64, bs, width) == len(lanes) * 64
    assert walk_rows(np.asarray(lanes[-1:]), S, L, bs, width) \
        == min(L, -(-min(lanes[-1] + S, L) // chunk) * chunk)


@pytest.mark.parametrize("slots,width,group", [
    (32, None, 32), (4, None, 4), (32, 576, 32), (32, 1024, 32),
    (32, 2048, 16), (32, 4096, 8), (4, 4096, 4), (32, 1 << 22, 1)])
def test_a_trip_is_sized_by_its_rows(slots, width, group):
    """32 items a trip up to rows 1,024 numbers wide (both routed
    models' caches), fewer the wider a position's K and V are, never
    more than there are slots and never less than one."""
    assert walk_group(slots, width) == group


@pytest.mark.parametrize("bs,chunk", [(None, 256), (8, 256), (16, 256),
                                      (48, 240), (512, 512)])
def test_chunk_is_whole_blocks(bs, chunk):
    assert walk_chunk(4096, bs) == chunk
    # never more than the table
    assert walk_chunk(96, bs) == 96


# -- the lowered decode program -------------------------------------

def _walk_avals(jaxpr):
    """Every value of a closed jaxpr, sub-jaxprs (loop bodies, pjit)
    included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_avals(inner)


def _long_model(max_position, **kw):
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0,
                             max_position=max_position, **kw)
    m.eval()
    return m


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_decode_program_holds_no_f32_copy_of_the_table(paged):
    """A bf16 model over a bf16 cache of four chunks: no float32 value
    of B x L x H x hd elements (or more) anywhere in the decode
    step's jaxpr, loop bodies included; the largest float32 value
    with a row axis is one chunk's worth."""
    L, nslots = 4 * CHUNK, 4
    m = _long_model(L)
    m.to(dtype="bfloat16")
    blk = m.blocks[0]
    x = paddle.Tensor(jnp.zeros((nslots, 1, 64), jnp.bfloat16))
    pos = jnp.zeros(nslots, jnp.int32)
    if paged:
        nb = nslots * L // BS + 1
        pool = jnp.zeros((nb, BS, H, HD), jnp.bfloat16)
        tables = jnp.zeros((nslots, L // BS), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda x, k, v, t, p: blk.attn.decode_slots_paged(
                paddle.Tensor(x), k, v, t, p)[0]._data)(
            x._data, pool, pool, tables, pos)
    else:
        buf = jnp.zeros((nslots, L, H, HD), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda x, k, v, p: blk.attn.decode_slots(
                paddle.Tensor(x), k, v, p)[0]._data)(
            x._data, buf, buf, pos)
    assert "while" in str(jaxpr)
    table_elems = nslots * L * H * HD
    f32 = [a for a in _walk_avals(jaxpr.jaxpr)
           if getattr(a, "dtype", None) == jnp.float32]
    assert f32, "no float32 value found: the walk is broken"
    worst = max(int(np.prod(a.shape)) for a in f32)
    assert worst < table_elems, worst
    assert worst <= walk_group(nslots) * CHUNK * H * HD


# -- the item rule has one home ---------------------------------------

_ROUTED = dict(
    vocab_size=128, max_position_embeddings=512, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_experts_per_tok=2, norm_topk_prob=True)
_LATENT = dict(
    _ROUTED, n_shared_experts=2, n_routed_experts=8,
    routed_scaling_factor=2.446, kv_lora_rank=32, q_lora_rank=None,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    first_k_dense_replace=1, rms_norm_eps=1e-5, rope_theta=800000)
_DIFFUSION = dict(block_length=4, denoising_steps=4, mask_token_id=127,
                  remasking_strategy="low_confidence_static")
_BLOCKS = dict(
    _ROUTED, num_key_value_heads=2, head_dim=16, num_experts=8,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    rope_theta=1000000, rope_scaling=None, generation=_DIFFUSION)
# sha256 of the StableHLO text of the step programs below, recorded at
# PR 43's parent, where ``walk_plan`` and its constants lived in
# ``models/mla_moe.py``
_RECORDED = {
    ("latent", "fused_decode"):
        "2fa52c5cf03a0c3f5e7e5481d3c4b91f012e6175d5362973744e104e1d9bda3d",
    ("latent", "paged_chunk_prefill"):
        "ce0f74616790417fae846155532fcac62a0c467d2ea73f9d2fd033dae6b9768e",
    ("blocks", "fused_decode"):
        "3bca81ddf2deb60ed9f37d4f37809d6e7d0001e161b7ff30871743795630e2b5",
    ("blocks", "paged_chunk_prefill"):
        "ff4db20e08fb43154d9eabfd5b56dddcd0cac115fd23fa9b1217219e7ad77f0e",
}


def _lowered(model, monkeypatch):
    """``{kind: sha256 of the lowered text}`` of every step program a
    tiny engine over ``model`` builds for two requests (a table of two
    chunks, three slots: the decode program walks the work list)."""
    seen, jit_named = {}, programs._jit_named

    def spy(kind, pure, **jit_kwargs):
        fn = jit_named(kind, pure, **jit_kwargs)

        def call(*args):
            if kind not in seen:
                seen[kind] = hashlib.sha256(
                    fn.lower(*args).as_text().encode()).hexdigest()
            return fn(*args)
        return call
    monkeypatch.setattr(programs, "_jit_named", spy)
    eng = Engine(model, num_slots=3, max_seq_len=512, kv_block_size=8,
                 kv_blocks=200, prefill_chunk=16,
                 registry=monitor.StatRegistry())
    rng = np.random.default_rng(1)
    for k in (20, 5):
        eng.submit(rng.integers(1, 120, k).tolist(), max_new_tokens=4)
    eng.run_until_idle()
    return seen


@pytest.mark.parametrize("which", ["latent", "blocks"])
def test_routed_programs_lower_to_the_text_they_had(which, monkeypatch):
    """Moving the item rule (``walk_plan``, ``walk_rows``,
    ``walk_chunk``, ``walk_group`` and their two constants) from
    ``models/mla_moe.py`` to ``models/programs.py`` was a move: the
    latent and the block-diffusion model's decode and chunk programs
    lower to the text they lowered to before it.  (A PR that MEANS to
    change one of these programs records its hash anew, from the
    assertion's message, and says so.)"""
    from paddle_tpu.models.mla_moe import MLAMoEModel
    from paddle_tpu.models.sdar_moe import SDARMoEModel
    model = (MLAMoEModel(_LATENT) if which == "latent"
             else SDARMoEModel(_BLOCKS, **_DIFFUSION))
    model.eval()
    assert {(which, kind): text for kind, text
            in _lowered(model, monkeypatch).items()} \
        == {k: v for k, v in _RECORDED.items() if k[0] == which}


# -- engine level: a table of four chunks on a tiny model ------------

LONG = 4 * CHUNK                        # max_seq_len 1,024


@pytest.fixture(scope="module")
def long_gpt():
    return _long_model(LONG)


@pytest.fixture
def _mesh_guard():
    """A sharded engine claims the process-global mesh; restore it."""
    from paddle_tpu.distributed import mesh as mesh_mod
    prev = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(prev)


def _long_prompts():
    """Contexts in the first, second, third and fourth chunk."""
    rng = np.random.RandomState(11)
    return [rng.randint(0, 128, (n,)).astype(np.int32)
            for n in (40, 300, 530, 790)]


def _long_engine(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", LONG)
    kw.setdefault("registry", monitor.StatRegistry())
    return Engine(model, **kw)


def _ref(model, p, n):
    return model.generate(paddle.to_tensor(p[None, :]),
                          max_new_tokens=n).numpy()[0].tolist()


@pytest.fixture(scope="module")
def long_refs(long_gpt):
    return [_ref(long_gpt, p, 6) for p in _long_prompts()]


def _serve(eng, prompts, max_new=6):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle()
    return [r.result(timeout=5).tolist() for r in reqs]


@pytest.mark.parametrize("cfg", [
    {},
    {"kv_block_size": 8},
    {"kv_block_size": 8, "prefill_chunk": 64, "async_depth": 2},
    {"prefill_chunk": 64},
    {"kv_block_size": 8, "spec_k": 3},
    {"spec_k": 3},
    {"kv_block_size": 8, "kv_dtype": "int8", "prefill_chunk": 64},
], ids=["contiguous", "paged", "paged+chunked+depth2", "chunked",
        "paged+spec", "spec", "paged+int8+chunked"])
def test_engine_streams_match_generate_across_chunks(long_gpt, long_refs,
                                                     cfg):
    """Greedy streams whose contexts span one to four chunks of the
    table, through every layout that calls ``_slot_attn``: token
    identical to per-request ``generate()`` (int8 KV: to the int8
    contiguous-free twin of itself, which is all int8 promises)."""
    got = _serve(_long_engine(long_gpt, **cfg), _long_prompts())
    if cfg.get("kv_dtype") == "int8":
        again = _serve(_long_engine(long_gpt, **cfg), _long_prompts())
        assert got == again
        return
    assert got == long_refs


def test_engine_preempt_resume_across_chunks(long_gpt):
    """A long low-priority stream is preempted mid-decode by a short
    high-priority one and resumed through chunked prefill: both equal
    ``generate()``, and the walk shrank while the short one ran."""
    eng = _long_engine(long_gpt, num_slots=1, kv_block_size=8,
                       prefill_chunk=64)
    p_low, p_high = _long_prompts()[2], _long_prompts()[0]
    low = eng.submit(p_low, max_new_tokens=10, priority=0)
    while len(low.generated) < 3:
        eng.step()
    high = eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    assert high.result(timeout=5).tolist() == _ref(long_gpt, p_high, 4)
    assert low.result(timeout=5).tolist() == _ref(long_gpt, p_low, 10)
    assert low.preemptions >= 1


@pytest.mark.mesh
@pytest.mark.parametrize("mesh", [2, (1, 2), (2, 2)],
                         ids=["mp2", "dp2", "mp2xdp2"])
def test_engine_mesh_across_chunks(long_gpt, long_refs, _mesh_guard, mesh):
    """On the virtual devices.  mp=2: heads shard, ``pos`` does not, so
    both shards walk the same list.  dp=2: slots, tables and pool
    blocks shard, and an item's rows come from the shard that owns its
    slot (the partitioner's business: the pool is never collected).
    Streams equal the dense model's ``generate()``."""
    model = _long_model(LONG)
    if mesh != (1, 2):
        model = model.to_tensor_parallel()
    got = _serve(_long_engine(model, mesh=mesh, kv_block_size=8,
                              prefill_chunk=64), _long_prompts())
    assert got == long_refs


def test_one_program_across_chunk_bounds():
    """Contexts that cross three chunk bounds after warm-up compile
    nothing: the trip count is data.  (A model of its own: compiled
    programs are kept on the model, and a sibling test's would leave
    nothing to count.)"""
    reg = monitor.StatRegistry()
    eng = _long_engine(_long_model(LONG), kv_block_size=8,
                       prefill_chunk=64, registry=reg)
    _serve(eng, [_long_prompts()[0]], max_new=3)          # warm-up
    warm = reg.get("serving.compiles_total").value
    assert warm >= 1
    rng = np.random.RandomState(5)
    crossing = [rng.randint(0, 128, (n,)).astype(np.int32)
                for n in (CHUNK - 3, 2 * CHUNK - 3, 3 * CHUNK - 3)]
    _serve(eng, crossing, max_new=8)
    assert reg.get("serving.compiles_total").value == warm


def _rows(reg, which):
    return reg.get("serving.decode_rows_" + which).value


def _walked_share(reg):
    return _rows(reg, "walked") / _rows(reg, "table")


@pytest.mark.parametrize("cfg", [{"kv_block_size": 8}, {}],
                         ids=["paged", "contiguous"])
def test_walked_share_counters(long_gpt, cfg):
    """Every lane short: four items, one trip, a quarter of the
    four-chunk table.  One lane at the ceiling beside three short
    ones: its four items and their three, two trips, half of the table
    (to the longest window it was all of it).  A mixed batch whose
    lanes end in the first, second, third and fourth chunk: what is
    walked is at most 1.25 of what some query sees.  The span carries
    the mean over slots."""
    rng = np.random.RandomState(2)
    short = [rng.randint(0, 128, (n,)).astype(np.int32)
             for n in (9, 30, 120, 200)]
    reg = monitor.StatRegistry()
    eng = _long_engine(long_gpt, registry=reg, **cfg)
    _serve(eng, short, max_new=5)
    assert _walked_share(reg) == pytest.approx(0.25)
    rows = [ev.args["rows"] for ev in eng.tracer.events()
            if ev.name == "decode.dispatch"]
    assert rows and set(rows) == {CHUNK}

    reg = monitor.StatRegistry()
    eng = _long_engine(long_gpt, registry=reg, **cfg)
    ceiling = rng.randint(0, 128, (LONG - 6,)).astype(np.int32)
    _serve(eng, short[:3] + [ceiling], max_new=5)
    assert _walked_share(reg) == pytest.approx(0.5)

    reg = monitor.StatRegistry()
    eng = _long_engine(long_gpt, registry=reg, **cfg)
    _serve(eng, [rng.randint(0, 128, (n,)).astype(np.int32)
                 for n in (250, 505, 760, 1010)], max_new=5)
    assert _walked_share(reg) == pytest.approx(0.75)      # 10 items of 16
    assert 1.0 <= _rows(reg, "walked") / _rows(reg, "live") <= 1.25

    # a table of one chunk is read whole, and says so
    reg = monitor.StatRegistry()
    eng = Engine(_long_model(64), num_slots=2, max_seq_len=64,
                 registry=reg, **cfg)
    _serve(eng, short[:2], max_new=3)
    assert _walked_share(reg) == 1.0
