"""The blocked slot-window attention (``GPTAttention._slot_attn``).

The XLA serving path reads each slot's cache a chunk of rows at a
time, in the dtype the cache holds, and only as far as the longest
live window.  Held here against an independent float32 one-shot
oracle over fully gathered rows (the form the function had before it
was blocked), for every fetch the four callers hand it: contiguous
buffers, paged block tables, ``QuantKV`` pools.

TOLERANCE (stated before the first run): the blocked walk computes
the same products as the oracle — bf16 x bf16 is exact in float32 —
and differs only in the order of float32 sums (per chunk, then across
chunks), so float32 outputs agree to ``2e-5`` absolute on O(1)
values; a bf16 query makes the output bf16, one rounding of 2**-8
relative, held to ``2e-2``.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.serving import Engine
from paddle_tpu.serving.quant import QuantKV, paged_gather, quantize_blocks

B, H, HD, BS = 4, 4, 16, 8
CHUNK = gpt_mod.slot_attn_chunk(BS)          # 256
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


def _tables(L, rng):
    """Distinct physical blocks per slot, shuffled; block 0 is the
    scratch block no table names."""
    nbt = L // BS
    ids = rng.permutation(B * nbt) + 1
    return ids.reshape(B, nbt).astype(np.int32), B * nbt + 1


def _pools(L, rng, dtype):
    tables, nb = _tables(L, rng)
    k = rng.standard_normal((nb, BS, H, HD)).astype(np.float32)
    v = rng.standard_normal((nb, BS, H, HD)).astype(np.float32)
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables))


def _gathered(pool, tables):
    return np.asarray(pool, np.float32)[np.asarray(tables)].reshape(
        tables.shape[0], -1, H, HD)


def _run(attn, qa, k_src, v_src, fetch, L, chunk, pos):
    out = jax.jit(lambda q, k, v, p: attn._slot_attn(
        q, k, v, fetch, L, chunk, p)._data)(qa, k_src, v_src, pos)
    return np.asarray(out, np.float32)


def _mixed_pos(L, S):
    """A parked lane at 0, a short lane, a lane just past a chunk
    edge, and a lane whose window ends on the table's last row."""
    return jnp.asarray([0, 37, CHUNK + 1, L - S], jnp.int32)


@pytest.mark.parametrize("S", [1, 4], ids=["decode", "verify"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", ["paged", "contiguous", "quant"])
@pytest.mark.parametrize("L", [3 * CHUNK, 2 * CHUNK + 40],
                         ids=["whole_chunks", "ragged_tail"])
def test_blocked_matches_oneshot_oracle(attn, L, layout, dtype, S):
    rng = np.random.default_rng(L + S)
    dt = jnp.dtype(dtype)
    qa = jnp.asarray(rng.standard_normal((B, S, H, HD)), dt)
    pos = _mixed_pos(L, S)
    k_pool, v_pool, tables = _pools(L, rng, dt)
    if layout == "contiguous":
        k_src = jnp.asarray(_gathered(k_pool, tables), dt)
        v_src = jnp.asarray(_gathered(v_pool, tables), dt)
        k_rows, v_rows = k_src, v_src
        fetch, chunk = gpt_mod._fetch_rows, gpt_mod.slot_attn_chunk()
    elif layout == "quant":
        k_src = QuantKV(*quantize_blocks(k_pool.astype(jnp.float32)))
        v_src = QuantKV(*quantize_blocks(v_pool.astype(jnp.float32)))
        k_rows, v_rows = (paged_gather(k_src, tables),
                          paged_gather(v_src, tables))
        fetch, chunk = gpt_mod._fetch_blocks(tables), CHUNK
    else:
        k_src, v_src = k_pool, v_pool
        k_rows, v_rows = _gathered(k_pool, tables), _gathered(v_pool, tables)
        fetch, chunk = gpt_mod._fetch_blocks(tables), CHUNK
    got = _run(attn, qa, k_src, v_src, fetch, L, chunk, pos)
    want = _oracle(attn, qa, k_rows, v_rows, pos)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("longest", [CHUNK - 1, CHUNK, 2 * CHUNK - 1, 700],
                         ids=["below_edge", "on_edge", "last_of_chunk",
                              "third_chunk"])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_dead_rows_are_not_read(attn, layout, longest):
    """Every block past the walk's bound holds NaN in K and V: the
    result is finite and equal to the clean one (a whole-table read
    gives 0 x NaN = NaN).  ``longest`` is the longest lane's LAST
    visible row, so ``on_edge`` (row 256 = the first row of the second
    chunk) must walk two chunks and ``below_edge`` only one."""
    L, S = 4 * CHUNK, 1
    rng = np.random.default_rng(longest)
    qa = jnp.asarray(rng.standard_normal((B, S, H, HD)), jnp.float32)
    pos = jnp.asarray([0, 5, longest, 17], jnp.int32)
    k_pool, v_pool, tables = _pools(L, rng, jnp.float32)
    bound = gpt_mod.slot_attn_rows(longest + S, L, CHUNK)
    assert bound == (longest + S + CHUNK - 1) // CHUNK * CHUNK < L
    dead = np.asarray(tables)[:, bound // BS:].reshape(-1)
    k_nan = k_pool.at[dead].set(jnp.nan)
    v_nan = v_pool.at[dead].set(jnp.nan)
    if layout == "contiguous":
        srcs = [(jnp.asarray(_gathered(k, tables)),
                 jnp.asarray(_gathered(v, tables)))
                for k, v in ((k_pool, v_pool), (k_nan, v_nan))]
        fetch = gpt_mod._fetch_rows
    else:
        srcs = [(k_pool, v_pool), (k_nan, v_nan)]
        fetch = gpt_mod._fetch_blocks(tables)
    clean, dirty = (_run(attn, qa, k, v, fetch, L, CHUNK, pos)
                    for k, v in srcs)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)
    # the row at the bound's last position IS read: poison it instead
    live = np.asarray(tables)[2, longest // BS]
    out = _run(attn, qa, k_pool.at[live].set(jnp.nan), v_pool,
               gpt_mod._fetch_blocks(tables), L, CHUNK, pos)
    assert np.isnan(out[2]).all() and np.isfinite(out[[0, 1, 3]]).all()


def test_one_chunk_table_keeps_one_shot_form(attn):
    """A table no longer than a chunk has no loop in its program and
    equals the oracle bit for bit in float32 on this backend."""
    L, S = 64, 1
    rng = np.random.default_rng(3)
    qa = jnp.asarray(rng.standard_normal((B, S, H, HD)), jnp.float32)
    pos = jnp.asarray([0, 9, 33, L - 1], jnp.int32)
    k_pool, v_pool, tables = _pools(L, rng, jnp.float32)
    fetch = gpt_mod._fetch_blocks(tables)
    jaxpr = jax.make_jaxpr(lambda q, k, v, p: attn._slot_attn(
        q, k, v, fetch, L, CHUNK, p)._data)(qa, k_pool, v_pool, pos)
    assert "while" not in str(jaxpr)
    got = _run(attn, qa, k_pool, v_pool, fetch, L, CHUNK, pos)
    want = _oracle(attn, qa, _gathered(k_pool, tables),
                   _gathered(v_pool, tables), pos)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


@pytest.mark.parametrize("end,rows", [(0, 256), (1, 256), (256, 256),
                                      (257, 512), (1000, 1024),
                                      (2048, 2048), (5000, 2048)])
def test_rows_walked_host_twin(end, rows):
    assert gpt_mod.slot_attn_rows(end, 2048, 256) == rows
    # a table of one chunk or less is read whole whatever is live
    assert gpt_mod.slot_attn_rows(end, 64, 256) == 64
    # a ragged tail never counts more rows than the table has
    assert gpt_mod.slot_attn_rows(end, 600, 256) == min(600, rows)


@pytest.mark.parametrize("bs,chunk", [(None, 256), (8, 256), (16, 256),
                                      (48, 240), (512, 512)])
def test_chunk_is_whole_blocks(bs, chunk):
    assert gpt_mod.slot_attn_chunk(bs) == chunk


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
    assert worst <= nslots * CHUNK * H * HD


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
def test_engine_mesh2_across_chunks(long_gpt, long_refs, _mesh_guard):
    """mp=2 on the virtual devices: heads shard, ``pos`` does not, so
    both shards walk the same trips; streams equal the dense
    model's ``generate()``."""
    tp = _long_model(LONG).to_tensor_parallel()
    got = _serve(_long_engine(tp, mesh=2, kv_block_size=8,
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


def _walked_share(reg):
    return (reg.get("serving.decode_rows_walked").value
            / reg.get("serving.decode_rows_table").value)


@pytest.mark.parametrize("cfg", [{"kv_block_size": 8}, {}],
                         ids=["paged", "contiguous"])
def test_walked_share_counters(long_gpt, cfg):
    """Every lane short: a quarter of the four-chunk table.  One lane
    at the ceiling: all of it.  The span carries the bound."""
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
    assert _walked_share(reg) == pytest.approx(1.0)

    # a table of one chunk is read whole, and says so
    reg = monitor.StatRegistry()
    eng = Engine(_long_model(64), num_slots=2, max_seq_len=64,
                 registry=reg, **cfg)
    _serve(eng, short[:2], max_new=3)
    assert _walked_share(reg) == 1.0
