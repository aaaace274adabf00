"""Mesh-sharded serving engine (``Engine(mesh=...)``): the
tensor-parallel pjit model + head-sharded KV block pools served over a
forced multi-device CPU mesh (conftest boots 8 virtual CPU devices).

Covers: dense -> tensor-parallel weight relayout parity
(``GPTModel.to_tensor_parallel``), mp=2 vs unsharded greedy AND seeded
token-identity across every layout (contiguous / paged x plain /
chunked / spec / ragged x async depth 1+2), preemption-resume
token-identity on the sharded engine, sharded-pool refcounts -> 0
after preemption and after step-failure recovery, KV capacity scaling
with the mesh (``kv_budget_mb``), the compile-once-per-config
contract, the unchanged 17-byte steady-state d2h contract, the
``shard.sync`` / ``decode.allgather`` trace spans + ``trace_view
--wall`` breakdown, the /healthz + /debug/requests + router-registry
mesh surface, and (slow) a REAL spawned 2-replica fleet — each
replica itself mesh-sharded — served through the router over sockets
with a mid-run replica kill."""
import importlib.util
import json
import os
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import Engine, EngineServer

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

# every test here drives (or validates against) a multi-device mesh;
# conftest skips mesh-marked tests when fewer than 4 devices exist
pytestmark = pytest.mark.mesh


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _mesh_guard():
    """A sharded engine claims the process-global mesh (the TP
    activation constraints read it); restore whatever was there so
    sibling test files never inherit a 2-device serving mesh."""
    from paddle_tpu.distributed import mesh as mesh_mod
    prev = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(prev)


@pytest.fixture(scope="module")
def dense_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


@pytest.fixture(scope="module")
def tp_gpt(dense_gpt):
    return dense_gpt.to_tensor_parallel()


def _engine(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("registry", monitor.StatRegistry())
    return Engine(model, **kw)


def _prompts(n, base=7):
    rng = np.random.RandomState(base)
    lens = (5, 7, 3, 9, 4, 6)
    return [rng.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def _drive(eng, prompts, max_new=8, seeded=False, stagger=True):
    """Staggered submit -> run to idle -> per-request outputs (two
    requests land mid-decode of the first wave, the engine-parity
    shape every serving test uses)."""
    reqs = []
    for i, p in enumerate(prompts):
        kw = (dict(temperature=0.9, top_p=0.8, seed=1234 + i)
              if seeded else {})
        reqs.append(eng.submit(p, max_new_tokens=max_new, **kw))
        if stagger and i == len(prompts) // 2:
            for _ in range(2):
                eng.step()
    eng.run_until_idle()
    return [list(r.generated) for r in reqs]


# -- dense -> tensor-parallel relayout --------------------------------

def test_to_tensor_parallel_forward_parity(dense_gpt, tp_gpt):
    """The einsum-form twin computes the dense model's math: logits
    agree to float tolerance and argmax everywhere — the weight
    mapping is a pure relayout, not a re-init."""
    from paddle_tpu.core.tensor import Tensor
    ids = np.random.RandomState(3).randint(0, 128, (2, 12)) \
        .astype(np.int32)
    a = np.asarray(dense_gpt(Tensor(ids))._data)
    b = np.asarray(tp_gpt(Tensor(ids))._data)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert (a.argmax(-1) == b.argmax(-1)).all()
    # the twin carries the 'mp' PartitionSpecs pjit consumes
    specs = {n: getattr(p, "partition_spec", None)
             for n, p in tp_gpt.named_parameters()}
    assert any(s is not None and "mp" in tuple(s)
               for s in specs.values() if s is not None)
    # idempotent: converting a TP model returns itself
    assert tp_gpt.to_tensor_parallel() is tp_gpt


def test_mesh_validation(dense_gpt, tp_gpt):
    with pytest.raises(ValueError, match="tensor-parallel"):
        _engine(dense_gpt, mesh=2)  # dense fused-qkv cannot shard
    with pytest.raises(ValueError, match=r"\(mp, dp\)"):
        _engine(tp_gpt, mesh=(2, 2, 2))  # 3-tuple: no third axis
    with pytest.raises(ValueError, match="jax Mesh"):
        _engine(tp_gpt, mesh="two")
    with pytest.raises(ValueError, match="paged"):
        _engine(tp_gpt, mesh=2, kv_budget_mb=1)
    with pytest.raises(ValueError, match="one"):
        _engine(tp_gpt, mesh=2, kv_block_size=8, kv_blocks=16,
                kv_budget_mb=1)
    # dp shards own equal contiguous slot ranges — ragged splits
    # would strand slots, so an indivisible num_slots is rejected
    with pytest.raises(ValueError, match="divide"):
        _engine(dense_gpt, mesh=(1, 2), num_slots=3,
                kv_block_size=8)
    # a prebuilt mesh with non-mp/dp axes > 1 would silently
    # replicate params/pools across them — rejected like the tuple
    # path (mp x dp prebuilt meshes are accepted, see the dp parity
    # matrix)
    import jax
    from paddle_tpu.distributed.mesh import build_mesh
    with pytest.raises(ValueError, match="extra axes"):
        _engine(tp_gpt, mesh=build_mesh(sp=2, mp=2,
                                        devices=jax.devices()[:4]))
    # non-dense variants cannot relayout onto the TP specs
    paddle.seed(1)
    sp = GPTModel.from_config("tiny", dropout=0.0, use_sp=True)
    with pytest.raises(ValueError, match="sequence-parallel"):
        sp.to_tensor_parallel()
    paddle.seed(1)
    moe = GPTModel.from_config("tiny", dropout=0.0, moe_experts=2)
    with pytest.raises(ValueError, match="MoE"):
        moe.to_tensor_parallel()


# -- mp=2 vs unsharded token-identity ---------------------------------

LAYOUTS = [
    pytest.param(dict(), id="contiguous"),
    pytest.param(dict(kv_block_size=8), id="paged"),
    pytest.param(dict(kv_block_size=8, prefill_chunk=8), id="chunked"),
    pytest.param(dict(kv_block_size=8, spec_k=3), id="spec"),
    pytest.param(dict(kv_block_size=8, prefill_chunk=8, spec_k=2,
                      attn_impl="ragged"), id="ragged"),
]


@pytest.mark.parametrize("kw", LAYOUTS)
def test_sharded_parity(dense_gpt, tp_gpt, kw):
    """THE acceptance case: the mp=2 engine is greedy AND seeded
    token-identical to the unsharded engine on every layout (async
    depth 2, the device-mode default), under staggered admissions."""
    prompts = _prompts(6)
    for seeded in (False, True):
        e0 = _engine(dense_gpt, **kw)
        e1 = _engine(tp_gpt, mesh=2, **kw)
        a = _drive(e0, prompts, seeded=seeded)
        b = _drive(e1, prompts, seeded=seeded)
        assert a == b, f"sharded divergence ({kw}, seeded={seeded})"
        assert e1.mp == 2 and e1.mesh_axes == {"mp": 2}
        assert e1.registry.get("serving.mesh_devices").value == 2


def test_sharded_parity_depth1(dense_gpt, tp_gpt):
    """async_depth=1 keeps the synchronous tick under the mesh too —
    sharding and pipelining are orthogonal."""
    kw = dict(kv_block_size=8, async_depth=1)
    a = _drive(_engine(dense_gpt, **kw), _prompts(5))
    b = _drive(_engine(tp_gpt, mesh=2, **kw), _prompts(5))
    assert a == b


def test_sharded_preemption_resume_parity(dense_gpt, tp_gpt):
    """A mid-stream priority preemption on the SHARDED engine resumes
    token-identically to an uninterrupted unsharded run, and with the
    prefix cache off every sharded-pool block refcount returns to 0."""
    bg, hi = _prompts(2, base=11)
    ref_eng = _engine(dense_gpt, kv_block_size=8)
    ref = ref_eng.submit(bg, max_new_tokens=12)
    ref_eng.run_until_idle()

    eng = _engine(tp_gpt, mesh=2, num_slots=1, kv_block_size=8,
                  prefix_cache=False)
    victim = eng.submit(bg, max_new_tokens=12, priority=0)
    for _ in range(3):
        eng.step()
    urgent = eng.submit(hi, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    assert victim.preemptions >= 1
    assert list(urgent.generated)
    assert list(victim.generated) == list(ref.generated)
    assert eng.block_pool.in_use() == 0  # refcounts -> 0, no cache


def test_sharded_step_failure_recovery(tp_gpt, monkeypatch):
    """A failing tick on the sharded engine recovers like the
    unsharded one: waiters unblock loudly, the rebuilt pools come
    back MESH-SHARDED, refcounts are 0, and the engine then serves
    token-identically to a fresh sharded engine."""
    eng = _engine(tp_gpt, mesh=2, kv_block_size=8,
                  prefix_cache=False)
    req = eng.submit(_prompts(1)[0], max_new_tokens=6)
    eng.step()

    def boom(active, tr):
        raise RuntimeError("synthetic dispatch failure")

    monkeypatch.setattr(eng, "_dispatch_decode", boom)
    with pytest.raises(RuntimeError):
        eng.step()
    with pytest.raises(RuntimeError, match="engine step failed"):
        req.result(timeout=1)
    monkeypatch.undo()
    assert eng.scheduler.occupancy() == 0
    assert eng.block_pool.in_use() == 0
    # the recovery-rebuilt pools kept the head-axis mesh sharding
    assert eng.k_pools[0].sharding.spec[2] == "mp"
    p = _prompts(3)[2]
    out = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    ref_eng = _engine(tp_gpt, mesh=2, kv_block_size=8)
    ref = ref_eng.submit(p, max_new_tokens=6)
    ref_eng.run_until_idle()
    assert list(out.generated) == list(ref.generated)


def test_compile_once_per_config_sharded(tp_gpt):
    """All hot dispatch paths compile ONCE with the sharding baked in:
    a second identical wave adds zero programs."""
    eng = _engine(tp_gpt, mesh=2, kv_block_size=8, prefill_chunk=8,
                  spec_k=2)
    prompts = _prompts(4)
    _drive(eng, prompts, stagger=False)
    c1 = eng.registry.get("serving.compiles_total").value
    assert c1 > 0
    _drive(eng, prompts, stagger=False)
    assert eng.registry.get("serving.compiles_total").value == c1


def test_sharded_d2h_contract(dense_gpt, tp_gpt):
    """The steady-state download is the SAME tiny payload sharded or
    not — [B] ids + packed done bits (17 bytes at B=4): the fused
    sampling epilogue stayed device-side, on the all-gathered logits,
    instead of pulling per-shard logits to the host."""
    sizes = {}
    for name, eng in (("unsharded", _engine(dense_gpt,
                                            kv_block_size=8)),
                      ("sharded", _engine(tp_gpt, mesh=2,
                                          kv_block_size=8))):
        eng.submit(_prompts(1)[0], max_new_tokens=8)
        eng.run_until_idle()
        sizes[name] = eng._m_d2h.value
    assert sizes["unsharded"] == sizes["sharded"] == 17


# -- dp: data-parallel batch sharding ---------------------------------

DP_MESHES = [(1, 2), (2, 1), (2, 2)]
DP_LAYOUTS = [
    pytest.param(dict(kv_block_size=8), id="paged"),
    pytest.param(dict(kv_block_size=8, prefill_chunk=8),
                 id="chunked"),
    pytest.param(dict(kv_block_size=8, spec_k=3), id="spec"),
    pytest.param(dict(kv_block_size=8, prefill_chunk=8, spec_k=2,
                      attn_impl="ragged"), id="ragged"),
    pytest.param(dict(kv_block_size=8, kv_dtype="int8"), id="int8kv"),
]


def _dp_model(dense, tp, mesh):
    return tp if mesh[0] > 1 else dense


@pytest.mark.parametrize("kw", DP_LAYOUTS)
def test_dp_parity_matrix(dense_gpt, tp_gpt, kw):
    """THE dp acceptance case: every (mp, dp) in {(1,2), (2,1),
    (2,2)} is greedy AND seeded token-identical to the unsharded
    engine on every paged layout (plain / chunked / spec / ragged /
    int8 KV), under staggered admissions — one program spans both
    axes, batch slots sharded over 'dp'."""
    prompts = _prompts(6)
    for seeded in (False, True):
        base = _drive(_engine(dense_gpt, **kw), prompts,
                      seeded=seeded)
        for mesh in DP_MESHES:
            eng = _engine(_dp_model(dense_gpt, tp_gpt, mesh),
                          mesh=mesh, **kw)
            got = _drive(eng, prompts, seeded=seeded)
            assert got == base, \
                f"dp divergence (mesh={mesh}, {kw}, seeded={seeded})"
            assert (eng.mp, eng.dp) == mesh
            assert eng.registry.get("serving.mesh_devices").value \
                == mesh[0] * mesh[1]


@pytest.mark.parametrize("mesh", [(2, 1), (1, 2)], ids=["mp2", "dp2"])
def test_patched_state_keeps_its_shardings(dense_gpt, tp_gpt, mesh):
    """Under a mesh the state's one upload and every per-slot patch
    land on the state's own shardings (cursors and table rows over
    'dp'), so no dispatch re-shards: chunked streams over a tight pool
    with two decodes in flight are token-identical to the unsharded
    synchronous engine, ``shard.sync`` happens once, and nothing
    retraces by slot or value."""
    kw = dict(kv_block_size=8, prefill_chunk=8, kv_blocks=12)
    prompts = _prompts(7)
    base = _drive(_engine(dense_gpt, async_depth=1, **kw), prompts)
    eng = _engine(_dp_model(dense_gpt, tp_gpt, mesh), mesh=mesh,
                  async_depth=3, **kw)
    assert _drive(eng, prompts) == base
    from test_serving import backend_compiles
    with backend_compiles() as seen:     # warm: slots and values are
        assert _drive(eng, prompts[::-1]) == base[::-1]       # data
    assert seen.count == 0
    reg = eng.registry
    assert reg.get("serving.state_pushes").value == 1
    assert reg.get("serving.state_patches").value >= len(prompts)
    spans = [e for e in eng.chrome_trace()["traceEvents"]
             if e.get("ph") == "X"]
    assert sum(e["name"] == "shard.sync" for e in spans) == 1
    assert not any(e["name"] == "ring.drain"
                   and e["args"]["why"] == "dirty" for e in spans)
    want = eng._state_sharding
    for k, v in eng._dev_state.items():
        assert v.sharding.is_equivalent_to(want, v.ndim), k


def test_dp_parity_depth1(dense_gpt, tp_gpt):
    """async_depth=1 keeps the synchronous tick under the dp mesh
    too — batch sharding and pipelining are orthogonal."""
    kw = dict(kv_block_size=8, async_depth=1)
    base = _drive(_engine(dense_gpt, **kw), _prompts(5))
    for mesh in DP_MESHES:
        got = _drive(_engine(_dp_model(dense_gpt, tp_gpt, mesh),
                             mesh=mesh, **kw), _prompts(5))
        assert got == base, f"depth1 divergence (mesh={mesh})"


def test_dp_preemption_resume_parity(dense_gpt, tp_gpt):
    """A mid-stream priority preemption on the dp-sharded engine
    resumes token-identically to uninterrupted unsharded runs, and
    with the prefix cache off every shard's block refcounts return
    to 0 (per-shard free lists fully restored)."""
    bg = _prompts(2, base=11)
    hi = _prompts(1, base=13)[0]
    refs = []
    for p in bg:
        ref_eng = _engine(dense_gpt, kv_block_size=8)
        r = ref_eng.submit(p, max_new_tokens=12)
        ref_eng.run_until_idle()
        refs.append(list(r.generated))

    eng = _engine(tp_gpt, mesh=(2, 2), num_slots=2, kv_block_size=8,
                  prefix_cache=False)
    victims = [eng.submit(p, max_new_tokens=12, priority=0)
               for p in bg]
    for _ in range(3):
        eng.step()
    urgent = eng.submit(hi, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    assert sum(v.preemptions for v in victims) >= 1
    assert list(urgent.generated)
    assert [list(v.generated) for v in victims] == refs
    assert eng.block_pool.in_use() == 0
    for d in range(eng.dp):
        assert eng.block_pool.free_count(d) == \
            eng._kv_managed // eng.dp


def test_dp_compile_once_per_config(tp_gpt):
    """All hot dispatch paths compile ONCE with the (mp, dp)
    sharding baked in: a second identical wave adds zero programs."""
    eng = _engine(tp_gpt, mesh=(2, 2), kv_block_size=8,
                  prefill_chunk=8, spec_k=2)
    prompts = _prompts(4)
    _drive(eng, prompts, stagger=False)
    c1 = eng.registry.get("serving.compiles_total").value
    assert c1 > 0
    _drive(eng, prompts, stagger=False)
    assert eng.registry.get("serving.compiles_total").value == c1


def test_serving_mesh_oversized_names_device_flag():
    """Satellite regression: asking for more mesh devices than exist
    fails loudly with the exact XLA flag that forces a virtual CPU
    pool — not a cryptic reshape error deep in jax."""
    import jax
    from paddle_tpu.distributed.mesh import serving_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError) as ei:
        serving_mesh(n, 2)
    msg = str(ei.value)
    assert "--xla_force_host_platform_device_count" in msg
    assert str(2 * n) in msg  # sized to the REQUESTED pool
    # the happy path still builds exactly (mp, dp)
    m = serving_mesh(2, 2)
    assert int(m.shape["mp"]) == 2 and int(m.shape["dp"]) == 2


@pytest.mark.pallas
def test_sharded_ragged_kernel_matches_gspmd_oracle():
    """Tentpole acceptance at the kernel level: the shard_map-
    partitioned ragged kernel (grid-per-shard, GLOBAL block tables
    localized per dp shard, heads pre-sliced per mp shard) matches
    the GSPMD-partitioned oracle — the SAME kernel jitted over the
    SAME mesh-sharded operands, with XLA deriving the partitioning
    from input shardings — and the unsharded single-device run."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import serving_mesh
    from paddle_tpu.ops.ragged_paged_attn import (
        ragged_paged_attention, sharded_ragged_paged_attention)

    mesh = serving_mesh(2, 2)
    B, W, H, hd, bs, bps = 4, 4, 4, 8, 8, 4
    NB = 10  # pool rows per dp shard: 5 blocks
    rng = np.random.RandomState(0)
    q = rng.randn(B, W, H, hd).astype(np.float32)
    k = rng.randn(NB * bs, H, hd).astype(np.float32)
    v = rng.randn(NB * bs, H, hd).astype(np.float32)
    # tables carry GLOBAL block ids, but each slot draws only from
    # its own dp shard's contiguous range — the invariant the
    # engine's shard-scoped admission gate maintains
    nb_local = NB // 2
    tables = np.zeros((B, bps), np.int32)
    for b in range(B):
        base = (b // 2) * nb_local
        tables[b] = base + 1 + (np.arange(bps) % (nb_local - 1))
    pos = np.array([5, 9, 0, 13], np.int32)
    width = np.array([3, 4, 0, 2], np.int32)

    shards = {
        "q": NamedSharding(mesh, P("dp", None, "mp", None)),
        "kv": NamedSharding(mesh, P("dp", "mp", None)),
        "tab": NamedSharding(mesh, P("dp", None)),
        "vec": NamedSharding(mesh, P("dp")),
    }
    qd = jax.device_put(q, shards["q"])
    kd = jax.device_put(k, shards["kv"])
    vd = jax.device_put(v, shards["kv"])
    td = jax.device_put(tables, shards["tab"])
    pd = jax.device_put(pos, shards["vec"])
    wd = jax.device_put(width, shards["vec"])

    unsharded = np.asarray(ragged_paged_attention(
        q, k, v, tables, pos, width, block_size=bs, interpret=True))
    oracle = np.asarray(jax.jit(
        lambda *a: ragged_paged_attention(
            *a, block_size=bs, interpret=True))(qd, kd, vd, td, pd, wd))
    got = np.asarray(sharded_ragged_paged_attention(
        q, k, v, tables, pos, width, block_size=bs, mesh=mesh,
        interpret=True))
    np.testing.assert_allclose(got, oracle, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(got, unsharded, atol=1e-5, rtol=1e-5)

    # int8 quantized pools thread per-block scales through the same
    # specs (P('dp', 'mp')) and dequantize in-loop per shard
    codes_k = rng.randint(-127, 128, (NB * bs, H, hd)) \
        .astype(np.int8)
    codes_v = rng.randint(-127, 128, (NB * bs, H, hd)) \
        .astype(np.int8)
    ks = (rng.rand(NB, H).astype(np.float32) + 0.5) / 127.0
    vs = (rng.rand(NB, H).astype(np.float32) + 0.5) / 127.0
    ref_q = np.asarray(ragged_paged_attention(
        q, codes_k, codes_v, tables, pos, width, block_size=bs,
        interpret=True, k_scale=ks, v_scale=vs))
    got_q = np.asarray(sharded_ragged_paged_attention(
        q, codes_k, codes_v, tables, pos, width, block_size=bs,
        mesh=mesh, interpret=True, k_scale=ks, v_scale=vs))
    np.testing.assert_allclose(got_q, ref_q, atol=1e-5, rtol=1e-5)


# -- KV capacity scales with the mesh ---------------------------------

def test_kv_capacity_scales_with_mesh(dense_gpt, tp_gpt):
    """A fixed PER-SHARD HBM budget buys mp x the logical blocks:
    each shard stores only its heads' slice of every block, so the
    per-shard block cost halves at mp=2 and the pool doubles —
    ``serving.kv_blocks_total`` reflecting the aggregate."""
    e1 = _engine(dense_gpt, kv_block_size=8, kv_budget_mb=1)
    e2 = _engine(tp_gpt, mesh=2, kv_block_size=8, kv_budget_mb=1)
    assert e1._kv_block_bytes_per_shard == \
        2 * e2._kv_block_bytes_per_shard
    # floor-exact against the budget, and at least 2x the unsharded
    # pool (exactly 2x when the per-shard bytes divide the budget —
    # true for the tiny config's power-of-two dims; an odd remainder
    # could only round the mp=2 pool UP an extra block)
    assert e2._kv_managed == 2 ** 20 // e2._kv_block_bytes_per_shard
    assert e2._kv_managed >= 2 * e1._kv_managed
    assert e2.registry.get("serving.kv_blocks_total").value == \
        e2._kv_managed
    from paddle_tpu.serving.kvcache import per_shard_block_bytes
    assert e2._kv_block_bytes_per_shard == per_shard_block_bytes(
        8, 4, 16, e2._kv_dtype, 2, mp=2)
    with pytest.raises(ValueError, match="divide"):
        per_shard_block_bytes(8, 4, 16, np.float32, 2, mp=3)
    # the budget-sized sharded pool actually serves
    out = e2.submit(_prompts(1)[0], max_new_tokens=4)
    e2.run_until_idle()
    assert len(out.generated) == 4


def test_kv_capacity_scales_mp_x_dp(dense_gpt, tp_gpt):
    """A fixed PER-SHARD HBM budget buys mp x dp the logical blocks:
    mp shards store only their heads' slice of every block, and each
    dp shard brings its OWN budget-sized pool range — at (2, 2) the
    aggregate is >= 3.9x the unsharded pool (exactly 4x for the tiny
    config's power-of-two dims)."""
    e1 = _engine(dense_gpt, kv_block_size=8, kv_budget_mb=1)
    e12 = _engine(dense_gpt, mesh=(1, 2), kv_block_size=8,
                  kv_budget_mb=1)
    e22 = _engine(tp_gpt, mesh=(2, 2), kv_block_size=8,
                  kv_budget_mb=1)
    assert e12._kv_managed == 2 * e1._kv_managed
    assert e22._kv_managed >= 3.9 * e1._kv_managed
    # floor-exact per dp shard against the per-shard budget
    assert e22._kv_managed == 2 * \
        (2 ** 20 // e22._kv_block_bytes_per_shard)
    assert e22.registry.get("serving.kv_blocks_total").value == \
        e22._kv_managed
    # each dp shard owns an equal share of the managed pool
    for d in range(2):
        assert e22.block_pool.free_count(d) == e22._kv_managed // 2
    # the budget-sized (2, 2) pool actually serves
    out = e22.submit(_prompts(1)[0], max_new_tokens=4)
    e22.run_until_idle()
    assert len(out.generated) == 4


# -- observability: spans, healthz, registry --------------------------

def test_shard_spans_and_wall_breakdown(tp_gpt, tmp_path):
    """Sharded ticks trace ``shard.sync`` (cursor replication) and
    ``decode.allgather`` (cross-shard collective wait), and
    trace_view --wall breaks both out."""
    eng = _engine(tp_gpt, mesh=2, kv_block_size=8)
    _drive(eng, _prompts(3), stagger=False)
    names = {e["name"] for e in
             eng.chrome_trace()["traceEvents"] if e.get("ph") == "X"}
    assert "shard.sync" in names
    assert "decode.allgather" in names
    tv = _load_tool("trace_view")
    w = tv.wall_summary(eng.chrome_trace()["traceEvents"])
    assert w["allgather_waits"] > 0
    assert w["shard_sync_ms"] >= 0.0
    assert "decode.allgather" in tv.format_wall(w)


def test_healthz_and_debug_mesh_surface(tp_gpt):
    eng = _engine(tp_gpt, mesh=2, kv_block_size=8)
    with EngineServer(eng, port=0) as srv:
        with urllib.request.urlopen(srv.address + "/healthz",
                                    timeout=10) as resp:
            h = json.loads(resp.read())
        assert h["mp"] == 2
        assert h["dp"] == 1
        assert h["mesh_shape"] == {"mp": 2}
        free = eng.block_pool.free_count()
        assert h["kv_blocks_free_per_shard"] == [free, free]
        assert h["kv_block_bytes_per_shard"] == \
            eng._kv_block_bytes_per_shard
        with urllib.request.urlopen(srv.address + "/debug/requests",
                                    timeout=10) as resp:
            d = json.loads(resp.read())
        assert d["engine"]["mp"] == 2
        assert d["engine"]["mesh_shape"] == {"mp": 2}


def test_healthz_and_debug_dp_surface(tp_gpt):
    """The (2, 2) engine reports the FULL mesh shape and each dp
    shard's own free count (repeated per mp shard — mp slices are
    uniform, dp shards drain independently)."""
    eng = _engine(tp_gpt, mesh=(2, 2), kv_block_size=8)
    with EngineServer(eng, port=0) as srv:
        with urllib.request.urlopen(srv.address + "/healthz",
                                    timeout=10) as resp:
            h = json.loads(resp.read())
        assert h["mp"] == 2 and h["dp"] == 2
        assert h["mesh_shape"] == {"mp": 2, "dp": 2}
        per_dp = [eng.block_pool.free_count(d) for d in range(2)]
        assert h["kv_blocks_free_per_shard"] == per_dp * 2
        with urllib.request.urlopen(srv.address + "/debug/requests",
                                    timeout=10) as resp:
            d = json.loads(resp.read())
        assert d["engine"]["mp"] == 2 and d["engine"]["dp"] == 2
        assert d["engine"]["mesh_shape"] == {"mp": 2, "dp": 2}


def test_router_registry_carries_mesh(tp_gpt):
    """The router's probe sweep copies the replica's mesh signals
    into the registry rows — /replicas (and timeline.py --router)
    can label sharded replicas without a second protocol."""
    from paddle_tpu.serving import InProcessReplica, Router
    eng = _engine(tp_gpt, mesh=2, kv_block_size=8)
    router = Router({"r0": InProcessReplica("r0", eng)},
                    registry=monitor.StatRegistry())
    router.probe_once()
    row = router.replicas()[0]
    assert row["signals"]["mp"] == 2
    assert row["signals"]["mesh_shape"] == {"mp": 2}


def test_router_registry_carries_dp(tp_gpt):
    from paddle_tpu.serving import InProcessReplica, Router
    eng = _engine(tp_gpt, mesh=(2, 2), kv_block_size=8)
    router = Router({"r0": InProcessReplica("r0", eng)},
                    registry=monitor.StatRegistry())
    router.probe_once()
    row = router.replicas()[0]
    assert row["signals"]["mp"] == 2
    assert row["signals"]["dp"] == 2
    assert row["signals"]["mesh_shape"] == {"mp": 2, "dp": 2}


def test_timeline_labels_sharded_replicas(monkeypatch):
    """timeline.py --router labels a sharded replica's timeline lane
    with its tensor-parallel degree from the registry signals."""
    tl = _load_tool("timeline")
    table = {"replicas": [
        {"name": "a", "address": "http://h:1",
         "signals": {"mp": 2, "mesh_shape": {"mp": 2}}},
        {"name": "b", "address": "http://h:2", "signals": {"mp": 1}},
        {"name": "c", "address": "http://h:3",
         "signals": {"mp": 2, "dp": 2,
                     "mesh_shape": {"mp": 2, "dp": 2}}},
        {"name": "d", "address": "http://h:4",
         "signals": {"mp": 1, "dp": 2}},
    ]}

    class FakeResp:
        def __init__(self, data):
            self._d = json.dumps(data).encode()

        def read(self):
            return self._d

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(tl.urllib.request, "urlopen",
                        lambda url, timeout=10.0: FakeResp(table))
    labels = [lab for lab, _ in tl.router_sources("http://r:9")]
    assert labels == ["router", "replica:a mp=2", "replica:b",
                      "replica:c mp=2 dp=2", "replica:d mp=1 dp=2"]


# -- real-process fleet (slow): spawn, route, kill, fail over ---------

@pytest.mark.slow
@pytest.mark.router
def test_real_fleet_failover_token_identical(tp_gpt, tmp_path):
    """Close the loop at the FLEET level: spawn 2 real replica
    processes via distributed/launch.py (each replica mesh-sharded,
    mp=2, on its own forced 2-device CPU pool), register them with a
    Router over the HTTP transport, exercise probe/affinity, kill one
    replica mid-run, and assert every request — including the ones
    re-dispatched across the kill — lands token-identical to the
    local sharded oracle."""
    from paddle_tpu.distributed.launch import spawn_serving_fleet
    from paddle_tpu.serving import Router, RouterPolicy
    from paddle_tpu.serving.router import HttpReplicaClient

    prompts = _prompts(8, base=23)
    MAX_NEW = 6
    # local oracle: same seed/config as the spawned replicas (httpd
    # main seeds 0 and builds the tiny config, dropout 0)
    oracle = _engine(tp_gpt, mesh=2, max_seq_len=64, kv_block_size=8)
    expected = []
    for p in prompts:
        r = oracle.submit(p, max_new_tokens=MAX_NEW)
        oracle.run_until_idle()
        expected.append(list(r.generated))

    with spawn_serving_fleet(2, mp=2, kv_block_size=8,
                             max_seq_len=64,
                             log_dir=str(tmp_path)) as fleet:
        router = Router(
            {f"r{i}": HttpReplicaClient(url, timeout_s=60)
             for i, url in enumerate(fleet.urls)},
            policy=RouterPolicy(seed=0, probe_interval_s=0.2),
            registry=monitor.StatRegistry())
        router.probe_once()
        rows = {r["name"]: r for r in router.replicas()}
        assert all(r["signals"]["mp"] == 2 for r in rows.values())
        got = []
        for i, p in enumerate(prompts):
            if i == len(prompts) // 2:
                # kill a replica mid-run: the router pays one
                # classified failure and fails over
                fleet.kill(0)
            out = router.generate(list(map(int, p)),
                                  max_new_tokens=MAX_NEW)
            got.append([int(x) for x in out["generated"]])
        assert got == expected
        # the dead replica was detected by probing
        router.probe_once()
        router.probe_once()
        router.probe_once()
        states = {r["name"]: r["state"] for r in router.replicas()}
        assert states["r0"] in ("degraded", "dead")
        assert states["r1"] == "healthy"
