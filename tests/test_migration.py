"""KV block migration (engine export/import + disaggregated serving).

Engine tier: ``migrate_out`` freezes a LIVE decoding stream, gathers
its full KV blocks into a portable payload, and tears the slot down
(waiter unblocks with ``Migrated``); ``migrate_in`` adopts the blocks
all-or-nothing on a peer and resumes the stream token-identically.
The parity matrix drives the handoff across every engine shape —
paged / contiguous x chunked prefill x speculative x async depth 2 —
for greedy AND seeded sampling, against an unmigrated single-engine
oracle.

Router tier: replica roles (``prefill``/``decode``/``mixed``) turn
the same primitive into disaggregated prefill/decode, operator
``rebalance`` (preempt-and-migrate off a live replica), and
cross-replica prefix warming on affinity misses.

Fault tier: an injected ``migrate_export`` declines the migration and
the stream keeps running on the source; an injected
``migrate_import`` rolls the destination back to refcount 0 and the
SAME payload replays on a healthy peer — exactly-once either way.

All CPU, tiny model, in-process — tier-1 (``migration`` marker); the
real-process fleet variant is additionally ``slow``.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import Engine, FaultInjector, InjectedFault
from paddle_tpu.serving.engine import Migrated

pytestmark = pytest.mark.migration

PROMPT = list(range(11, 31))
MAX_NEW = 12
SEEDED = dict(temperature=0.8, top_k=8, seed=1234)

# every engine shape the migration payload must survive: the paged
# baseline, chunked prefill (the destination re-prefills the partial
# tail in chunks), speculative decoding (draft state is NOT migrated —
# the destination re-drafts), async depth 2 (the export drains the
# in-flight ring first), and contiguous KV (no blocks travel; the
# request alone migrates and the destination recomputes)
CONFIGS = {
    "paged": dict(kv_block_size=8),
    "chunked": dict(kv_block_size=8, prefill_chunk=8),
    "spec": dict(kv_block_size=8, spec_k=2),
    "depth2": dict(kv_block_size=8, async_depth=2),
    "contiguous": dict(),
}


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def _engine(model, **kw):
    cfg = dict(num_slots=2, max_seq_len=64,
               registry=monitor.StatRegistry())
    cfg.update(kw)
    return Engine(model, **cfg)


def _sample_kw(seed):
    return {} if seed is None else dict(SEEDED, seed=seed)


def _oracle(model, cfg, seed):
    """Full ids (prompt + generated) of the UNMIGRATED stream on a
    single engine of the same shape."""
    eng = _engine(model, **cfg)
    r = eng.submit(PROMPT, max_new_tokens=MAX_NEW, **_sample_kw(seed))
    eng.run_until_idle()
    assert r.error is None, r.error
    return r.result(timeout=1).tolist()


def _step_until(eng, pred, limit=400):
    for _ in range(limit):
        if pred():
            return True
        eng.step()
    return pred()


def _resolve(eng, demand, limit=100):
    """Step the engine until a wait=False migration demand resolves
    (its verdict — or its failure — raises/returns out of wait(0))."""
    for _ in range(limit):
        eng.step()
        try:
            return demand.wait(0)
        except TimeoutError:
            continue
    return demand.wait(0)


# ---------------------------------------------------------------------------
# engine tier: the parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CONFIGS))
@pytest.mark.parametrize("seed", [None, 1234],
                         ids=["greedy", "seeded"])
def test_migrate_mid_decode_token_identical(tiny_gpt, name, seed):
    """Export a live stream after >= 3 emitted tokens, import it on a
    fresh engine, and the completed stream is token-identical to the
    unmigrated oracle — across every engine shape, greedy and
    seeded.  Source ends at refcount 0; paged shapes actually move
    blocks."""
    cfg = CONFIGS[name]
    ref = _oracle(tiny_gpt, cfg, seed)
    src = _engine(tiny_gpt, **cfg)
    dst = _engine(tiny_gpt, **cfg)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW, **_sample_kw(seed))
    assert _step_until(src, lambda: len(r.generated) >= 3 or r.done())
    assert not r.done(), "stream finished before the export landed"
    d = src.migrate_out(request_id=r.id, min_tokens=3,
                        deliver="return", wait=False)
    verdict = _resolve(src, d)
    assert verdict["completed"] is False
    payload = verdict["payload"]
    assert payload is not None
    # the waiter unblocked with Migrated carrying the emitted tokens
    # (payload rides the return, not the exception, under "return")
    assert isinstance(r.error, Migrated)
    assert r.error.payload is None
    assert r.error.emitted == verdict["generated"]
    assert len(verdict["generated"]) >= 3
    # source owns nothing: slot torn down, trie refs are the only
    # remaining holders, clearing them hits refcount 0
    src.run_until_idle()
    assert src.scheduler.idle()
    if getattr(src, "prefix_cache", None) is not None:
        src.prefix_cache.clear()
        assert src.block_pool.in_use() == 0
    got = _resolve(dst, dst.migrate_in(payload, wait=False))
    r2 = got["request"]
    if cfg.get("kv_block_size") is not None:
        # >= 3 emitted on a 20-token prompt crosses a block boundary
        assert got["blocks"] >= 1, got
        assert payload["kv"]["n_blocks"] == got["blocks"]
    else:
        assert got["blocks"] == 0 and payload["kv"] is None
    dst.run_until_idle()
    assert r2.error is None, r2.error
    assert r2.result(timeout=1).tolist() == ref, \
        f"migrated stream diverged from oracle ({name}, seed={seed})"
    assert dst.scheduler.idle()
    if getattr(dst, "prefix_cache", None) is not None:
        dst.prefix_cache.clear()
        assert dst.block_pool.in_use() == 0
    # both sides logged the hop for /debug/requests
    assert any(m["dir"] == "out" for m in src._migration_history())
    assert any(m["dir"] == "in" for m in dst._migration_history())
    assert src.registry.get("serving.kv_blocks_migrated").value \
        == (payload["kv"]["n_blocks"] if payload["kv"] else 0)


def test_migrate_deliver_error_payload_rides_waiter(tiny_gpt):
    """deliver='error': the payload travels INSIDE the waiter's
    Migrated exception (the router's generate loop owns the import)
    and the migrate_out return carries payload=None."""
    ref = _oracle(tiny_gpt, CONFIGS["paged"], None)
    src = _engine(tiny_gpt, kv_block_size=8)
    dst = _engine(tiny_gpt, kv_block_size=8)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW)
    assert _step_until(src, lambda: len(r.generated) >= 2 or r.done())
    d = src.migrate_out(request_id=r.id, min_tokens=2,
                        deliver="error", wait=False)
    verdict = _resolve(src, d)
    assert verdict["completed"] is False and verdict["payload"] is None
    assert isinstance(r.error, Migrated)
    assert r.error.payload is not None
    assert r.error.emitted == verdict["generated"]
    got = _resolve(dst, dst.migrate_in(r.error.payload, wait=False))
    dst.run_until_idle()
    assert got["request"].result(timeout=1).tolist() == ref


def test_import_ignores_a_stale_rng_state(tiny_gpt):
    """The wire is input from outside the process: a peer of an older
    build still sends the host sampler's ``rng_state``.  The payload
    is adopted, the field ignored, and the seeded stream continues
    from the fold counter alone; this build's own payloads no longer
    carry the field."""
    ref = _oracle(tiny_gpt, CONFIGS["paged"], 1234)
    src = _engine(tiny_gpt, kv_block_size=8)
    dst = _engine(tiny_gpt, kv_block_size=8)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW, **_sample_kw(1234))
    assert _step_until(src, lambda: len(r.generated) >= 3 or r.done())
    verdict = _resolve(src, src.migrate_out(
        request_id=r.id, min_tokens=3, deliver="return", wait=False))
    payload = verdict["payload"]
    assert "rng_state" not in payload["request"]
    payload["request"]["rng_state"] = {
        "bit_generator": "PCG64", "has_uint32": 0, "uinteger": 0,
        "state": {"state": 1, "inc": 3}}
    got = _resolve(dst, dst.migrate_in(payload, wait=False))
    dst.run_until_idle()
    assert got["request"].result(timeout=1).tolist() == ref


def test_migrate_out_unpinned_picks_a_victim(tiny_gpt):
    """request_id=None exports SOME eligible decoding stream (lowest
    priority first) — the operator 'drain one stream off this
    replica' shape; the other stream keeps running untouched."""
    refs = {}
    for mn in (8, MAX_NEW):
        eng = _engine(tiny_gpt, kv_block_size=8)
        r = eng.submit(PROMPT, max_new_tokens=mn)
        eng.run_until_idle()
        refs[mn] = r.result(timeout=1).tolist()
    src = _engine(tiny_gpt, kv_block_size=8)
    dst = _engine(tiny_gpt, kv_block_size=8)
    keep = src.submit(PROMPT, max_new_tokens=8, priority=5)
    victim = src.submit(PROMPT, max_new_tokens=MAX_NEW, priority=0)
    assert _step_until(src, lambda: len(keep.generated) >= 1
                       and len(victim.generated) >= 1)
    verdict = _resolve(src, src.migrate_out(min_tokens=1, wait=False))
    assert victim.done() and isinstance(victim.error, Migrated)
    src.run_until_idle()
    assert keep.error is None
    assert keep.result(timeout=1).tolist() == refs[8]
    got = _resolve(dst, dst.migrate_in(verdict["payload"],
                                       wait=False))
    dst.run_until_idle()
    assert got["request"].result(timeout=1).tolist() == refs[MAX_NEW]


def test_migrate_out_of_completed_stream(tiny_gpt):
    """A stream that finishes before the export lands resolves as
    completed=True with the full generation — nothing migrates,
    nothing is lost.  (The min_tokens bar is never reached, so the
    pinned demand rides along until the stream's natural finish.)"""
    src = _engine(tiny_gpt, kv_block_size=8)
    r = src.submit(PROMPT, max_new_tokens=3)
    d = src.migrate_out(request_id=r.id, min_tokens=50, wait=False)
    verdict = _resolve(src, d)
    assert verdict["completed"] is True
    assert verdict["payload"] is None
    assert verdict["generated"] == list(r.generated)
    assert r.error is None  # the waiter saw a NORMAL finish


# ---------------------------------------------------------------------------
# engine tier: injected faults at the three migration stages
# ---------------------------------------------------------------------------

def test_export_fault_declines_stream_stays(tiny_gpt):
    """An injected migrate_export DECLINES the migration: the demand
    fails, the stream keeps decoding on the source to full greedy
    parity — the caller simply did not get the stream."""
    inj = FaultInjector(seed=0, rates={"migrate_export": 1.0})
    ref = _oracle(tiny_gpt, CONFIGS["paged"], None)
    src = _engine(tiny_gpt, kv_block_size=8, faults=inj)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW)
    assert _step_until(src, lambda: len(r.generated) >= 2)
    d = src.migrate_out(request_id=r.id, min_tokens=2, wait=False)
    with pytest.raises(InjectedFault):
        _resolve(src, d)
    assert inj.log and inj.log[0][1] == "migrate_export"
    assert not r.done()
    src.run_until_idle()
    assert r.error is None
    assert r.result(timeout=1).tolist() == ref
    src.prefix_cache.clear()
    assert src.block_pool.in_use() == 0


def test_import_fault_rolls_back_and_payload_replays(tiny_gpt):
    """An injected migrate_import adopts NOTHING (fresh allocation
    rolls back to refcount 0, no request queued) — and because a
    failed import leaves the payload with its holder, the SAME
    payload replays on a healthy peer token-identically."""
    ref = _oracle(tiny_gpt, CONFIGS["paged"], 1234)
    src = _engine(tiny_gpt, kv_block_size=8)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW, **SEEDED)
    assert _step_until(src, lambda: len(r.generated) >= 3)
    verdict = _resolve(src, src.migrate_out(
        request_id=r.id, min_tokens=3, wait=False))
    payload = verdict["payload"]
    bad = _engine(tiny_gpt, kv_block_size=8,
                  faults=FaultInjector(seed=0,
                                       rates={"migrate_import": 1.0}))
    with pytest.raises(InjectedFault):
        _resolve(bad, bad.migrate_in(payload, wait=False))
    assert bad.scheduler.idle() and bad.queue.depth() == 0
    assert bad.block_pool.in_use() == 0, \
        "failed import leaked blocks on the destination"
    good = _engine(tiny_gpt, kv_block_size=8)
    got = _resolve(good, good.migrate_in(payload, wait=False))
    good.run_until_idle()
    assert got["request"].result(timeout=1).tolist() == ref


def test_import_geometry_mismatch_adopts_nothing(tiny_gpt):
    """A payload whose KV geometry does not match the destination
    fails validation BEFORE any state lands: refcount 0, no queued
    request."""
    src = _engine(tiny_gpt, kv_block_size=8)
    r = src.submit(PROMPT, max_new_tokens=MAX_NEW)
    assert _step_until(src, lambda: len(r.generated) >= 8)
    verdict = _resolve(src, src.migrate_out(
        request_id=r.id, min_tokens=8, wait=False))
    payload = verdict["payload"]
    assert payload["kv"] is not None
    dst = _engine(tiny_gpt, kv_block_size=16)  # wrong block size
    with pytest.raises(ValueError):
        _resolve(dst, dst.migrate_in(payload, wait=False))
    assert dst.block_pool.in_use() == 0 and dst.queue.depth() == 0


# ---------------------------------------------------------------------------
# router tier: disaggregation, rebalance, prefix warming
# ---------------------------------------------------------------------------

def _router(model, roles, **pol):
    from paddle_tpu.serving.router import (InProcessReplica, Router,
                                           RouterPolicy)
    reg = monitor.StatRegistry()
    engines = []
    for _ in roles:
        e = _engine(model, kv_block_size=8, prefill_chunk=8)
        e.start()
        engines.append(e)
    reps = {f"r{i}": InProcessReplica(f"r{i}", engines[i], role=role)
            for i, role in enumerate(roles)}
    policy = RouterPolicy(probe_interval_s=30.0, retry_max=3,
                          backoff_base_s=0.001, backoff_cap_s=0.01,
                          breaker_cooldown_s=0.05, seed=7, **pol)
    rt = Router(reps, policy=policy, kv_block_size=8, registry=reg)
    rt.probe_once()
    return rt, engines


@pytest.mark.router
@pytest.mark.parametrize("seed", [None, 1234],
                         ids=["greedy", "seeded"])
def test_disaggregated_prefill_decode_parity(tiny_gpt, seed):
    """Prefill/decode disaggregation end to end: the router prefills
    on the prefill-role replica, migrates the warm blocks, decodes on
    the decode-role replica — and the answer is token-identical to a
    single mixed replica, greedy and seeded."""
    cfg = dict(kv_block_size=8, prefill_chunk=8)
    oracle = _engine(tiny_gpt, **cfg)
    ro = oracle.submit(PROMPT, max_new_tokens=MAX_NEW,
                       **_sample_kw(seed))
    oracle.run_until_idle()
    ref = list(ro.generated)
    rt, engines = _router(tiny_gpt, ["prefill", "decode"],
                          disaggregate=True)
    try:
        out = rt.generate(PROMPT, max_new_tokens=MAX_NEW,
                          **_sample_kw(seed))
    finally:
        for e in engines:
            e.stop()
    assert out["generated"] == ref
    assert out["replica"] == "r1", out  # the DECODE replica served it
    mig = [ev for ev in rt.route_log() if ev[0] == "migrate"]
    assert mig and mig[-1][4] >= 1  # warm blocks actually moved
    assert rt.registry.get("router.migrations_total").value == 1
    # the prefill replica exported its stream (terminal there) and
    # kept the warm prefix in its trie — nothing leaked
    assert engines[0].scheduler.idle()
    engines[0].prefix_cache.clear()
    assert engines[0].block_pool.in_use() == 0


@pytest.mark.router
def test_disaggregation_degrades_without_decode_replicas(tiny_gpt):
    """Role routing degrades before it fails: a fleet with only a
    prefill-role replica still serves (the request runs to completion
    there instead of migrating into a void)."""
    oracle = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)
    ro = oracle.submit(PROMPT, max_new_tokens=MAX_NEW)
    oracle.run_until_idle()
    rt, engines = _router(tiny_gpt, ["prefill"], disaggregate=True)
    try:
        out = rt.generate(PROMPT, max_new_tokens=MAX_NEW)
    finally:
        for e in engines:
            e.stop()
    assert out["generated"] == list(ro.generated)
    assert out["replica"] == "r0"
    assert rt.registry.get("router.migrations_total").value == 0


@pytest.mark.router
def test_rebalance_preempt_and_migrate(tiny_gpt):
    """Operator rebalance: preempt a LIVE stream off its replica; the
    router re-lands it on a peer and the caller — blocked in
    generate() the whole time — receives the oracle answer exactly
    once, served by a different replica."""
    import threading
    import time

    # a LONG stream (44 tokens) keeps the race winnable: the
    # rebalance must land while the stream is still mid-decode
    long_new = 44
    oracle = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)
    ro = oracle.submit(PROMPT, max_new_tokens=long_new)
    oracle.run_until_idle()
    rt, engines = _router(tiny_gpt, ["mixed", "mixed"])
    res = {}
    th = threading.Thread(
        target=lambda: res.update(
            out=rt.generate(PROMPT, max_new_tokens=long_new)))
    th.start()
    try:
        src = None
        deadline = time.time() + 20
        while time.time() < deadline and src is None:
            for i, e in enumerate(engines):
                if any(s.request is not None
                       and len(s.request.generated) >= 2
                       for s in e.scheduler.busy_slots()):
                    src = f"r{i}"
                    break
            time.sleep(0.002)
        assert src is not None, "stream never went live"
        verdict = rt.rebalance(src, min_tokens=2)
        th.join(timeout=30)
        assert not th.is_alive(), "caller never unblocked"
    finally:
        for e in engines:
            e.stop()
    out = res["out"]
    assert verdict["completed"] is False
    assert out["generated"] == list(ro.generated)
    assert out["replica"] != src, "stream did not move"
    assert any(ev[0] == "migrate" for ev in rt.route_log())
    assert rt.registry.get("router.migrations_total").value == 1


@pytest.mark.router
def test_prefix_warm_on_affinity_miss(tiny_gpt):
    """When load steering overrides prefix affinity, the router warms
    the chosen replica's trie from the affinity target before
    dispatch — the destination's prefix-hit counter moves and the
    answer is unchanged."""
    rt, engines = _router(tiny_gpt, ["mixed", "mixed"],
                          prefix_warm=True, affinity=True)
    try:
        out1 = rt.generate(PROMPT, max_new_tokens=4)
        aff = out1["replica"]
        other = next(r["name"] for r in rt.replicas()
                     if r["name"] != aff)
        idx = int(other[1:])
        hits0 = engines[idx]._m_prefix_hits.value
        # declare the affinity target overloaded: the pick falls back
        # to least-loaded (the other replica) and warming kicks in
        rt.policy.affinity_queue_threshold = -1
        out2 = rt.generate(PROMPT, max_new_tokens=4)
    finally:
        for e in engines:
            e.stop()
    assert out2["replica"] == other
    warms = [ev for ev in rt.route_log() if ev[0] == "warm"]
    assert warms and warms[-1][2] == aff and warms[-1][3] == other
    assert warms[-1][4] >= 1  # blocks actually moved
    assert engines[idx]._m_prefix_hits.value > hits0
    assert out2["generated"] == out1["generated"]


# ---------------------------------------------------------------------------
# HTTP tier: the /migrate endpoints over real sockets
# ---------------------------------------------------------------------------

@pytest.mark.router
def test_httpd_migrate_export_import_roundtrip(tiny_gpt):
    """The wire form end to end: export over POST /migrate/export
    (base64 payload), import over POST /migrate/import on a second
    server, stream completes token-identically."""
    import json
    import urllib.request

    from paddle_tpu.serving.httpd import EngineServer

    def post(url, body, timeout=30.0):
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    ref = _oracle(tiny_gpt, CONFIGS["paged"], None)
    src = _engine(tiny_gpt, kv_block_size=8)
    dst = _engine(tiny_gpt, kv_block_size=8)
    with EngineServer(src) as a, EngineServer(dst) as b:
        # /migrate/export with no request_id submits the body itself
        # and blocks until min_tokens have been emitted — the
        # disaggregated-prefill handler shape
        exp = post(a.address + "/migrate/export",
                   {"prompt": PROMPT, "max_new_tokens": MAX_NEW,
                    "min_tokens": 3})
        assert exp["completed"] is False
        payload = exp["payload"]
        assert payload["kv"]["data_b64"]  # wire form, JSON-safe
        imp = post(b.address + "/migrate/import", payload)
        assert imp["migrated_blocks"] >= 1
        assert imp["ids"] == ref
        # /debug/requests on both sides shows the hop
        with urllib.request.urlopen(a.address + "/debug/requests",
                                    timeout=10) as r:
            dbg = json.loads(r.read())
        assert any(m["dir"] == "out"
                   for m in dbg.get("migrations", []))


# ---------------------------------------------------------------------------
# real-process fleet (slow): disaggregated roles over HTTP
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.router
def test_real_fleet_disaggregated(tiny_gpt, tmp_path):
    """Spawn a real 2-process fleet with --role prefill / --role
    decode, route with disaggregation on, and assert the streams are
    token-identical to the local oracle, served by the decode
    replica, with the blocks having actually moved over HTTP."""
    from paddle_tpu.distributed.launch import spawn_serving_fleet
    from paddle_tpu.serving.router import (HttpReplicaClient, Router,
                                           RouterPolicy)

    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
               for n in (12, 20, 9)]
    oracle = _engine(tiny_gpt, num_slots=4, kv_block_size=8)
    expected = []
    for p in prompts:
        r = oracle.submit(p, max_new_tokens=6)
        oracle.run_until_idle()
        expected.append(list(r.generated))

    with spawn_serving_fleet(2, kv_block_size=8, max_seq_len=64,
                             roles=["prefill", "decode"],
                             log_dir=str(tmp_path)) as fleet:
        router = Router(
            {f"r{i}": HttpReplicaClient(url, timeout_s=60)
             for i, url in enumerate(fleet.urls)},
            policy=RouterPolicy(seed=0, probe_interval_s=0.2,
                                disaggregate=True),
            registry=monitor.StatRegistry())
        router.probe_once()
        roles = {r["name"]: r["role"] for r in router.replicas()}
        assert roles == {"r0": "prefill", "r1": "decode"}
        got = []
        for p in prompts:
            out = router.generate(list(map(int, p)),
                                  max_new_tokens=6)
            assert out["replica"] == "r1", out
            got.append([int(x) for x in out["generated"]])
        assert got == expected
        assert router.registry.get(
            "router.migrations_total").value == len(prompts)
