"""Resilient multi-replica router (serving/router.py + routerd.py).

Three tiers, all CPU tier-1 (``router`` marker):

* unit: affinity hashing / rendezvous stability, circuit-breaker
  state machine, retry classification + seeded backoff, hedging over
  scripted fake replicas (no engine, no jax work);
* integration: ``InProcessReplica`` over real tiny engines — probe
  classification (healthy/degraded/draining/dead), failover of a
  queued-but-unstarted request off a replica declared dead, greedy
  resume-with-context parity;
* the seeded CHAOS STORM (acceptance): a 3-replica fleet under the
  mixed workload with one replica's transport on a seeded
  refuse/black-hole/disconnect schedule — every request delivered
  exactly ONCE (greedy token-identical to ``generate()`` despite
  mid-stream kills), the breaker trips and recovers through
  half-open, survivors' pools refcount to zero, and the SAME SEED
  replays the SAME routing/failover log.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import (CircuitBreaker, Engine, FaultInjector,
                                InProcessReplica, NoReplicasAvailable,
                                ReplicaAbandoned, ReplicaHTTPError,
                                ReplicaUnavailable, RequestFailed,
                                Router, RouterPolicy, affinity_key)
from paddle_tpu.serving.faults import (NET_SITES, SITES, NetDisconnect,
                                       NetRefused, NetTimeout)
from paddle_tpu.serving.router import (CLOSED, DEAD, DEGRADED,
                                       DRAINING, HALF_OPEN, HEALTHY,
                                       OPEN)

pytestmark = pytest.mark.router


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def _registry():
    return monitor.StatRegistry()


def _fast_policy(**kw):
    kw.setdefault("seed", 0)
    kw.setdefault("backoff_base_s", 0.0005)
    kw.setdefault("backoff_cap_s", 0.002)
    kw.setdefault("breaker_cooldown_s", 0.0)
    return RouterPolicy(**kw)


class FakeReplica:
    """Scripted no-engine replica: generated token i is
    ``(last_prompt_token + i + 1) % 97`` (deterministic, resumable —
    a greedy resume from k emitted tokens continues the same series),
    with per-op failure scripts."""

    def __init__(self, name, fail=None, health=None, delay_s=0.0):
        self.name = name
        self.fail = dict(fail or {})      # op -> exception factory
        self.health = health or (lambda: {
            "queue_depth": 0, "slots_free": 4, "draining": False})
        self.delay_s = delay_s
        self.op = 0
        self.served = []
        self.aborted = 0
        self.payloads = []

    def probe(self):
        return self.health()

    @staticmethod
    def continuation(prompt, n):
        return [(int(prompt[-1]) + i + 1) % 97 for i in range(n)]

    def generate(self, payload, should_abort=None):
        t = self.op
        self.op += 1
        self.payloads.append(dict(payload))
        if self.delay_s:
            t0 = time.monotonic()
            while time.monotonic() - t0 < self.delay_s:
                if should_abort is not None and should_abort():
                    self.aborted += 1
                    raise ReplicaAbandoned(f"{self.name} aborted")
                time.sleep(0.001)
        if t in self.fail:
            raise self.fail[t]()
        gen = self.continuation(payload["prompt"],
                                payload["max_new_tokens"])
        self.served.append(t)
        return {"id": t, "ids": list(payload["prompt"]) + gen,
                "generated": gen, "ttft_ms": 0.5}


def _router(reps, **pol):
    return Router(reps, policy=_fast_policy(**pol),
                  kv_block_size=8, registry=_registry())


def _prompt_on(router, name, length=8):
    """A prompt whose rendezvous affinity target is ``name``."""
    reps = router._reps()
    for s in range(500):
        p = [(s * 7 + i) % 100 for i in range(length)]
        key = affinity_key(p, router.block_size())
        if router._affinity_target(key, reps).name == name:
            return p
    raise AssertionError(f"no prompt maps to {name}")


# ---------------------------------------------------------------------------
# affinity hashing + pick policy (pure unit)
# ---------------------------------------------------------------------------

def test_affinity_key_block_alignment():
    """The hash covers the longest block-aligned span only: prompts
    sharing an aligned system-prompt head hash equal, a difference
    INSIDE the span diverges, and short prompts hash whole."""
    sys_prompt = list(range(16))
    a = affinity_key(sys_prompt + [50, 51, 52], 8)
    b = affinity_key(sys_prompt + [60, 61], 8)
    assert a == b                       # tails differ only past 16
    assert a != affinity_key([1] + sys_prompt[1:] + [50], 8)
    # 19 tokens at bs=8 -> span 16: changing token 17 is invisible,
    # changing token 15 is not
    assert affinity_key(sys_prompt + [1, 2, 3], 8) == \
        affinity_key(sys_prompt + [9, 2, 3], 8)
    assert affinity_key([1, 2, 3], 8) != affinity_key([1, 2, 4], 8)


def test_rendezvous_stability_under_churn():
    """Removing a replica only remaps the keys IT owned; everyone
    else's prefix-cache affinity survives the churn."""
    r = _router({n: FakeReplica(n) for n in ("a", "b", "c")})
    keys = [[(s * 11 + i) % 100 for i in range(8)] for s in range(60)]
    before = {}
    for i, p in enumerate(keys):
        before[i] = r._affinity_target(
            affinity_key(p, 8), r._reps()).name
    assert len(set(before.values())) == 3  # all three used
    r.remove_replica("c")
    for i, p in enumerate(keys):
        after = r._affinity_target(affinity_key(p, 8),
                                   r._reps()).name
        if before[i] != "c":
            assert after == before[i]


def test_pick_affinity_with_load_fallback():
    """The affinity target wins while its probed queue is shallow;
    past the threshold the pick falls back to least-loaded."""
    load = {"a": 0, "b": 0}
    reps = {n: FakeReplica(n, health=lambda n=n: {
        "queue_depth": load[n], "slots_free": 4, "draining": False})
        for n in ("a", "b")}
    r = _router(reps, affinity_queue_threshold=3)
    r.probe_once()
    p = _prompt_on(r, "a")
    rep, how = r.pick(p)
    assert (rep.name, how) == ("a", "affinity")
    load["a"] = 10                       # hot shard: probed depth up
    r.probe_once()
    rep, how = r.pick(p)
    assert (rep.name, how) == ("b", "load")


def test_pick_excludes_draining_and_dead():
    r = _router({n: FakeReplica(n) for n in ("a", "b")})
    r.probe_once()
    pa = _prompt_on(r, "a")
    for state in (DRAINING, DEAD):
        r._replicas["a"].state = state
        rep, how = r.pick(pa)
        assert rep.name == "b"
    r._replicas["b"].state = DEAD
    with pytest.raises(NoReplicasAvailable):
        r.pick(pa)
    # degraded is routable as last resort
    r._replicas["a"].state = DEGRADED
    rep, how = r.pick(pa)
    assert (rep.name, how) == ("a", "last_resort")


def test_random_routing_arm_is_seeded():
    """affinity=False (the A/B baseline) picks by seeded hash:
    deterministic per (seed, request, attempt), spread over the
    pool."""
    def run(seed):
        r = _router({n: FakeReplica(n) for n in ("a", "b", "c")},
                    affinity=False, seed=seed)
        return [r.generate([5, 6, 7], max_new_tokens=2)["replica"]
                for _ in range(12)]
    first = run(3)
    assert first == run(3)
    assert len(set(first)) > 1
    assert first != run(4)


# ---------------------------------------------------------------------------
# circuit breaker (pure unit)
# ---------------------------------------------------------------------------

def test_breaker_trip_halfopen_and_recovery():
    events = []
    b = CircuitBreaker(threshold=3, cooldown_s=0.03,
                       on_transition=events.append)
    assert b.state == CLOSED and b.peek()
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED             # not yet: consecutive < 3
    b.record_success()
    b.record_failure()
    b.record_failure()
    assert b.state == CLOSED             # success reset the streak
    b.record_failure()
    assert b.state == OPEN and b.trips == 1
    assert not b.peek() and not b.acquire()   # cooling down
    time.sleep(0.04)
    assert b.peek()
    assert b.acquire()                    # admits the ONE trial
    assert b.state == HALF_OPEN
    assert not b.acquire()                # second concurrent trial: no
    b.record_failure()                    # failed trial -> re-open
    assert b.state == OPEN and b.trips == 2
    time.sleep(0.04)
    assert b.acquire()
    b.record_success()                    # clean trial -> closed
    assert b.state == CLOSED and b.peek()
    assert events == [OPEN, HALF_OPEN, OPEN, HALF_OPEN, CLOSED]


def test_breaker_trips_through_router_and_probe_recovers():
    """Consecutive request failures trip the replica's breaker (picks
    skip it); a clean health probe against the cooled-open breaker
    re-admits traffic through half-open."""
    boom = {i: lambda: NetRefused("down") for i in range(2)}
    a = FakeReplica("a", fail=boom)
    b = FakeReplica("b")
    r = _router({"a": a, "b": b}, breaker_threshold=2, retry_max=1)
    r.probe_once()
    pa = _prompt_on(r, "a")
    # two requests, each failing over a->b, trip a's breaker
    for _ in range(2):
        out = r.generate(list(pa), max_new_tokens=2)
        assert out["replica"] == "b"
    assert r._replicas["a"].breaker.state == OPEN
    assert r.registry.get("router.breaker_trips_total").value == 1
    assert r.registry.get("router.breaker_state.a").value == 2
    # cooled (cooldown 0) + clean probe -> half-open
    r.probe_once()
    assert r._replicas["a"].breaker.state == HALF_OPEN
    assert r.registry.get("router.breaker_state.a").value == 1
    # the trial request (a serves op 3 fine) closes it
    out = r.generate(list(pa), max_new_tokens=2)
    assert out["replica"] == "a"
    assert r._replicas["a"].breaker.state == CLOSED
    trans = [e for e in r.route_log() if e[0] == "breaker"]
    assert trans == [("breaker", "a", OPEN),
                     ("breaker", "a", HALF_OPEN),
                     ("breaker", "a", CLOSED)]


# ---------------------------------------------------------------------------
# retry classification / backoff / hedging (fake replicas)
# ---------------------------------------------------------------------------

def test_retry_honors_retry_after_and_backoff_is_seeded():
    hint = 0.05
    a = FakeReplica("a", fail={0: lambda: ReplicaUnavailable(
        "shedding", retry_after=hint)})
    r = _router({"a": a}, retry_max=2)
    t0 = time.monotonic()
    out = r.generate([3, 4, 5], max_new_tokens=2)
    waited = time.monotonic() - t0
    assert out["replica"] == "a" and out["attempts"] == 2
    assert waited >= hint                # the 503's hint was honored
    assert r.registry.get("router.retries_total").value == 1
    # the jitter draw is a pure function of (seed, request, attempt)
    assert r._backoff(7, 2) == r._backoff(7, 2)
    assert r._backoff(7, 2) != r._backoff(8, 2)
    assert Router({}, policy=_fast_policy(seed=0),
                  registry=_registry())._backoff(7, 2) == \
        r._backoff(7, 2)


def test_non_retryable_4xx_fails_fast():
    calls = []
    a = FakeReplica("a")
    a.fail = {i: lambda: ReplicaHTTPError("bad prompt", 400,
                                          reason="bad_request")
              for i in range(5)}
    orig = a.generate
    a.generate = lambda *aa, **kw: (calls.append(1),
                                    orig(*aa, **kw))[1]
    r = _router({"a": a, "b": FakeReplica("b")}, retry_max=3)
    pa = _prompt_on(r, "a")
    with pytest.raises(RequestFailed) as ei:
        r.generate(list(pa), max_new_tokens=2)
    assert isinstance(ei.value.cause, ReplicaHTTPError)
    assert len(calls) == 1               # 4xx never re-dispatches
    assert r.registry.get("router.retries_total").value == 0


def test_blackhole_timeout_retries_only_idempotent():
    """A lost response MAY mean executed work: greedy (and seeded)
    requests re-send, unseeded sampled requests fail fast."""
    def mk():
        a = FakeReplica("a", fail={0: lambda: NetTimeout("void")})
        return _router({"a": a, "b": FakeReplica("b")}, retry_max=2), a
    r, a = mk()
    pa = _prompt_on(r, "a")
    out = r.generate(list(pa), max_new_tokens=2)   # greedy: retried
    assert out["attempts"] == 2
    r2, a2 = mk()
    with pytest.raises(RequestFailed):
        r2.generate(list(pa), max_new_tokens=2, top_p=0.9)  # sampled,
        #   no seed: not idempotent, not blindly re-sent
    out = r2.generate(list(pa), max_new_tokens=2, top_p=0.9,
                      seed=11)            # seeded: idempotent again
    assert out["attempts"] == 1           # (op 1: no fault scripted)


def test_disconnect_resume_greedy_vs_restart_sampled():
    """Mid-body disconnect: greedy failover resumes from the emitted
    context (delivered stream identical to uninterrupted); sampled
    requests restart from scratch (emitted tokens discarded)."""
    p = [10, 11, 12]
    whole = FakeReplica.continuation(p, 6)

    def mk(**gen_kw):
        a = FakeReplica("a", fail={0: lambda: NetDisconnect(
            "mid-body", emitted=whole[:2])})
        b = FakeReplica("b")
        r = _router({"a": a, "b": b})
        pa = _prompt_on(r, "a")  # ensure the pick lands on a first
        return r, a, b
    r, a, b = mk()
    pa = _prompt_on(r, "a")
    whole_pa = FakeReplica.continuation(pa, 6)
    a.fail = {0: lambda: NetDisconnect("mid-body",
                                       emitted=whole_pa[:2])}
    out = r.generate(list(pa), max_new_tokens=6)
    assert out["generated"] == whole_pa           # seam-free resume
    assert b.payloads[0]["prompt"] == list(pa) + whole_pa[:2]
    assert b.payloads[0]["max_new_tokens"] == 4
    assert r.registry.get("router.failovers_total").value == 1
    # sampled+seeded: restart whole, nothing salvaged
    r2, a2, b2 = mk()
    pa2 = _prompt_on(r2, "a")
    a2.fail = {0: lambda: NetDisconnect(
        "mid-body", emitted=FakeReplica.continuation(pa2, 6)[:2])}
    r2.generate(list(pa2), max_new_tokens=6, top_p=0.9, seed=5)
    assert b2.payloads[0]["prompt"] == list(pa2)
    assert b2.payloads[0]["max_new_tokens"] == 6


def test_hedge_fires_after_delay_and_cancels_loser():
    """Tail-latency hedging: a slow primary gets a delayed second
    dispatch; the fast winner returns, the loser is cancelled via its
    abort hook, and the metrics/log record the hedge win."""
    reps = {"a": FakeReplica("a"), "b": FakeReplica("b")}
    r = _router(reps, hedge=True, hedge_after_s=0.03)
    r.probe_once()
    pa = _prompt_on(r, "a")
    reps["a"].delay_s = 0.5               # primary: slow
    reps["b"].delay_s = 0.0
    out = r.generate(list(pa), max_new_tokens=3)
    assert out["replica"] == "b"
    assert out["generated"] == FakeReplica.continuation(pa, 3)
    # the fired hedge was a real second dispatch: attempts counts it
    assert out["attempts"] == 2
    assert r.registry.get("router.hedges_total").value == 1
    assert r.registry.get("router.hedge_wins_total").value == 1
    for _ in range(100):                  # loser observes its abort
        if reps["a"].aborted:
            break
        time.sleep(0.005)
    assert reps["a"].aborted == 1
    kinds = [e[0] for e in r.route_log()]
    assert "hedge" in kinds and "hedge_win" in kinds
    # a hedge-cancelled primary is NOT a breaker failure
    assert reps["a"].name not in [
        e[1] for e in r.route_log() if e[0] == "breaker"]
    assert r._replicas["a"].breaker.failures == 0


def test_hedge_default_p99_delay_path():
    """``RouterPolicy(hedge=True)`` with the DEFAULT p99-derived
    delay (hedge_after_s=None) — the README's own example — must
    work: the floor applies until enough latency samples exist."""
    reps = {"a": FakeReplica("a"), "b": FakeReplica("b")}
    r = _router(reps, hedge=True, hedge_floor_s=0.02)
    r.probe_once()
    pa = _prompt_on(r, "a")
    reps["a"].delay_s = 0.5
    out = r.generate(list(pa), max_new_tokens=2)
    assert out["replica"] == "b"
    assert r.registry.get("router.hedge_wins_total").value == 1


def test_hedge_is_the_halfopen_trial():
    """A hedge dispatched at a recovering replica consumes its
    HALF_OPEN trial slot like any other dispatch: the transition log
    shows open -> half_open -> closed, never open -> closed (a hedge
    that skipped acquire would race the single-trial invariant)."""
    reps = {"a": FakeReplica("a"), "b": FakeReplica("b")}
    r = _router(reps, hedge=True, hedge_after_s=0.02,
                breaker_threshold=1)
    r.probe_once()
    pa = _prompt_on(r, "a")
    r._replicas["b"].breaker.record_failure()   # OPEN; cooldown 0
    reps["a"].delay_s = 0.3
    out = r.generate(list(pa), max_new_tokens=2)
    assert out["replica"] == "b"            # the hedge WAS the trial
    trans = [s for (_, name, s) in
             (e for e in r.route_log() if e[0] == "breaker")
             if name == "b"]
    assert trans == [OPEN, HALF_OPEN, CLOSED]


def test_probe_sweep_not_blocked_by_hung_replicas():
    """Probes go out concurrently: hung replicas must not head-of-
    line block health detection for the rest of the fleet (sweep
    cost ~max over replicas, not the sum)."""
    def hang(delay):
        def health():
            time.sleep(delay)
            return {"queue_depth": 0, "slots_free": 4}
        return health
    r = _router({"s1": FakeReplica("s1", health=hang(0.4)),
                 "s2": FakeReplica("s2", health=hang(0.4)),
                 "fast": FakeReplica("fast")})
    t0 = time.monotonic()
    out = r.probe_once()
    dt = time.monotonic() - t0
    assert set(out.values()) == {HEALTHY}
    assert dt < 0.75                      # serial would be >= 0.8


def test_router_spans_and_lifecycle_instants():
    a = FakeReplica("a", fail={0: lambda: NetRefused("down")})
    r = _router({"a": a, "b": FakeReplica("b")}, retry_max=1)
    r.probe_once()
    pa = _prompt_on(r, "a")
    r.generate(list(pa), max_new_tokens=2)
    events = r.chrome_trace()["traceEvents"]
    names = {e["name"] for e in events}
    assert {"probe", "route.pick", "route.accepted",
            "route.served", "route.failover"} <= names


# ---------------------------------------------------------------------------
# probe classification + failover off a dying replica (real engines)
# ---------------------------------------------------------------------------

def _engine(model, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("kv_block_size", 8)
    kw.setdefault("registry", _registry())
    return Engine(model, **kw)


def _fresh_model():
    """A private model instance with the SAME seeded weights as the
    ``tiny_gpt`` fixture.  Engines that may TRACE new programs
    concurrently (one replica decoding while another prefills) must
    not share a model: jax tracing is not thread-safe across threads
    sharing one compile cache.  Same seed => greedy outputs still
    match the fixture's ``generate()`` references."""
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def test_probe_states_from_real_engine(tiny_gpt):
    eng = _engine(tiny_gpt)
    rep = InProcessReplica("r0", eng)
    r = _router({"r0": rep}, dead_after=2)
    assert r.probe_once() == {"r0": HEALTHY}
    assert r.block_size() == 8            # adopted from the probe
    eng._draining = True
    assert r.probe_once() == {"r0": DRAINING}
    eng._draining = False
    eng._watchdog_fired = True
    assert r.probe_once() == {"r0": DEGRADED}
    eng._watchdog_fired = False
    rep.kill()
    assert r.probe_once() == {"r0": DEGRADED}   # first miss degrades
    assert r.probe_once() == {"r0": DEAD}       # dead_after=2 kills
    assert r.registry.get("router.replica_health.r0").value == 0
    rep.revive()
    assert r.probe_once() == {"r0": HEALTHY}
    assert r.registry.get("router.replica_health.r0").value == 3
    # the log records state CHANGES only (kill's first miss lands on
    # an already-degraded replica, so only the DEAD step logs)
    state_log = [e for e in r.route_log() if e[0] == "probe"]
    assert state_log == [("probe", "r0", DRAINING),
                         ("probe", "r0", DEGRADED),
                         ("probe", "r0", DEAD),
                         ("probe", "r0", HEALTHY)]


def test_affinity_routing_keeps_prefix_hits_over_random(tiny_gpt):
    """Three replicas, interleaved repeats of three system prompts:
    the greedy ids do not depend on the routing policy, and affinity
    routing adopts at least as many cached prompt tokens fleet-wide
    as seeded-random routing does — every repeat of a class lands on
    the replica whose prefix cache holds its blocks."""
    rng = np.random.RandomState(0)
    sys_prompts = [rng.randint(0, 128, (16,)).tolist()
                   for _ in range(3)]
    jobs = [sys_prompts[i % 3]
            + rng.randint(0, 128, (1 + i % 3,)).tolist()
            for i in range(12)]

    def run(affinity):
        engines = [_engine(tiny_gpt) for _ in range(3)]
        r = _router({f"r{i}": InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)},
                    affinity=affinity)
        for e in engines:
            e.start()
        try:
            r.probe_once()
            outs = [r.generate(list(p), max_new_tokens=3)["ids"]
                    for p in jobs]
        finally:
            for e in engines:
                e.stop(drain=False)
        return outs, sum(
            e.registry.get("serving.prefix_hit_tokens").value
            for e in engines)

    outs_aff, hits_aff = run(True)
    outs_rand, hits_rand = run(False)
    assert outs_aff == outs_rand
    # 9 repeats of a 16-token (two-block) system prompt, all adopted
    assert hits_aff == 9 * 16 >= hits_rand


def test_unstarted_request_fails_over_off_dead_replica(tiny_gpt):
    """A request still QUEUED on a replica the router declares dead is
    abandoned (nothing emitted) and re-routed — delivered exactly
    once, by the survivor."""
    # a's engine loop is NEVER STARTED: the routed request sits in its
    # queue until the router declares a dead — deterministically
    # "queued-but-unstarted", with no wall-clock slot wedge that
    # full-suite CPU load could let finish early (private models: b
    # traces while the main thread runs the reference generate)
    ea, eb = _engine(_fresh_model()), _engine(_fresh_model())
    ra = InProcessReplica("a", ea)
    rb = InProcessReplica("b", eb)
    r = _router({"a": ra, "b": rb})
    r.probe_once()
    eb.start()
    try:
        pa = _prompt_on(r, "a")
        ref = tiny_gpt.generate(
            paddle.to_tensor(np.asarray([pa], np.int32)),
            max_new_tokens=6).numpy()[0]
        box = {}

        def call():
            box["out"] = r.generate(list(pa), max_new_tokens=6)

        t = threading.Thread(target=call, daemon=True)
        t.start()
        # wait until the request is actually queued on a (nothing
        # drains a's queue, so depth can only rise)
        queued = False
        for _ in range(5000):
            if ea.queue.depth() >= 1:
                queued = True
                break
            time.sleep(0.002)
        assert queued
        r.mark_dead("a")
        t.join(timeout=20)
        assert not t.is_alive()
        out = box["out"]
        assert out["replica"] == "b"
        assert out["ids"] == [int(x) for x in ref]
        assert ("failover", out["req"], "a", "abandoned") in \
            r.route_log()
        assert r.registry.get("router.failovers_total").value == 1
        serves = [e for e in r.route_log() if e[0] == "serve"]
        assert len(serves) == 1           # exactly once
    finally:
        ea.stop(drain=False)
        eb.stop(drain=False)


def test_draining_replica_stops_receiving_new_requests(tiny_gpt):
    """Cooperative drain: a replica reporting draining keeps its
    in-flight streams but the router routes new work elsewhere."""
    ea, eb = _engine(_fresh_model()), _engine(_fresh_model())
    r = _router({"a": InProcessReplica("a", ea),
                 "b": InProcessReplica("b", eb)})
    r.probe_once()
    ea.start()
    eb.start()
    try:
        pa = _prompt_on(r, "a")
        assert r.generate(list(pa), max_new_tokens=2)["replica"] == "a"
        ea._draining = True               # stop(drain=True) mid-flight
        r.probe_once()
        for _ in range(3):
            out = r.generate(list(pa), max_new_tokens=2)
            assert out["replica"] == "b"
    finally:
        ea.stop(drain=False)
        eb.stop(drain=False)


# ---------------------------------------------------------------------------
# net fault sites (faults.py satellites)
# ---------------------------------------------------------------------------

def test_net_sites_pure_schedule_and_actions():
    assert set(NET_SITES) <= set(SITES)
    a = FaultInjector(seed=9, rates={"net_refuse": 0.4})
    b = FaultInjector(seed=9, rates={"net_refuse": 0.4})
    sched = [a.scheduled("net_refuse", t) for t in range(100)]
    assert sched == [b.scheduled("net_refuse", t) for t in range(100)]
    assert 10 <= sum(sched) <= 80
    inj = FaultInjector(seed=0, blackhole_s=0.0)
    with pytest.raises(NetRefused):
        inj.fire("net_refuse", 3)
    with pytest.raises(NetTimeout):
        inj.fire("net_blackhole", 4)
    with pytest.raises(NetDisconnect) as ei:
        inj.fire("net_disconnect", 5, emitted=[7, 8])
    assert ei.value.emitted == [7, 8]
    inj.fire("net_slow", 6)               # proceeds after the sleep
    assert inj.log == [(3, "net_refuse"), (4, "net_blackhole"),
                       (5, "net_disconnect"), (6, "net_slow")]


def test_blackhole_abort_hook_cuts_the_wait_short():
    inj = FaultInjector(seed=0, blackhole_s=5.0)
    t0 = time.monotonic()
    with pytest.raises(NetTimeout):
        inj.fire("net_blackhole", 0, abort=lambda: True)
    assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# the seeded chaos storm (acceptance)
# ---------------------------------------------------------------------------

def _storm_workload():
    """Mixed, deterministic: shared 8-token system prompt (one
    affinity class) + unique tails, varying lengths, greedy AND
    seeded-sampled traffic."""
    rng = np.random.RandomState(42)
    sys_prompt = rng.randint(0, 128, (8,)).tolist()
    jobs = []
    for i in range(14):
        tail = rng.randint(0, 128, (1 + i % 5,)).tolist()
        kw = {"max_new_tokens": 3 + i % 6}
        if i % 4 == 3:
            kw.update(top_p=0.9, temperature=0.8, seed=1000 + i)
        jobs.append((sys_prompt + tail, kw))
    return jobs


def _run_storm(tiny_gpt, seed):
    """One full storm run on fresh engines; returns everything the
    determinism/exactly-once assertions need."""
    engines = [_engine(tiny_gpt) for _ in range(3)]
    injs = [FaultInjector(seed=seed * 10 + i, blackhole_s=0.0,
                          net_slow_s=0.001)
            for i in range(3)]
    reps = {f"r{i}": InProcessReplica(f"r{i}", engines[i],
                                      faults=injs[i])
            for i in range(3)}
    r = Router(reps, policy=_fast_policy(
        seed=seed, retry_max=5, breaker_threshold=2,
        affinity_queue_threshold=64), kv_block_size=8,
        registry=_registry())
    # the whole workload shares one system prompt = ONE affinity
    # class: make ITS target the sick replica (refuse / black-hole /
    # mid-stream disconnect on a seeded schedule) so the storm rains
    # where the traffic lands; one bystander is merely slow
    sys_prompt = _storm_workload()[0][0][:8]
    sick = r._affinity_target(affinity_key(sys_prompt, 8),
                              r._reps()).name
    slow = next(n for n in ("r0", "r1", "r2") if n != sick)
    injs[int(sick[1])].rates = {"net_refuse": 0.30,
                                "net_blackhole": 0.15,
                                "net_disconnect": 0.25}
    # windowed storm: ops past the window are clean, so the tail of
    # the workload deterministically exercises breaker RECOVERY (a
    # half-open trial that finally succeeds), not just tripping
    injs[int(sick[1])].last_tick = 10
    injs[int(slow[1])].rates = {"net_slow": 0.2}
    for e in engines:
        e.start()
    def settle():
        # wait for every engine to go fully idle before probing: a
        # probe racing the engine thread's slot release would read a
        # timing-dependent slots_free, and the least-loaded tie-break
        # would fork the routing log between identically-seeded runs
        for e in engines:
            for _ in range(5000):
                if e.scheduler.idle() and e.queue.depth() == 0:
                    break
                time.sleep(0.002)

    outs = []
    try:
        settle()
        r.probe_once()
        for prompt, kw in _storm_workload():
            outs.append(r.generate(list(prompt), **kw))
            settle()
            r.probe_once()                # deterministic probe cadence
    finally:
        # let orphaned work (streams the router abandoned mid-fault)
        # finish before shutdown so pool invariants are checkable
        for e in engines:
            for _ in range(2000):
                if e.scheduler.idle() and e.queue.depth() == 0:
                    break
                time.sleep(0.002)
            e.stop(drain=False)
    leaks = []
    for e in engines:
        if e.prefix_cache is not None:
            e.prefix_cache.clear()
        leaks.append(e.block_pool.in_use())
    return {
        "outs": outs,
        "sick": sick,
        "route_log": r.route_log(),
        "fault_logs": [list(i.log) for i in injs],
        "breaker_events": [e for e in r.route_log()
                           if e[0] == "breaker"],
        "leaks": leaks,
        "retries": r.registry.get("router.retries_total").value,
        "failovers": r.registry.get("router.failovers_total").value,
    }


@pytest.mark.chaos
def test_chaos_storm_exactly_once_and_deterministic(tiny_gpt):
    """THE acceptance storm: a replica killed/black-holed mid-stream
    under the mixed workload.  Every request is delivered exactly
    once (greedy results token-identical to ``generate()`` despite
    failovers — no losses, no duplicates, no cross-replica
    corruption), the sick replica's breaker trips and recovers
    through half-open, survivors' pools refcount to zero, and the
    same seed replays the same fault AND routing/failover logs."""
    run1 = _run_storm(tiny_gpt, seed=7)
    # --- delivery: exactly once, content-correct ---------------------
    jobs = _storm_workload()
    assert len(run1["outs"]) == len(jobs)
    serves = [e for e in run1["route_log"] if e[0] == "serve"]
    assert len(serves) == len(jobs)                  # one serve each
    assert len({e[1] for e in serves}) == len(jobs)  # ...per request
    for (prompt, kw), out in zip(jobs, run1["outs"]):
        assert len(out["generated"]) <= kw["max_new_tokens"]
        if "seed" not in kw:                         # greedy: exact
            ref = tiny_gpt.generate(
                paddle.to_tensor(np.asarray([prompt], np.int32)),
                max_new_tokens=kw["max_new_tokens"]).numpy()[0]
            assert out["ids"] == [int(x) for x in ref]
    # --- the storm actually stormed ----------------------------------
    sick = run1["sick"]
    assert run1["retries"] >= 3
    assert run1["failovers"] >= 1
    assert run1["fault_logs"][int(sick[1])]
    # --- breaker tripped AND recovered through half-open -------------
    states = [s for (_, name, s) in run1["breaker_events"]
              if name == sick]
    assert OPEN in states, "the sick replica never tripped its breaker"
    assert HALF_OPEN in states
    assert CLOSED in states[states.index(HALF_OPEN):], \
        "breaker never recovered through half-open"
    # --- no leaks on any replica (survivors AND the sick one) --------
    assert run1["leaks"] == [0, 0, 0]
    # --- same seed => same fault schedule, same routing log ----------
    run2 = _run_storm(tiny_gpt, seed=7)
    assert run2["fault_logs"] == run1["fault_logs"]
    assert run2["route_log"] == run1["route_log"]
    assert [o["ids"] for o in run2["outs"]] == \
        [o["ids"] for o in run1["outs"]]
    assert [o["replica"] for o in run2["outs"]] == \
        [o["replica"] for o in run1["outs"]]
    # --- a different seed diverges somewhere -------------------------
    run3 = _run_storm(tiny_gpt, seed=8)
    assert (run3["fault_logs"] != run1["fault_logs"]
            or run3["route_log"] != run1["route_log"])
    # seeded-sampled outputs are reproducible across storms with
    # DIFFERENT fault schedules too: a replica change or a restart
    # must not fork a seeded stream
    for (prompt, kw), o1, o3 in zip(jobs, run1["outs"],
                                    run3["outs"]):
        if "seed" in kw:
            assert o1["ids"] == o3["ids"]


def test_classify_probe_handles_both_healthz_shapes():
    """DRAINING must be detected from httpd's /healthz shape (a
    "state" field, no top-level "draining" key) as well as
    InProcessReplica's bool — an HTTP replica in stop(drain=True)
    must not be misread as merely degraded (degraded is routable as
    last resort; draining never is)."""
    r = Router({}, policy=_fast_policy(), registry=_registry())
    # httpd /healthz shape
    assert r.classify_probe({"status": "ok", "live": True,
                             "ready": False,
                             "state": DRAINING}) == DRAINING
    assert r.classify_probe({"live": True, "ready": False,
                             "state": "watchdog_fired",
                             "watchdog_fired": True}) == DEGRADED
    assert r.classify_probe({"status": "ok", "live": True,
                             "ready": True, "state": "ok"}) == HEALTHY
    # InProcessReplica shape
    assert r.classify_probe({"draining": True}) == DRAINING
    assert r.classify_probe({"watchdog_fired": True}) == DEGRADED
    assert r.classify_probe({"status": "ok"}) == HEALTHY


def test_4xx_is_caller_fault_not_a_breaker_failure():
    """A 4xx reply PROVES the replica is answering: it must not trip
    the breaker (a bad client would otherwise blackball a healthy
    replica for everyone)."""
    a = FakeReplica("a", fail={i: (lambda: ReplicaHTTPError(
        "bad prompt", 400, reason="bad_request")) for i in range(4)})
    r = _router({"a": a}, breaker_threshold=2)
    for _ in range(4):
        with pytest.raises(RequestFailed):
            r.generate([1, 2, 3], max_new_tokens=2)
    assert r._replicas["a"].breaker.state == CLOSED
    assert r.registry.get("router.breaker_trips_total").value == 0


def test_inprocess_caller_fault_maps_to_400_not_breaker(tiny_gpt):
    """Engine-side argument validation (a bad seed) through the
    IN-PROCESS transport is the caller's fault too — surfaced as a
    non-retryable 400 exactly like httpd would send, never fed to the
    replica's breaker (the HTTP transport's 4xx rule, mirrored; a bad
    client must not blackball a healthy replica on any transport)."""
    eng = _engine(tiny_gpt)
    r = _router({"r0": InProcessReplica("r0", eng)},
                breaker_threshold=2)
    r.probe_once()
    for _ in range(3):
        with pytest.raises(RequestFailed) as ei:
            r.generate([1, 2, 3], max_new_tokens=2, seed=-1)
        assert isinstance(ei.value.cause, ReplicaHTTPError)
        assert ei.value.cause.status == 400
        assert ei.value.cause.reason == "bad_request"
    assert r._replicas["r0"].breaker.state == CLOSED
    assert r.registry.get("router.retries_total").value == 0
    assert r.registry.get("router.breaker_trips_total").value == 0


def test_cancelled_attempt_releases_halfopen_trial():
    """A router-cancelled attempt (hedge loser, shutdown) during a
    HALF_OPEN trial releases the trial slot — neither success nor
    failure — so the breaker cannot wedge in HALF_OPEN forever."""
    b = CircuitBreaker(threshold=1, cooldown_s=0.0)
    b.record_failure()
    assert b.state == OPEN
    assert b.acquire()                   # HALF_OPEN, trial in flight
    b.release_trial()
    assert b.state == HALF_OPEN and b.peek()
    assert b.acquire()                   # the NEXT request can trial
    b.record_success()
    assert b.state == CLOSED
    # through the router's attempt path: an aborted dispatch on a
    # half-open replica hands the slot back
    a = FakeReplica("a", delay_s=0.5)
    r = _router({"a": a}, breaker_threshold=1)
    br = r._replicas["a"].breaker
    br.record_failure()
    assert br.acquire()
    assert br.state == HALF_OPEN
    failures_before = br.failures
    with pytest.raises(ReplicaAbandoned):
        r._attempt(r._replicas["a"],
                   {"prompt": [1], "max_new_tokens": 1}, rid=0,
                   abort_extra=lambda: True)
    assert br.state == HALF_OPEN and br.peek()
    assert br.failures == failures_before   # cancellation not counted


def test_http_retry_after_accepts_both_header_forms():
    """Retry-After is delta-seconds OR an HTTP-date (RFC 7231 —
    proxies emit the date form); unparseable values degrade to None
    instead of crashing the 503 handler."""
    import datetime
    from email.utils import format_datetime
    from paddle_tpu.serving import HttpReplicaClient
    c = HttpReplicaClient("http://nowhere")
    assert c._retry_after_s("1.5") == 1.5
    assert c._retry_after_s(None) is None
    assert c._retry_after_s("not a date") is None
    future = (datetime.datetime.now(datetime.timezone.utc)
              + datetime.timedelta(seconds=30))
    got = c._retry_after_s(format_datetime(future, usegmt=True))
    assert got is not None and 0.0 <= got <= 31.0
    past = (datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(seconds=30))
    assert c._retry_after_s(format_datetime(past, usegmt=True)) == 0.0


def test_disconnect_after_eos_does_not_redispatch():
    """A salvaged stream that already ends in EOS is WHOLE: resuming
    it would generate past the EOS — the router must serve it as-is
    even though max_new_tokens is not exhausted."""
    a = FakeReplica("a")
    b = FakeReplica("b")
    r = _router({"a": a, "b": b})
    pa = _prompt_on(r, "a")
    a.fail = {0: lambda: NetDisconnect("mid-body",
                                       emitted=[20, 30, 7])}
    out = r.generate(list(pa), max_new_tokens=6, eos_token_id=7)
    assert out["generated"] == [20, 30, 7]
    assert b.payloads == []        # nothing re-dispatched past EOS
    assert a.payloads[0]["eos_token_id"] == 7
    # "attempts" counts DISPATCHES: one was made (it disconnected but
    # delivered the whole stream), none re-dispatched
    assert out["attempts"] == 1


def test_caller_timeout_caps_attempt_transport_budget():
    """A caller deadline shrinks each attempt's transport timeout —
    one slow attempt must not overrun the caller's budget by the
    policy-wide 60s default."""
    a = FakeReplica("a")
    r = _router({"a": a}, request_timeout_s=60.0)
    r.generate([1, 2, 3], max_new_tokens=2, timeout=0.5)
    assert a.payloads[0]["timeout_s"] <= 0.5
    r.generate([1, 2, 3], max_new_tokens=2)
    assert a.payloads[1]["timeout_s"] == 60.0   # no deadline: policy


def test_http_client_maps_connect_phase_reset_retryable():
    """A URLError WRAPPING a connection reset (replica died
    mid-handshake) maps to NetDisconnect — retryable like any other
    transport death, not an anonymous non-retryable error."""
    import urllib.error
    from paddle_tpu.serving import HttpReplicaClient
    c = HttpReplicaClient("http://nowhere")
    got = c._map_net(urllib.error.URLError(
        ConnectionResetError(104, "reset by peer")), "generate")
    assert isinstance(got, NetDisconnect)
    got = c._map_net(urllib.error.URLError(
        ConnectionRefusedError(111, "refused")), "generate")
    assert isinstance(got, NetRefused)


def test_routerd_replica_spec_parsing():
    """NAME=URL splits on the first '=' ONLY when the left side is a
    name — a bare URL with '=' in its query string stays whole."""
    from paddle_tpu.serving.routerd import parse_replica_spec
    assert parse_replica_spec("a=http://h:1") == ("a", "http://h:1")
    assert parse_replica_spec("http://h:8000") == \
        ("h:8000", "http://h:8000")
    assert parse_replica_spec("http://h:8000/v1?key=abc") == \
        ("h:8000/v1?key=abc", "http://h:8000/v1?key=abc")


def test_routerd_main_fails_fast_when_no_replica_answers():
    """A fleet where NO replica answers its first probe is a
    configuration error (typo'd address): routerd exits instead of
    serving guaranteed 503s."""
    from paddle_tpu.serving import routerd
    with pytest.raises(SystemExit):
        routerd.main(["--replica", "http://127.0.0.1:9",
                      "--port", "0"])


# ---------------------------------------------------------------------------
# routerd: the HTTP front door (fake replicas over a real socket)
# ---------------------------------------------------------------------------

def _http(method, url, body=None, timeout=5.0):
    import json
    import urllib.error
    import urllib.request
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(
                resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_routerd_endpoints_and_json_error_contract():
    """RouterServer speaks the router's whole surface over a real
    socket — /generate (carrying ``replica`` + ``attempts``),
    /healthz, /livez, /readyz, /replicas, /metrics — and every error
    is JSON with a machine-readable ``reason``."""
    from paddle_tpu.serving import RouterServer
    reps = {"a": FakeReplica("a"), "b": FakeReplica("b")}
    r = _router(reps, probe_interval_s=0.02)
    with RouterServer(r, port=0) as srv:
        code, out, _ = _http("POST", srv.address + "/generate",
                             {"prompt": [3, 4, 5],
                              "max_new_tokens": 3})
        assert code == 200
        assert out["generated"] == FakeReplica.continuation(
            [3, 4, 5], 3)
        assert out["replica"] in ("a", "b") and out["attempts"] == 1
        code, h, _ = _http("GET", srv.address + "/healthz")
        assert code == 200 and h["live"] and h["ready"]
        assert h["replicas_total"] == 2
        code, h, _ = _http("GET", srv.address + "/livez")
        assert code == 200 and h["live"]
        code, h, _ = _http("GET", srv.address + "/readyz")
        assert code == 200 and h["ready"]
        code, table, _ = _http("GET", srv.address + "/replicas")
        assert {row["name"] for row in table["replicas"]} == \
            {"a", "b"}
        import urllib.request
        with urllib.request.urlopen(srv.address + "/metrics",
                                    timeout=5.0) as resp:
            text = resp.read().decode()
            ctype = resp.headers.get("Content-Type", "")
        assert "router_requests_total 1" in text
        assert ctype.startswith("text/plain")
        code, body, _ = _http("GET", srv.address + "/nope")
        assert code == 404 and body["reason"] == "not_found"
        code, body, _ = _http("POST", srv.address + "/generate",
                              {"prompt": []})
        assert code == 400 and body["reason"] == "bad_request"
        # stdlib-generated errors (unsupported method) keep the JSON
        # contract AND close the connection: the unread PUT body must
        # not desync a keep-alive client into parsing it as the next
        # request line
        import http.client
        import json as _json
        conn = http.client.HTTPConnection(srv.host, srv.port,
                                          timeout=5.0)
        conn.request("PUT", "/generate", body=b'{"x": 1}',
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 501
        assert resp.headers.get("Content-Type") == "application/json"
        assert resp.headers.get("Connection") == "close"
        assert _json.loads(resp.read())["reason"] == "http_501"
        conn.close()
        # the whole fleet drains -> not ready, generate sheds with a
        # reason (the prober flips the states; poll its cadence)
        for rep in reps.values():
            rep.health = lambda: {"draining": True}
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            code, body, _ = _http("GET", srv.address + "/readyz")
            if code == 503:
                break
            time.sleep(0.01)
        assert code == 503 and body["reason"] == "no_replicas"
        code, body, _ = _http("POST", srv.address + "/generate",
                              {"prompt": [1, 2]})
        assert code == 503 and body["reason"] == "no_replicas"


def _load_timeline_tool():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "timeline.py")
    spec = importlib.util.spec_from_file_location("timeline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_timeline_expands_router_into_per_replica_sources(tmp_path):
    """tools/timeline.py --router expands a routerd base URL via its
    /replicas registry into the router's own trace plus one source
    per HTTP-addressable replica — one pid each in the merge, named
    by the registry row (a source's self-reported process_name is
    dropped: it carries a host pid, ambiguous on a shared host);
    replicas without a fetchable address are skipped, not fatal."""
    import json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from paddle_tpu.serving import HttpReplicaClient, RouterServer

    replica_trace = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 4242, "tid": 0,
         "args": {"name": "paddle_tpu-serving pid=4242"}},
        {"name": "tick", "ph": "X", "ts": 1.0, "dur": 5.0,
         "pid": 4242, "tid": 0, "cat": "serving"}]}

    class Stub(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def do_GET(self):
            obj = ({"status": "ok", "queue_depth": 0, "slots_free": 2}
                   if self.path == "/healthz" else replica_trace)
            data = json.dumps(obj).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    stub = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    stub_url = f"http://127.0.0.1:{stub.server_address[1]}"
    try:
        # "gone" has a fetchable-LOOKING address but nothing answers:
        # the replica-kill scenario — the merge must skip it with a
        # note, not crash with no timeline at all
        r = _router({"web": HttpReplicaClient(stub_url),
                     "local": FakeReplica("local"),
                     "gone": HttpReplicaClient("http://127.0.0.1:9")},
                    probe_interval_s=30.0)
        r.probe_once()                  # router trace gets probe spans
        tl = _load_timeline_tool()
        with RouterServer(r, port=0) as srv:
            pairs = tl.router_sources(srv.address)
            assert [lbl for lbl, _ in pairs] == \
                ["router", "replica:web", "replica:gone"]
            assert pairs[1][1] == stub_url + "/debug/trace"
            out = tmp_path / "fleet.json"
            assert tl.main(["--router", srv.address,
                            "--out", str(out)]) == 0
        import json as _json
        merged = _json.loads(out.read_text())
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}
        names = {(e["pid"], e["args"]["name"])
                 for e in merged["traceEvents"]
                 if e.get("name") == "process_name"}
        assert names == {(0, "router"), (1, "replica:web")}
        assert any(e.get("name") == "probe" and e["pid"] == 0
                   for e in merged["traceEvents"])
        assert any(e.get("name") == "tick" and e["pid"] == 1
                   for e in merged["traceEvents"])
    finally:
        stub.shutdown()
        stub.server_close()


@pytest.mark.slow
def test_routerd_fleet_failover_over_real_sockets(tiny_gpt):
    """End-to-end over real sockets: two EngineServer replicas behind
    a routerd.  A request lands on its affinity target; that server
    dies; the next request pays one refused hop and fails over — the
    HTTP caller sees every request answered, token-identical to
    ``generate()``, and the fleet timeline merges router + replicas
    with one pid each."""
    from paddle_tpu.serving import (EngineServer, HttpReplicaClient,
                                    RouterServer)
    ea, eb = _engine(_fresh_model()), _engine(_fresh_model())
    sa = EngineServer(ea, port=0).start()
    sb = EngineServer(eb, port=0).start()
    killed_a = False
    try:
        r = _router({"a": HttpReplicaClient(sa.address),
                     "b": HttpReplicaClient(sb.address)},
                    retry_max=3, probe_interval_s=0.05,
                    request_timeout_s=10.0)
        with RouterServer(r, port=0) as srv:
            pa = _prompt_on(r, "a")
            ref = tiny_gpt.generate(
                paddle.to_tensor(np.asarray([pa], np.int32)),
                max_new_tokens=4).numpy()[0]
            code, out, _ = _http("POST", srv.address + "/generate",
                                 {"prompt": list(map(int, pa)),
                                  "max_new_tokens": 4}, timeout=60.0)
            assert code == 200 and out["replica"] == "a"
            assert out["ids"] == [int(x) for x in ref]
            # whole-fleet timeline before the kill: 3 sources, 3 pids
            tl = _load_timeline_tool()
            pairs = tl.router_sources(srv.address)
            assert [lbl for lbl, _ in pairs] == \
                ["router", "replica:a", "replica:b"]
            merged = tl.merge_traces(
                [tl.load_trace(src) for _, src in pairs],
                labels=[lbl for lbl, _ in pairs])
            assert {e["pid"] for e in merged["traceEvents"]} == \
                {0, 1, 2}
            # kill replica a's server: connection refused from now on
            sa.close()
            killed_a = True
            code, out, _ = _http("POST", srv.address + "/generate",
                                 {"prompt": list(map(int, pa)),
                                  "max_new_tokens": 4}, timeout=60.0)
            assert code == 200 and out["replica"] == "b"
            assert out["ids"] == [int(x) for x in ref]
            # the router learns of the death either way: traffic paid
            # a refused hop and failed over, or the background prober
            # got there first and the pick skipped the corpse
            assert out["attempts"] >= 2 or any(
                ev[0] == "probe" and ev[1] == "a"
                and ev[2] in (DEGRADED, DEAD)
                for ev in r.route_log())
    finally:
        if not killed_a:
            sa.close()
        sb.close()


# ---------------------------------------------------------------------------
# supervisor incarnations: breaker reset + stale-probe fencing
# ---------------------------------------------------------------------------

def _fake_engine():
    """The minimal engine surface InProcessReplica.probe() reads."""
    import types
    return types.SimpleNamespace(
        queue=types.SimpleNamespace(depth=lambda: 0),
        scheduler=types.SimpleNamespace(free_count=lambda: 4),
        num_slots=4)


def test_incarnation_bump_resets_breaker_and_history():
    """A replica respawned on the same URL (supervisor bumps the
    incarnation) must NOT inherit its dead predecessor's breaker: the
    successor's first probe swaps in a fresh CLOSED breaker and
    zeroes the health history, instead of walking OPEN -> HALF_OPEN
    -> trial like a same-process recovery would."""
    rep_client = InProcessReplica("a", _fake_engine())
    r = _router({"a": rep_client}, breaker_threshold=2)
    rep = r._reps()[0]
    r.probe_once()
    assert rep.incarnation == 0
    assert rep.signals["incarnation"] == 0
    # predecessor dies mid-traffic: breaker trips OPEN, probes fail
    old_breaker = rep.breaker
    old_breaker.record_failure()
    old_breaker.record_failure()
    assert old_breaker.state == OPEN
    rep_client.kill()
    r.probe_once()
    assert rep.probe_failures == 1
    # the supervisor respawns it: NEW incarnation on the old address
    rep_client.revive(bump_incarnation=True)
    r.probe_once()
    assert rep.incarnation == 1
    assert rep.breaker is not old_breaker      # atomic swap
    assert rep.breaker.state == CLOSED
    assert rep.probe_failures == 0
    assert rep.state == HEALTHY
    assert ("incarnation", "a", 1) in r.log
    # the reset is visible on every surface: registry view + gauge
    assert r.replicas()[0]["incarnation"] == 1
    g = r.registry.gauge("router.replica_incarnation.a", "")
    assert g.value == 1
    # a stale failure landing on the OLD breaker object (an in-flight
    # attempt that started before the respawn) cannot poison the
    # successor's fresh breaker
    old_breaker.record_failure()
    assert rep.breaker.state == CLOSED


def test_stale_probe_from_dead_incarnation_is_discarded():
    """The stale-probe race: a probe that left incarnation 0 before
    it died can arrive AFTER the registry already applied the
    successor's (incarnation 1) probe.  The whole stale body must be
    discarded — state, signals and breaker stay the successor's."""
    script = {"inc": 1}
    client = FakeReplica("a", health=lambda: {
        "queue_depth": 7 if script["inc"] == 0 else 0,
        "slots_free": 4,
        "draining": script["inc"] == 0,   # the corpse reported
        #   draining; applying it would stop routing to the successor
        "incarnation": script["inc"]})
    r = _router({"a": client})
    rep = r._reps()[0]
    r.probe_once()
    assert rep.incarnation == 1 and rep.state == HEALTHY
    # the delayed predecessor probe arrives late
    script["inc"] = 0
    out = r.probe_once()
    assert out["a"] == HEALTHY                # NOT draining
    assert rep.incarnation == 1
    assert rep.signals["queue_depth"] == 0    # stale signals dropped
    assert ("stale_probe", "a", 0) in r.log
    # same-incarnation probes keep applying normally
    script["inc"] = 1
    r.probe_once()
    assert rep.state == HEALTHY


def test_revive_without_bump_keeps_breaker_recovery_path():
    """Default revive() models the SAME process answering again: the
    incarnation does not advance and an OPEN breaker recovers through
    the probe-driven HALF_OPEN path, exactly as before supervisors
    existed."""
    client = InProcessReplica("a", _fake_engine())
    r = _router({"a": client}, breaker_threshold=1,
                breaker_cooldown_s=0.0)
    rep = r._reps()[0]
    r.probe_once()
    rep.breaker.record_failure()
    assert rep.breaker.state == OPEN
    client.kill()
    client.revive()
    old = rep.breaker
    r.probe_once()
    assert rep.incarnation == 0
    assert rep.breaker is old                  # no swap
    assert rep.breaker.state == HALF_OPEN      # cooled OPEN + probe
    assert not any(ev[0] == "incarnation" for ev in r.log)
