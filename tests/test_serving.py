"""Continuous-batching serving engine: token parity with per-request
generate(), slot eviction on EOS, admission under a full pool, queue
timeouts, budgeted CHUNKED PREFILL (parity, per-tick token budget,
decode-not-stalled mixed workload, mid-chunk failure recovery),
SPECULATIVE DECODING (draft-and-verify parity on both KV layouts,
exact acceptance accounting, in-flight-lane failure recovery), FUSED
ON-DEVICE SAMPLING (greedy parity with generate() on all four
dispatch layouts, seeded determinism across engines,
device-resident-cursor failure recovery, d2h metrics), HTTP
edge validation, and the metrics surface (all CPU, tiny model, tier-1
safe)."""
import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import (Engine, EngineServer, QueueFull,
                                RequestQueue, RequestTimeout, Request,
                                Proposer, PromptLookupProposer,
                                DraftModelProposer, TenantPolicy,
                                RateLimited, DeadlineShed, Rejected)


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("registry", monitor.StatRegistry())
    return Engine(model, **kw)


def _prompts(n, lens=(5, 7, 3, 9, 4, 6)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def test_engine_parity_staggered(tiny_gpt):
    """4 concurrent STAGGERED requests (two admitted mid-decode of the
    first two) produce greedy outputs token-identical to per-request
    generate() — the acceptance-criterion case."""
    eng = _engine(tiny_gpt)
    prompts = _prompts(4)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
    for _ in range(3):  # first two requests are mid-decode...
        eng.step()
    reqs += [eng.submit(p, max_new_tokens=8) for p in prompts[2:]]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        got = r.result(timeout=1)
        ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                                max_new_tokens=8).numpy()[0]
        np.testing.assert_array_equal(got, ref)
        ref_c = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                                  max_new_tokens=8,
                                  compiled=True).numpy()[0]
        np.testing.assert_array_equal(got, ref_c)


def test_slot_eviction_on_eos(tiny_gpt):
    """A request whose first generated token is its eos finishes with
    exactly that token and frees its slot."""
    eng = _engine(tiny_gpt)
    p = _prompts(1)[0]
    full = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                             max_new_tokens=8).numpy()[0]
    eos = int(full[len(p)])  # greedy first token == eos => stop at 1
    req = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    eng.step()  # admission prefill emits the first token
    assert req.done()
    got = req.result(timeout=1)
    assert got.tolist() == full[:len(p) + 1].tolist()
    assert eng.scheduler.occupancy() == 0
    assert eng.scheduler.free_count() == eng.num_slots


def test_eos_mid_sequence_matches_generate(tiny_gpt):
    """EOS a few tokens in: engine stops where generate() stops."""
    eng = _engine(tiny_gpt)
    p = _prompts(1)[0]
    full = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                             max_new_tokens=8).numpy()[0]
    eos = int(full[len(p) + 3])  # 4th generated token
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=8,
                            eos_token_id=eos).numpy()[0]
    req = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    eng.run_until_idle()
    np.testing.assert_array_equal(req.result(timeout=1), ref)


def test_admission_under_full_pool(tiny_gpt):
    """More requests than slots: the overflow waits in the queue, is
    admitted as slots free, and still decodes to parity."""
    eng = _engine(tiny_gpt, num_slots=2)
    prompts = _prompts(5)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()
    assert eng.scheduler.occupancy() == 2      # pool is full...
    assert eng.queue.depth() == 3              # ...overflow queued
    eng.run_until_idle()
    assert eng.scheduler.occupancy() == 0
    assert eng.queue.depth() == 0
    for p, r in zip(prompts, reqs):
        ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                                max_new_tokens=6).numpy()[0]
        np.testing.assert_array_equal(r.result(timeout=1), ref)


def test_queue_timeout(tiny_gpt):
    """A request whose deadline passes while the pool is full is failed
    with RequestTimeout at its admission attempt, never decoded."""
    eng = _engine(tiny_gpt, num_slots=1)
    p = _prompts(1)[0]
    blocker = eng.submit(p, max_new_tokens=12)
    eng.step()  # blocker owns the only slot
    doomed = eng.submit(p, max_new_tokens=4, timeout=0.01)
    time.sleep(0.03)
    eng.step()  # admission attempt happens with the deadline passed
    assert doomed.done()
    with pytest.raises(RequestTimeout):
        doomed.result(timeout=1)
    assert eng.registry.get("serving.requests_timeout").value == 1
    eng.run_until_idle()
    assert blocker.result(timeout=1).shape[0] == len(p) + 12


def test_request_queue_deadline_unit():
    """RequestQueue.pop_ready fails expired entries in FIFO order and
    returns the first live one."""
    q = RequestQueue()
    expired = Request([1, 2], 4, timeout=-1.0)  # already past deadline
    live = Request([3, 4], 4)
    q.put(expired)
    q.put(live)
    got, timed_out = q.pop_ready()
    assert got is live
    assert timed_out == [expired]
    assert expired.done() and isinstance(expired.error, RequestTimeout)


def test_submit_validation_and_queue_bound(tiny_gpt):
    eng = _engine(tiny_gpt, num_slots=1, max_seq_len=16, max_queue=1)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(10, np.int32), max_new_tokens=10)  # > 16
    eng.submit(np.zeros(4, np.int32), max_new_tokens=4)
    with pytest.raises(QueueFull):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=4)


def test_submit_rejects_bad_sampling_params(tiny_gpt):
    """Sampling params are validated at the edge (a crash inside the
    engine loop thread would strand every in-flight request)."""
    eng = _engine(tiny_gpt)
    p = np.zeros(4, np.int32)
    for kw in ({"temperature": 0.0}, {"temperature": -1.0},
               {"top_p": 0.0}, {"top_p": 1.5}, {"top_k": -3}):
        with pytest.raises(ValueError):
            eng.submit(p, max_new_tokens=2, **kw)
    # top_k beyond the vocab clamps instead of crashing the loop
    r = eng.submit(p, max_new_tokens=3, top_k=10 ** 6, seed=0)
    eng.run_until_idle()
    assert r.result(timeout=1).shape[0] == 7


def test_step_failure_recovers_engine(tiny_gpt, monkeypatch):
    """A tick that raises (transient XLA error) fails the in-flight
    requests loudly, rebuilds the donated pools, and leaves the engine
    serving — for EVERY driver, not just the background loop."""
    eng = _engine(tiny_gpt)
    req = eng.submit(_prompts(1)[0], max_new_tokens=6)
    eng.step()  # prefill + first decode tick

    def boom(active, tr):
        raise RuntimeError("synthetic dispatch failure")

    monkeypatch.setattr(eng, "_dispatch_decode", boom)
    with pytest.raises(RuntimeError):
        eng.step()
    with pytest.raises(RuntimeError, match="engine step failed"):
        req.result(timeout=1)
    assert eng.scheduler.occupancy() == 0
    monkeypatch.undo()
    # engine still serves correctly after recovery
    p = _prompts(2)[1]
    r2 = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=6).numpy()[0]
    np.testing.assert_array_equal(r2.result(timeout=1), ref)


@pytest.mark.parametrize("temp,top_k,top_p", [
    (0.7, 5, 1.0), (1.0, 0, 0.9), (1.3, 8, 0.75), (1.0, 3, 1.0)])
def test_filter_logits_lanes_matches_model_filter(temp, top_k, top_p):
    """The engine's per-lane sampling filter (traced params,
    ``programs.filter_logits_lanes``) must stay equivalent to
    GPTModel._filter_logits (same kept set and filtered values) — the
    two implementations are the documented parity contract between
    engine sampling and generate() sampling."""
    import jax.numpy as jnp
    from paddle_tpu.models.programs import filter_logits_lanes
    rng = np.random.RandomState(3)
    rows = jnp.asarray(rng.randn(3, 64).astype(np.float32) * 3)
    ref = np.asarray(GPTModel._filter_logits(rows, temp, top_k, top_p))
    got = np.asarray(filter_logits_lanes(
        rows, jnp.full((3,), temp, jnp.float32),
        jnp.full((3,), top_k, jnp.int32),
        jnp.full((3,), top_p, jnp.float32)))
    kept_ref, kept_got = ref > -1e8, got > -1e8
    np.testing.assert_array_equal(kept_got, kept_ref)
    np.testing.assert_allclose(got[kept_got], ref[kept_ref], rtol=1e-5)


# ---------------------------------------------------------------------------
# Budgeted chunked prefill (Engine(prefill_chunk=..., tick_token_budget=...))
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mid_gpt():
    """2-layer model with a LONG position table: room for the mixed
    long-prompt/short-decode workload that tiny's 64 positions cannot
    hold (still seconds-scale on CPU — tier-1 safe)."""
    paddle.seed(0)
    m = GPTModel(num_layers=2, hidden_size=64, num_heads=4,
                 vocab_size=128, max_position=256, dropout=0.0)
    m.eval()
    return m


def test_chunked_parity_contiguous(tiny_gpt):
    """prefill_chunk on the contiguous engine: staggered requests stay
    token-identical to the unchunked engine and generate(), and every
    chunk of every prompt shares ONE compiled program."""
    eng = _engine(tiny_gpt, prefill_chunk=4, tick_token_budget=8)
    ref_eng = _engine(tiny_gpt)                      # unchunked A/B
    prompts = _prompts(4)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
    for _ in range(3):                               # mid-decode arrivals
        eng.step()
    reqs += [eng.submit(p, max_new_tokens=8) for p in prompts[2:]]
    eng.run_until_idle()
    ref_reqs = [ref_eng.submit(p, max_new_tokens=8) for p in prompts]
    ref_eng.run_until_idle()
    for p, r, rr in zip(prompts, reqs, ref_reqs):
        got = r.result(timeout=1).tolist()
        assert got == rr.result(timeout=1).tolist()
        ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                                max_new_tokens=8).numpy()[0].tolist()
        assert got == ref
    # 4 prompt lengths, many chunk dispatches, ONE compiled program
    assert len(tiny_gpt._chunk_prefill_fn_cache) == 1


def test_chunked_parity_paged(tiny_gpt):
    """prefill_chunk + kv_block_size: chunked paged prefill (including
    prefix-cache adoption mid-prompt) stays token-identical to
    generate(), with ONE compiled paged chunk program."""
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, 128, (20,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, 128, (k,))
                               .astype(np.int32)]) for k in (3, 5, 4, 6)]
    refs = [tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                              max_new_tokens=6).numpy()[0].tolist()
            for p in prompts]
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, kv_block_size=8,
                  prefill_chunk=4, tick_token_budget=8)
    first = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()          # prompt 0's blocks now cached
    rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    eng.run_until_idle()
    outs = [first.result(timeout=1).tolist()] + \
        [r.result(timeout=1).tolist() for r in rest]
    assert outs == refs
    # adopters skipped the shared 16-token span (2 full 8-token blocks)
    assert reg.get("serving.prefix_hits").value == 3
    assert reg.get("serving.prefix_hit_tokens").value == 3 * 16
    assert len(tiny_gpt._paged_chunk_prefill_fn_cache) == 1


def test_chunked_mixed_workload_decode_not_stalled(mid_gpt):
    """The tentpole behavior on a mixed workload: a LONG prompt
    arriving during active decode never pauses token emission — each tick spends at most tick_token_budget
    prompt tokens on chunks and still decodes every DECODING slot."""
    reg = monitor.StatRegistry()
    eng = Engine(mid_gpt, num_slots=4, max_seq_len=256, registry=reg,
                 prefill_chunk=16, tick_token_budget=32)
    rng = np.random.RandomState(3)
    shorts = [rng.randint(0, 128, (8,)).astype(np.int32)
              for _ in range(2)]
    long_p = rng.randint(0, 128, (150,)).astype(np.int32)
    srefs = [mid_gpt.generate(paddle.to_tensor(p[None, :]),
                              max_new_tokens=24).numpy()[0].tolist()
             for p in shorts]
    lref = mid_gpt.generate(paddle.to_tensor(long_p[None, :]),
                            max_new_tokens=8).numpy()[0].tolist()
    sreqs = [eng.submit(p, max_new_tokens=24) for p in shorts]
    for _ in range(4):
        eng.step()                       # shorts actively decoding
    lreq = eng.submit(long_p, max_new_tokens=8)
    pf = reg.get("serving.prefill_tokens")
    ticks_to_first = 0
    while not lreq.generated:
        before = [len(r.generated) for r in sreqs]
        tok_before = pf.value
        eng.step()
        ticks_to_first += 1
        assert ticks_to_first <= 20, "long prompt never finished prefill"
        # the budget strictly bounds the tick's prefill spend
        assert pf.value - tok_before <= 32
        # decode never stalls: every decoding short emitted this tick
        for r, b in zip(sreqs, before):
            assert len(r.generated) == b + 1
        # the decode_batch gauge counts exactly the DECODING slots
        expect = 3 if lreq.generated else 2
        assert reg.get("serving.decode_batch").value == expect
    # 150 prompt tokens / 32-token budget = 5 ticks of chunking;
    # chunk dispatches = 1 per short prompt + ceil(150/16) for the long
    assert ticks_to_first == 5
    assert reg.get("serving.prefill_chunks").value == 2 + 10
    eng.run_until_idle()
    assert [r.result(timeout=1).tolist() for r in sreqs] == srefs
    assert lreq.result(timeout=1).tolist() == lref
    # the stall histogram observed the interleaved ticks and renders
    h = reg.get("serving.decode_stall_ms")
    assert h.count > 0
    assert h.percentile(99) >= 0.0
    assert "serving_decode_stall_ms_bucket" in \
        monitor.render_prometheus(reg)


def test_chunked_paged_failure_mid_prompt_recovers(tiny_gpt,
                                                  monkeypatch):
    """Step-failure recovery with a PARTIALLY-PREFILLED paged slot in
    flight: a chunk dispatch that dies mid-prompt fails every waiter
    loudly (the half-prefilled one included), rebuilds the pools with
    all block refcounts back to zero, and the next submit completes."""
    reg = monitor.StatRegistry()
    eng = Engine(tiny_gpt, num_slots=2, max_seq_len=48, registry=reg,
                 kv_block_size=8, prefill_chunk=8, tick_token_budget=8)
    short = _prompts(1)[0]
    sreq = eng.submit(short, max_new_tokens=12)
    eng.step()
    eng.step()                            # short actively decoding
    long_p = np.random.RandomState(8).randint(0, 128, (30,)) \
        .astype(np.int32)
    lreq = eng.submit(long_p, max_new_tokens=4)
    eng.step()                            # long admitted, 1 of 4 chunks
    slot = next(s for s in eng.scheduler.busy_slots()
                if s.request is lreq)
    assert 0 < slot.prefilled < len(long_p)   # mid-prompt, PREFILLING
    assert eng.block_pool.in_use() > 0

    def boom(slot, n):
        raise RuntimeError("synthetic chunk dispatch failure")

    monkeypatch.setattr(eng, "_run_chunk", boom)
    with pytest.raises(RuntimeError):
        eng.step()
    with pytest.raises(RuntimeError, match="engine step failed"):
        sreq.result(timeout=1)
    with pytest.raises(RuntimeError, match="engine step failed"):
        lreq.result(timeout=1)            # the PREFILLING waiter too
    monkeypatch.undo()
    assert eng.scheduler.occupancy() == 0
    assert eng.block_pool.in_use() == 0   # pools rebuilt...
    assert all(eng.block_pool.refcount(b) == 0
               for b in range(eng.block_pool.num_blocks))
    r2 = eng.submit(long_p, max_new_tokens=4)
    eng.run_until_idle()                  # ...and serving continues
    ref = tiny_gpt.generate(paddle.to_tensor(long_p[None, :]),
                            max_new_tokens=4).numpy()[0].tolist()
    assert r2.result(timeout=1).tolist() == ref


def test_chunked_param_validation(tiny_gpt):
    with pytest.raises(ValueError, match="divide"):
        _engine(tiny_gpt, prefill_chunk=7)          # 48 % 7 != 0
    with pytest.raises(ValueError, match="tick_token_budget"):
        _engine(tiny_gpt, prefill_chunk=8, tick_token_budget=4)
    with pytest.raises(ValueError, match="requires prefill_chunk"):
        _engine(tiny_gpt, tick_token_budget=8)


# ---------------------------------------------------------------------------
# Speculative decoding (Engine(spec_k=..., proposer=...), serving/spec.py)
# ---------------------------------------------------------------------------

def _gen_ref(model, p, n):
    return model.generate(paddle.to_tensor(p[None, :]),
                          max_new_tokens=n).numpy()[0].tolist()


def test_prompt_lookup_proposer_unit():
    """n-gram drafting against the history: most recent earlier
    occurrence wins, the trailing pattern itself never matches, and
    short/matchless histories draft nothing (the engine pads)."""
    prop = PromptLookupProposer(ngram=2)
    #          0  1  2  3  4  5  6  7
    history = [5, 9, 7, 3, 5, 9, 4, 5, 9]
    # trailing bigram (5, 9) last occurred at 4..5 -> continue with 4, 5
    assert prop.propose(history, 2).tolist() == [4, 5]
    assert prop.propose(history, 4).tolist() == [4, 5, 9]  # clipped tail
    assert prop.propose([1, 2, 3, 4], 3).tolist() == []    # no match
    assert prop.propose([1, 2], 3).tolist() == []          # too short
    with pytest.raises(ValueError):
        PromptLookupProposer(ngram=0)


def test_spec_param_validation(tiny_gpt):
    with pytest.raises(ValueError, match="spec_k must be"):
        _engine(tiny_gpt, spec_k=0)
    with pytest.raises(ValueError, match="requires spec_k"):
        _engine(tiny_gpt, proposer=PromptLookupProposer())
    bad = type("P", (Proposer,), {"vocab_size": 999})()
    with pytest.raises(ValueError, match="vocab"):
        _engine(tiny_gpt, spec_k=2, proposer=bad)
    # the speculative window margin tightens the capacity rule
    eng = _engine(tiny_gpt, spec_k=4, max_seq_len=16)
    with pytest.raises(ValueError, match="spec_k"):
        eng.submit(np.zeros(6, np.int32), max_new_tokens=8)  # 6+8+4 > 16
    eng.submit(np.zeros(4, np.int32), max_new_tokens=8)      # 4+8+4 = 16


def test_spec_parity_contiguous_vs_plain_and_chunked(tiny_gpt):
    """The acceptance criterion: Engine(spec_k=4, PromptLookupProposer)
    greedy outputs are token-identical to the non-speculative engine
    (unchunked AND chunked) and to generate(), with staggered
    mid-decode admissions."""
    prompts = _prompts(4)
    outs = {}
    for name, kw in (("spec", dict(spec_k=4,
                                   proposer=PromptLookupProposer())),
                     ("plain", dict()),
                     ("chunked", dict(prefill_chunk=4,
                                      tick_token_budget=8)),
                     ("spec+chunked", dict(spec_k=4, prefill_chunk=4,
                                           tick_token_budget=8)),
                     ("spec+chunked+paged", dict(spec_k=4,
                                                 prefill_chunk=4,
                                                 tick_token_budget=8,
                                                 kv_block_size=8))):
        eng = _engine(tiny_gpt, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
        for _ in range(2):
            eng.step()                   # mid-decode arrivals
        reqs += [eng.submit(p, max_new_tokens=8) for p in prompts[2:]]
        eng.run_until_idle()
        outs[name] = [r.result(timeout=1).tolist() for r in reqs]
    assert all(o == outs["plain"] for o in outs.values()), \
        {k: v for k, v in outs.items() if v != outs["plain"]}
    for p, got in zip(prompts, outs["spec"]):
        assert got == _gen_ref(tiny_gpt, p, 8)


def test_spec_parity_paged_with_prefix_reuse(tiny_gpt):
    """Speculative decode over the PAGED layout, including adoption of
    a cached prompt prefix: still token-identical to generate(), and
    rejected-lane writes never corrupt shared blocks (the adopters'
    outputs would diverge if they did)."""
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, 128, (16,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, 128, (k,))
                               .astype(np.int32)]) for k in (3, 5, 4)]
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, kv_block_size=8, spec_k=4)
    first = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()              # prompt 0's blocks now cached
    rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    eng.run_until_idle()
    outs = [first.result(timeout=1).tolist()] + \
        [r.result(timeout=1).tolist() for r in rest]
    assert outs == [_gen_ref(tiny_gpt, p, 6) for p in prompts]
    assert reg.get("serving.prefix_hits").value == 2
    # every block reference was returned at eviction despite the
    # speculative margin reservation
    assert eng.block_pool.in_use() == \
        eng.prefix_cache.cached_blocks()


def test_spec_compile_probe_one_program_per_layout():
    """The compile-bound guarantee of the FUSED verify dispatch:
    however many prompts, lengths, and dispatches, a fixed spec_k
    compiles exactly ONE verify program per layout
    (``_fused_spec_verify_fn_cache``)."""
    paddle.seed(0)
    model = GPTModel.from_config("tiny", dropout=0.0)
    model.eval()
    prompts = _prompts(4)
    for kw in (dict(), dict(kv_block_size=8)):
        eng = _engine(model, spec_k=3, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run_until_idle()
        for r in reqs:
            r.result(timeout=1)
    keys = sorted(k[0] for k in model._fused_spec_verify_fn_cache)
    assert keys == ["paged", "slot"], keys
    # re-serving does not grow the cache (no retrace)
    eng = _engine(model, spec_k=3)
    eng.submit(prompts[0], max_new_tokens=4)
    eng.run_until_idle()
    assert len(model._fused_spec_verify_fn_cache) == 2


class _OracleProposer(Proposer):
    """Drafts the target's own greedy continuation (precomputed) —
    every lane matches, making the acceptance accounting exactly
    predictable."""

    def __init__(self, ref_ids):
        self.ref = [int(x) for x in ref_ids]

    def propose(self, history, k):
        n = len(history)
        assert self.ref[:n] == [int(x) for x in history]
        return np.asarray(self.ref[n:n + k], np.int32)


def test_spec_acceptance_accounting_exact(tiny_gpt):
    """serving.spec_proposed / spec_accepted / spec_acceptance_rate /
    spec_tokens_per_tick count proposed vs accepted EXACTLY: an oracle
    proposer accepts every lane, so 11 post-prefill tokens of one
    request take ceil(11/4) = 3 dispatches of spec_k=3."""
    p = _prompts(1)[0]
    ref = _gen_ref(tiny_gpt, p, 12)
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, spec_k=3,
                  proposer=_OracleProposer(ref))
    req = eng.submit(p, max_new_tokens=12)
    eng.run_until_idle()
    assert req.result(timeout=1).tolist() == ref
    # prefill emits token 1; dispatches emit 4 + 4 + 3 (capped by
    # max_new_tokens): accepted lanes 3 + 3 + 2, and the final window
    # PROPOSES only the 2 lanes the request can still consume — a
    # perfect oracle therefore reads acceptance_rate exactly 1.0
    # (request length must not deflate the draft-quality gauge)
    assert reg.get("serving.spec_proposed").value == 8
    assert reg.get("serving.spec_accepted").value == 8
    assert reg.get("serving.spec_windows").value == 3
    assert reg.get("serving.spec_acceptance_rate").value == 1.0
    assert reg.get("serving.spec_tokens_per_tick").value == 3.0
    assert reg.get("serving.tokens_total").value == 12


def test_spec_empty_proposer_counts_nothing(tiny_gpt):
    """A proposer that never drafts: the window runs on pad filler
    only — one token per dispatch, outputs still exact, and NO pad
    lane is ever counted as proposed or consumed as accepted (the
    acceptance gauges measure the proposer, not the engine's
    filler)."""

    class _NeverProposer(Proposer):
        def propose(self, history, k):
            return np.zeros(0, np.int32)

    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, spec_k=4,
                  proposer=_NeverProposer())
    p = _prompts(1)[0]
    req = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    assert req.result(timeout=1).tolist() == _gen_ref(tiny_gpt, p, 6)
    assert reg.get("serving.spec_windows").value == 5  # 1 tok each
    assert reg.get("serving.spec_proposed").value == 0
    assert reg.get("serving.spec_accepted").value == 0
    assert reg.get("serving.spec_acceptance_rate").value == 0.0


def test_spec_sampling_matches_nonspec_engine(tiny_gpt):
    """Seeded sampling under speculation: lane j's logits equal the
    one-token tick's logits for the same prefix and the per-request
    rng draws once per emitted token either way, so sampled outputs
    match the non-speculative engine token-for-token."""
    p = _prompts(1)[0]
    kw = dict(max_new_tokens=8, temperature=0.8, top_k=20, seed=123)
    outs = []
    for spec in (None, 4):
        eng = _engine(tiny_gpt, spec_k=spec)
        r = eng.submit(p, **kw)
        eng.run_until_idle()
        outs.append(r.result(timeout=1).tolist())
    assert outs[0] == outs[1]


def test_spec_eos_mid_window_matches_generate(tiny_gpt):
    """EOS emitted from inside an accepted window: the engine stops
    exactly where generate() stops and discards the window's remaining
    verified lanes."""
    p = _prompts(1)[0]
    full = _gen_ref(tiny_gpt, p, 8)
    eos = int(full[len(p) + 3])
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=8,
                            eos_token_id=eos).numpy()[0].tolist()
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, spec_k=4,
                  proposer=_OracleProposer(full))
    req = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    eng.run_until_idle()
    assert req.result(timeout=1).tolist() == ref
    assert eng.scheduler.occupancy() == 0
    if len(ref) == len(p) + 4:      # EOS really was the 4th token
        # ONE window: lanes 2-4 emit tokens 2-4; the lane that
        # correctly drafted the EOS counts as accepted too
        assert reg.get("serving.spec_proposed").value == 4
        assert reg.get("serving.spec_accepted").value == 3
        assert reg.get("serving.spec_windows").value == 1


def test_spec_failure_with_inflight_lanes_recovers(tiny_gpt):
    """Step failure DURING a speculative verify (draft lanes in
    flight, paged layout): every waiter unblocks loudly, slots carry
    their lanes into eviction and come back clean, pool refcounts
    rebuild to zero, and the engine keeps serving."""
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, kv_block_size=8, spec_k=4)
    prompts = _prompts(2)
    reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.step()                       # resolves the verify dispatch
    assert all(not r.done() for r in reqs)

    def boom(*a, **kw):
        raise RuntimeError("synthetic verify dispatch failure")

    # the resolved handle is the fused verify+sample dispatch
    eng._fused_spec_fn = boom        # the NEXT verify dies mid-flight
    with pytest.raises(RuntimeError):
        eng.step()
    for r in reqs:
        with pytest.raises(RuntimeError, match="engine step failed"):
            r.result(timeout=1)
    assert eng.scheduler.occupancy() == 0
    assert all(s.spec_lanes == 0 for s in eng.scheduler.slots)
    assert eng.block_pool.in_use() == 0
    assert all(eng.block_pool.refcount(b) == 0
               for b in range(eng.block_pool.num_blocks))
    eng._fused_spec_fn = None        # re-resolve on the next tick
    r2 = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()
    assert r2.result(timeout=1).tolist() == _gen_ref(tiny_gpt,
                                                     prompts[0], 6)


@pytest.fixture(scope="module")
def cyclic_gpt():
    """Tiny model trained to emit a short cycle (the
    test_generation.py trick): prompt-lookup drafts then accept, so
    speculation actually pays (a repetitive workload)."""
    from paddle_tpu import optimizer
    from paddle_tpu.parallel.train_step import TrainStep
    paddle.seed(3)
    m = GPTModel.from_config("tiny", dropout=0.0, max_position=128)
    cyc = np.tile(np.array([11, 22, 33, 44], np.int32), 16)
    step = TrainStep(m, optimizer.Adam(
        learning_rate=5e-3, parameters=m.parameters()), loss_fn=None)
    for _ in range(60):
        lv = float(step.step([cyc[None, :-1].copy(),
                              cyc[None, 1:].copy()]).numpy())
    assert lv < 0.1, lv
    step.sync_to_layer()
    m.eval()
    return m


def test_spec_accepts_on_repetitive_workload(cyclic_gpt):
    """The speedup case: on a
    repetitive workload the prompt-lookup proposer's lanes accept —
    acceptance_rate > 0, mean accepted lanes > 1 — in far fewer
    dispatches than tokens, while staying token-identical to the
    non-speculative engine and generate()."""
    prompts = [np.tile(np.array([11, 22, 33, 44], np.int32), 3),
               np.tile(np.array([22, 33, 44, 11], np.int32), 3)]
    n_new = 24
    reg = monitor.StatRegistry()
    eng = Engine(cyclic_gpt, num_slots=2, max_seq_len=64,
                 registry=reg, spec_k=4)
    ref_eng = Engine(cyclic_gpt, num_slots=2, max_seq_len=64,
                     registry=monitor.StatRegistry())
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    n_ticks = 0
    while not eng.scheduler.idle():
        eng.step()
        n_ticks += 1
    ref_reqs = [ref_eng.submit(p, max_new_tokens=n_new)
                for p in prompts]
    ref_eng.run_until_idle()
    for p, r, rr in zip(prompts, reqs, ref_reqs):
        got = r.result(timeout=1).tolist()
        assert got == rr.result(timeout=1).tolist()
        assert got == _gen_ref(cyclic_gpt, p, n_new)
    proposed = reg.get("serving.spec_proposed").value
    accepted = reg.get("serving.spec_accepted").value
    windows = reg.get("serving.spec_windows").value
    rate = reg.get("serving.spec_acceptance_rate").value
    assert proposed > 0 and accepted > 0
    assert rate == pytest.approx(accepted / proposed)
    assert rate > 0.5                  # the cycle drafts accept
    assert accepted / windows > 1.0    # mean accepted lanes > 1
    # 2 * 24 tokens in far fewer than 2 * 24 slot-dispatches
    assert n_ticks < n_new / 2


def test_spec_draft_model_proposer(tiny_gpt):
    """DraftModelProposer: drafting with the target itself is a
    perfect oracle — full acceptance, parity intact (a real deployment
    would use a smaller model sharing the vocab)."""
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, spec_k=3,
                  proposer=DraftModelProposer(tiny_gpt))
    p = _prompts(1)[0]
    req = eng.submit(p, max_new_tokens=10)
    eng.run_until_idle()
    assert req.result(timeout=1).tolist() == _gen_ref(tiny_gpt, p, 10)
    # self-drafting accepts every lane: 9 post-prefill tokens in 3
    # dispatches emitting 4 + 4 + 1; the last window proposes 0 lanes
    # (only the bonus token fits under max_new), so the draft model
    # is never even consulted for it
    assert reg.get("serving.spec_proposed").value == 6
    assert reg.get("serving.spec_accepted").value == 6
    assert reg.get("serving.spec_acceptance_rate").value == 1.0


# ---------------------------------------------------------------------------
# Fused on-device sampling
# ---------------------------------------------------------------------------

SAMPLE_LAYOUTS = (dict(), dict(kv_block_size=8), dict(spec_k=4),
                  dict(spec_k=4, kv_block_size=8),
                  dict(prefill_chunk=4, tick_token_budget=8),
                  dict(prefill_chunk=4, tick_token_budget=8,
                       kv_block_size=8))


def test_device_sampling_greedy_parity_all_layouts(tiny_gpt):
    """The tentpole acceptance case: greedy outputs under fused
    on-device sampling are token-identical to generate() on all four
    dispatch layouts (contiguous / paged x one-token / spec) plus the
    chunked-prefill variants — the chunk/fused-tick interplay re-parks
    the device cursor on each chunk's start row — with staggered
    mid-decode admissions."""
    prompts = _prompts(4)
    refs = [_gen_ref(tiny_gpt, p, 8) for p in prompts]
    for kw in SAMPLE_LAYOUTS:
        eng = _engine(tiny_gpt, **kw)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
        for _ in range(2):
            eng.step()               # mid-decode arrivals
        reqs += [eng.submit(p, max_new_tokens=8) for p in prompts[2:]]
        eng.run_until_idle()
        assert [r.result(timeout=1).tolist() for r in reqs] == refs, kw


def test_device_sampling_parity_with_prefix_reuse(tiny_gpt):
    """Device sampling over the paged layout WITH prefix-cache
    adoption: adopters decode against cached blocks through the fused
    dispatch and stay token-identical to generate() (a stale device
    cursor or block table would diverge them)."""
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, 128, (16,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, 128, (k,))
                               .astype(np.int32)]) for k in (3, 5, 4)]
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg, kv_block_size=8)
    first = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()              # prompt 0's blocks now cached
    rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
    eng.run_until_idle()
    outs = [first.result(timeout=1).tolist()] + \
        [r.result(timeout=1).tolist() for r in rest]
    assert outs == [_gen_ref(tiny_gpt, p, 6) for p in prompts]
    assert reg.get("serving.prefix_hits").value == 2
    assert reg.get("serving.fused_sample_ticks").value > 0


def test_device_sampling_deterministic_across_engines(tiny_gpt):
    """Seeded device sampling: the rng key derives from the request
    seed + emitted-token counter (core/rng.request_key), so two
    engine instances given the same seed emit identical tokens — the
    reproducible-across-restarts contract."""
    outs = []
    for _ in range(2):
        eng = _engine(tiny_gpt)
        r = eng.submit(_prompts(1)[0], max_new_tokens=6,
                       temperature=0.8, top_k=20, top_p=0.9, seed=123)
        eng.run_until_idle()
        outs.append(r.result(timeout=1).tolist())
    assert outs[0] == outs[1]
    # and a 63-bit seed survives the two-word key transport
    big = 2 ** 62 + 12345
    outs = []
    for _ in range(2):
        eng = _engine(tiny_gpt)
        r = eng.submit(_prompts(1)[0], max_new_tokens=4,
                       temperature=0.7, seed=big)
        eng.run_until_idle()
        outs.append(r.result(timeout=1).tolist())
    assert outs[0] == outs[1]


def test_device_spec_sampling_matches_nonspec(tiny_gpt):
    """Seeded device sampling under speculation: verify-window lane j
    draws from fold(request_key, token_index) exactly like the
    one-token tick, so spec and non-spec device engines emit the same
    sampled stream."""
    p = _prompts(1)[0]
    kw = dict(max_new_tokens=8, temperature=0.8, top_k=20, seed=123)
    outs = []
    for spec in (None, 4):
        eng = _engine(tiny_gpt, spec_k=spec)
        r = eng.submit(p, **kw)
        eng.run_until_idle()
        outs.append(r.result(timeout=1).tolist())
    assert outs[0] == outs[1]


def test_fused_compile_probe_one_program_per_layout():
    """Compile-bound guarantee for the fused one-token tick: however
    many prompts and ticks, ONE fused decode+sample program per KV
    layout (sampling params are traced lanes, never constants)."""
    paddle.seed(0)
    model = GPTModel.from_config("tiny", dropout=0.0)
    model.eval()
    prompts = _prompts(4)
    for kw in (dict(), dict(kv_block_size=8)):
        eng = _engine(model, **kw)
        # a sampled and a greedy request share the same program
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts[:2]]
        reqs += [eng.submit(p, max_new_tokens=6, temperature=0.8,
                            top_p=0.9, seed=7) for p in prompts[2:]]
        eng.run_until_idle()
        for r in reqs:
            r.result(timeout=1)
    keys = sorted(k[0] for k in model._fused_decode_fn_cache)
    assert keys == ["paged", "slot"]
    eng = _engine(model)
    eng.submit(prompts[0], max_new_tokens=4)
    eng.run_until_idle()
    assert len(model._fused_decode_fn_cache) == 2


def test_device_step_failure_recovers(tiny_gpt):
    """Step-failure recovery of the fused dispatch (paged):
    the device-resident cursors die with the pools, waiters unblock
    loudly, refcounts rebuild to zero, and the next tick re-uploads
    rebuilt state — the engine keeps serving with correct outputs."""
    reg = monitor.StatRegistry()
    eng = Engine(tiny_gpt, num_slots=2, max_seq_len=48, registry=reg,
                 kv_block_size=8)
    prompts = _prompts(2)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()                           # device state now resident
    assert eng._dev_state is not None and not eng._dirty_slots

    def boom(*a, **kw):
        raise RuntimeError("synthetic fused dispatch failure")

    eng._fused_fn = boom
    with pytest.raises(RuntimeError):
        eng.step()
    for r in reqs:
        with pytest.raises(RuntimeError, match="engine step failed"):
            r.result(timeout=1)
    assert eng.scheduler.occupancy() == 0
    assert eng._dev_state is None        # cursors rebuilt on next tick:
    #   the one case besides the first tick of a whole upload
    assert not eng._dirty_slots and not eng._first_pending
    assert eng.block_pool.in_use() == 0
    assert all(eng.block_pool.refcount(b) == 0
               for b in range(eng.block_pool.num_blocks))
    eng._fused_fn = None
    r2 = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()
    assert r2.result(timeout=1).tolist() == _gen_ref(tiny_gpt,
                                                     prompts[0], 6)
    assert reg.get("serving.state_pushes").value == 2


def test_sampling_metrics(tiny_gpt):
    """The observability satellite: a tick downloads only the [B] ids
    (never the [B, V] logits) and counts as a fused tick — rendered by
    render_prometheus()."""
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg)
    r = eng.submit(_prompts(1)[0], max_new_tokens=6)
    eng.run_until_idle()
    r.result(timeout=1)
    assert reg.get("serving.fused_sample_ticks").value > 0
    text = monitor.render_prometheus(reg)
    assert "serving_d2h_bytes_per_tick" in text
    assert "serving_fused_sample_ticks" in text
    # the B int32 ids plus the bit-packed done mask (ceil(B/8) bytes —
    # the device-side stop condition's summary byte); the logits would
    # be B*V f32
    assert reg.get("serving.d2h_bytes_per_tick").value == 4 * 4 + 1


def test_submit_rejects_out_of_range_seed(tiny_gpt):
    """Seeds that cannot feed the device key derivation (negative /
    >= 2**63) fail at submit, not in the engine loop mid-decode."""
    eng = _engine(tiny_gpt)
    for bad in (-1, 2 ** 63, 2 ** 64):
        with pytest.raises(ValueError, match="seed"):
            eng.submit(_prompts(1)[0], max_new_tokens=2,
                       temperature=0.8, seed=bad)
    assert eng.queue.depth() == 0
    # boundary value is admissible
    eng = _engine(tiny_gpt)
    eng.submit(_prompts(1)[0], max_new_tokens=2, seed=2 ** 63 - 1)


# ---------------------------------------------------------------------------
# HTTP edge validation (no socket: the handler's POST path is driven
# directly with a stubbed send)
# ---------------------------------------------------------------------------

def _post_probe(engine, body):
    """Drive _Handler.do_POST without a socket; returns (code, body,
    headers) of the response the handler would have sent."""
    from paddle_tpu.serving.httpd import _Handler

    h = object.__new__(_Handler)
    h.engine = engine
    data = json.dumps(body).encode()
    h.headers = {"Content-Length": str(len(data))}
    h.rfile = io.BytesIO(data)
    h.path = "/generate"
    sent = {}

    def _send(code, payload, ctype="application/json", headers=None):
        sent["resp"] = (code, json.loads(payload), headers)

    h._send = _send
    h.do_POST()
    return sent["resp"]


def test_httpd_validates_prompt_at_edge(tiny_gpt):
    """Over-capacity / malformed prompts get a clear 400 at the edge
    instead of surfacing as an engine-side failure or timeout; nothing
    reaches the queue."""
    eng = _engine(tiny_gpt)               # never stepped on purpose
    code, body, _ = _post_probe(
        eng, {"prompt": list(range(60)), "max_new_tokens": 8})
    assert code == 400 and "capacity" in body["error"]
    code, body, _ = _post_probe(eng, {"prompt": [], "max_new_tokens": 2})
    assert code == 400 and "non-empty" in body["error"]
    code, body, _ = _post_probe(
        eng, {"prompt": [1, "x"], "max_new_tokens": 2})
    assert code == 400 and "integer" in body["error"]
    code, body, _ = _post_probe(
        eng, {"prompt": [1, 999], "max_new_tokens": 2})
    assert code == 400 and "vocabulary" in body["error"]
    code, body, _ = _post_probe(
        eng, {"prompt": [1, 2], "max_new_tokens": 0})
    assert code == 400 and "max_new_tokens" in body["error"]
    # seeds the device key derivation cannot carry: clear 400 at the
    # edge (submit raises ValueError; do_POST maps it), never a crash
    # inside the shared engine loop
    for bad in (-1, 2 ** 63):
        code, body, _ = _post_probe(
            eng, {"prompt": [1, 2], "max_new_tokens": 2,
                  "temperature": 0.8, "seed": bad})
        assert code == 400 and "seed" in body["error"], bad
    assert eng.queue.depth() == 0


def _get_probe(engine, path):
    """Drive _Handler.do_GET without a socket; returns (code, body,
    ctype) of the response the handler would have sent."""
    from paddle_tpu.serving.httpd import _Handler

    h = object.__new__(_Handler)
    h.engine = engine
    h.path = path
    sent = {}

    def _send(code, payload, ctype="application/json", headers=None):
        sent["resp"] = (code, payload, ctype)

    def _send_json(code, obj, headers=None):
        sent["resp"] = (code, obj, "application/json")

    h._send = _send
    h._send_json = _send_json
    h.do_GET()
    return sent["resp"]


def test_httpd_metrics_content_type_and_spec_healthz(tiny_gpt):
    """/metrics must carry the full exposition content type
    (version + charset — scrapers negotiate on it), and /healthz
    reports the speculative-decode gauges when spec_k is on."""
    eng = _engine(tiny_gpt, spec_k=4)
    code, _, ctype = _get_probe(eng, "/metrics")
    assert code == 200
    assert ctype == "text/plain; version=0.0.4; charset=utf-8"
    req = eng.submit(_prompts(1)[0], max_new_tokens=6)
    eng.run_until_idle()
    req.result(timeout=1)
    code, health, _ = _get_probe(eng, "/healthz")
    assert code == 200 and health["status"] == "ok"
    assert health["spec_k"] == 4
    assert 0.0 <= health["spec_acceptance_rate"] <= 1.0
    assert health["spec_tokens_per_tick"] >= 1.0
    # spec off -> the gauges stay out of the health payload
    code, health, _ = _get_probe(_engine(tiny_gpt), "/healthz")
    assert "spec_k" not in health
    text = monitor.render_prometheus(eng.registry)
    assert "serving_spec_proposed" in text
    assert "serving_spec_accepted" in text
    assert "serving_spec_acceptance_rate" in text
    assert "serving_spec_tokens_per_tick" in text


def test_httpd_queue_full_sends_retry_after(tiny_gpt):
    """The 503 shed-load response carries a Retry-After hint."""
    eng = _engine(tiny_gpt, max_queue=1)  # never stepped: queue stays full
    eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    code, body, headers = _post_probe(
        eng, {"prompt": [1, 2, 3], "max_new_tokens": 2})
    assert code == 503 and "full" in body["error"]
    assert headers and headers.get("Retry-After") == "1"


@pytest.mark.slow
def test_engine_sampling_reproducible(tiny_gpt):
    """Per-request seeded sampling: same seed, same tokens; the stream
    is per-request, so a busy pool cannot perturb it.  (slow: builds
    two engines, two full sets of prefill/decode compiles)"""
    outs = []
    for _ in range(2):
        eng = _engine(tiny_gpt)
        r = eng.submit(_prompts(1)[0], max_new_tokens=6,
                       temperature=0.8, top_k=20, seed=123)
        eng.run_until_idle()
        outs.append(r.result(timeout=1).tolist())
    assert outs[0] == outs[1]


def test_engine_metrics_exposition(tiny_gpt):
    """The acceptance surface: engine gauges/histograms land in
    render_prometheus()."""
    eng = _engine(tiny_gpt)
    reqs = [eng.submit(p, max_new_tokens=5) for p in _prompts(3)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=1)
    text = monitor.render_prometheus(eng.registry)
    assert "serving_queue_depth 0" in text
    assert "serving_slot_occupancy 0" in text
    assert "serving_tokens_total 15" in text
    assert "serving_requests_completed 3" in text
    assert 'serving_ttft_ms_bucket{le="+Inf"} 3' in text
    assert "serving_tpot_ms_count 3" in text
    assert "serving_tokens_per_sec" in text


@pytest.mark.slow
def test_background_loop_and_http(tiny_gpt):
    """End-to-end over a real socket: concurrent POSTs share the slot
    pool; /metrics and /healthz answer.  (slow: threads + sockets +
    engine-thread compiles — the verify drive covers this path too)"""
    eng = _engine(tiny_gpt)
    prompts = _prompts(3)
    refs = [tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                              max_new_tokens=6).numpy()[0].tolist()
            for p in prompts]
    with EngineServer(eng, port=0) as srv:
        results = {}

        def post(i):
            body = json.dumps({"prompt": prompts[i].tolist(),
                               "max_new_tokens": 6}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    f"{srv.address}/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=30) as resp:
                results[i] = json.loads(resp.read())

        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for i, ref in enumerate(refs):
            assert results[i]["ids"] == ref
        with urllib.request.urlopen(f"{srv.address}/healthz",
                                    timeout=10) as resp:
            health = json.loads(resp.read())
        assert health["status"] == "ok"
        assert health["slots_free"] == eng.num_slots
        with urllib.request.urlopen(f"{srv.address}/metrics",
                                    timeout=10) as resp:
            metrics = resp.read().decode()
        assert "serving_requests_completed 3" in metrics


# ---------------------------------------------------------------------------
# Tick-level tracing + flight recorder (monitor/tracing.py wired through
# the engine: per-tick phase spans, per-request lifecycle instants,
# compile events, /debug endpoints, auto-dump on step failure)
# ---------------------------------------------------------------------------

def _events_by_name(trace):
    out = {}
    for ev in trace["traceEvents"]:
        out.setdefault(ev["name"], []).append(ev)
    return out


def test_trace_mixed_engine_spans_and_lifecycle(tiny_gpt):
    """The acceptance surface: a MIXED run (paged KV + chunked prefill
    + speculative decode + device sampling) produces a chrome trace
    whose tick spans nest the phase spans (admit / prefill.chunk /
    spec.draft / decode.dispatch / d2h / emit) and whose per-request
    lifecycle instants (queued -> admitted -> prefix-adopted ->
    first-token -> finished) carry the request ids."""
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8,
                  tick_token_budget=16, spec_k=3)
    rng = np.random.RandomState(3)
    sysp = rng.randint(0, 128, (16,)).astype(np.int32)
    first = eng.submit(np.concatenate(
        [sysp, rng.randint(0, 128, (5,)).astype(np.int32)]),
        max_new_tokens=6)
    eng.run_until_idle()          # request 1 caches the shared prefix
    first.result(timeout=1)
    second = eng.submit(np.concatenate(
        [sysp, rng.randint(0, 128, (7,)).astype(np.int32)]),
        max_new_tokens=6, temperature=0.9, top_p=0.9, seed=5)
    eng.run_until_idle()
    second.result(timeout=1)
    trace = eng.chrome_trace()
    json.loads(json.dumps(trace))                 # valid Catapult JSON
    by = _events_by_name(trace)
    # the default engine pipelines (async_depth=2), so the materialize
    # wait is traced as decode.d2h_wait, not the synchronous decode.d2h
    for name in ("tick", "admit", "prefill.chunk", "spec.draft",
                 "decode.dispatch", "decode.d2h_wait", "decode.emit"):
        assert name in by, f"missing span {name!r}"
    # phase spans nest inside a tick span on the same thread
    ticks = by["tick"]
    for name in ("admit", "prefill.chunk", "decode.dispatch"):
        for ev in by[name]:
            assert any(t["tid"] == ev["tid"]
                       and t["ts"] <= ev["ts"]
                       and ev["ts"] + ev["dur"]
                       <= t["ts"] + t["dur"] + 1e-3
                       for t in ticks), f"{name} not inside any tick"
    # ts monotonic in the merged export (metadata rows excluded)
    ts = [e["ts"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    # per-request lifecycle, second request: adopted the cached prefix
    rid = second.id
    for name in ("req.queued", "req.admitted", "req.prefix_adopted",
                 "req.first_token", "req.finished"):
        assert any(e["args"].get("req") == rid for e in by[name]), \
            f"lifecycle instant {name!r} missing for request {rid}"
    # args carry the tick anatomy the timeline reader needs
    assert all("batch" in t["args"] for t in ticks)
    assert any("kv_blocks_in_use" in t["args"] for t in ticks)
    assert any(e["args"].get("accepted") is not None
               for e in by["decode.emit"])


def test_flight_recorder_dumps_on_step_failure(tiny_gpt, monkeypatch,
                                               tmp_path):
    """An injected step failure auto-dumps the flight recorder: the
    in-memory snapshot AND the flight_dir file hold the trace ring
    plus the in-flight request states AS THEY WERE at the failure
    (before recovery evicts), and the engine keeps serving after."""
    eng = _engine(tiny_gpt, flight_dir=str(tmp_path))
    req = eng.submit(_prompts(1)[0], max_new_tokens=6)
    eng.step()

    def boom(active, tr):
        raise RuntimeError("synthetic dispatch failure")

    monkeypatch.setattr(eng, "_dispatch_decode", boom)
    with pytest.raises(RuntimeError):
        eng.step()
    monkeypatch.undo()
    assert eng.last_flight is not None
    assert eng.last_flight_path is not None
    dumped = json.load(open(eng.last_flight_path))
    fr = dumped["metadata"]["flight-recorder"]
    assert "synthetic dispatch failure" in fr["error"]
    assert fr["tick"] == eng.tick_no
    slot0 = fr["requests"]["slots"][0]
    assert slot0["state"] == "decoding"          # pre-eviction state
    assert slot0["request_id"] == req.id
    assert slot0["generated"] >= 1
    # the dump is a loadable chrome trace with the tick spans retained
    names = {e["name"] for e in dumped["traceEvents"]}
    assert "tick" in names and "decode.dispatch" in names
    # step-failure evictions are traced too
    post = _events_by_name(eng.chrome_trace())
    assert any(e["args"] == {"req": req.id, "reason": "step_failure"}
               for e in post["req.evicted"])
    # engine recovered: still serves to parity
    p = _prompts(2)[1]
    r2 = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=6).numpy()[0]
    np.testing.assert_array_equal(r2.result(timeout=1), ref)


def test_debug_endpoints_smoke(tiny_gpt):
    """/debug/trace downloads the live ring as chrome-trace JSON and
    /debug/requests reports in-flight slot states (prefill progress,
    spec window) plus the queue — mid-flight and when idle."""
    eng = _engine(tiny_gpt, num_slots=1, spec_k=2)
    r1 = eng.submit(_prompts(1)[0], max_new_tokens=8)
    r2 = eng.submit(_prompts(2)[1], max_new_tokens=4)  # waits in queue
    eng.step()
    code, body, hdr = _get_probe(eng, "/debug/trace")
    assert code == 200
    trace = json.loads(body)
    assert any(e["name"] == "tick" for e in trace["traceEvents"])
    code, dbg, _ = _get_probe(eng, "/debug/requests")
    assert code == 200
    slot = dbg["slots"][0]
    assert slot["state"] == "decoding"
    assert slot["request_id"] == r1.id
    assert slot["prefilled"] == len(r1.prompt)
    assert slot["pos"] >= len(r1.prompt)
    assert dbg["queue"][0]["request_id"] == r2.id
    assert dbg["queue"][0]["queued_ms"] >= 0
    assert dbg["engine"]["spec_k"] == 2
    assert dbg["engine"]["tracing"] is True
    assert dbg["engine"]["platform"] == "cpu"
    assert dbg["engine"]["device_ids"] == eng.placement["device_ids"]
    eng.run_until_idle()
    r1.result(timeout=1)
    r2.result(timeout=1)
    code, dbg, _ = _get_probe(eng, "/debug/requests")
    assert all(s["state"] == "free" for s in dbg["slots"])
    assert dbg["queue"] == []


def test_healthz_always_reports_load_signals(tiny_gpt):
    """The router-tier load signals (queue_depth, slots_free,
    kv_blocks_free) are ALWAYS in /healthz — kv_blocks_free is null
    in contiguous mode, the pool's free count in paged mode."""
    code, health, _ = _get_probe(_engine(tiny_gpt), "/healthz")
    assert code == 200
    assert health["queue_depth"] == 0
    assert health["slots_free"] == 4
    assert health["kv_blocks_free"] is None
    paged = _engine(tiny_gpt, kv_block_size=8)
    code, health, _ = _get_probe(paged, "/healthz")
    assert health["kv_blocks_free"] == paged.block_pool.free_count()
    assert health["kv_blocks_free"] > 0
    # the router's prefix-affinity hash aligns on the block size
    assert health["kv_block_size"] == 8
    # ... and WHERE the engine runs: platform, device_kind and the ids
    # of the devices holding the pools, as jax itself reports them
    import jax
    dev = jax.devices()[0]
    assert health["platform"] == dev.platform == "cpu"
    assert health["device_kind"] == dev.device_kind
    assert health["device_ids"] == [dev.id]


def test_healthz_liveness_readiness_split(tiny_gpt):
    """Liveness vs readiness: a DRAINING engine is live but not ready
    (state "draining" — finishing up, let it land its streams), a
    WATCHDOG-FIRED one is live but not ready (state "watchdog_fired"
    — wedged mid-tick, possibly dying).  /livez answers 200 for both
    (restarting would kill the streams); /readyz answers 503 with a
    machine-readable reason so a dumb prober can act on the code and
    a smart one (the router) on the distinction."""
    eng = _engine(tiny_gpt)
    code, h, _ = _get_probe(eng, "/healthz")
    assert code == 200 and h["live"] and h["ready"]
    assert h["state"] == "ok"
    code, h, _ = _get_probe(eng, "/livez")
    assert code == 200 and h["live"]
    code, h, _ = _get_probe(eng, "/readyz")
    assert code == 200 and h["ready"]
    eng._draining = True
    code, h, _ = _get_probe(eng, "/healthz")
    assert code == 200 and h["live"] and not h["ready"]
    assert h["state"] == "draining"
    code, h, _ = _get_probe(eng, "/readyz")
    assert code == 503 and not h["ready"]
    assert h["reason"] == "draining"
    code, h, _ = _get_probe(eng, "/livez")
    assert code == 200                    # draining is NOT dying
    eng._draining = False
    eng._watchdog_fired = True
    code, h, _ = _get_probe(eng, "/readyz")
    assert code == 503 and h["reason"] == "watchdog_fired"
    code, h, _ = _get_probe(eng, "/healthz")
    assert h["state"] == "watchdog_fired" and h["watchdog_fired"]
    # watchdog beats draining: wedged is the scarier verdict
    eng._draining = True
    _, h, _ = _get_probe(eng, "/healthz")
    assert h["state"] == "watchdog_fired"


def test_httpd_errors_always_json_with_reason(tiny_gpt):
    """Every 4xx/5xx leaving httpd is JSON with a machine-readable
    ``reason`` and an application/json Content-Type — the router's
    retry classifier keys on ``reason``, never on prose."""
    from paddle_tpu.serving.httpd import _shed_reason
    from paddle_tpu.serving.request import (DeadlineShed, QueueFull,
                                            RateLimited)
    eng = _engine(tiny_gpt)
    code, body, ctype = _get_probe(eng, "/no/such/route")
    assert code == 404 and ctype == "application/json"
    assert body["reason"] == "not_found"
    code, body, _ = _post_probe(eng, {"max_new_tokens": 2})
    assert code == 400 and body["reason"] == "bad_request"
    full = _engine(tiny_gpt, max_queue=1)
    full.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
    code, body, headers = _post_probe(
        full, {"prompt": [1, 2, 3], "max_new_tokens": 2})
    assert code == 503 and body["reason"] == "queue_full"
    # the classifier's one decision table for shed-load causes —
    # "draining" comes from the engine's actual flag, never prose
    assert _shed_reason(RateLimited("slow down")) == "rate_limited"
    assert _shed_reason(DeadlineShed("too late")) == "deadline_shed"
    assert _shed_reason(QueueFull("rejected"), draining=True) == \
        "draining"
    assert _shed_reason(QueueFull("queue is full")) == "queue_full"
    # over the wire: a draining engine's shed carries the reason
    full._draining = True
    code, body, _ = _post_probe(
        full, {"prompt": [1, 2, 3], "max_new_tokens": 2})
    assert code == 503 and body["reason"] == "draining"


def test_compile_events_counter_and_trace():
    """Every NEW jitted program fires the compile hook: the
    serving.compiles_total counter and a compile:<kind> trace span
    with the program's scalar key + wall time — the production-side
    compile-thrash detector.  A second engine over the SAME model (a
    warm program cache) records none."""
    paddle.seed(0)
    model = GPTModel.from_config("tiny", dropout=0.0)
    model.eval()
    eng = _engine(model)
    r = eng.submit(_prompts(1)[0], max_new_tokens=4)
    eng.run_until_idle()
    r.result(timeout=1)
    n = eng.registry.get("serving.compiles_total").value
    assert n >= 2          # at least the prefill + fused decode tick
    assert eng.registry.get("serving.compile_ms").count == n
    by = _events_by_name(eng.chrome_trace())
    kinds = {name for name in by if name.startswith("compile:")}
    assert "compile:fused_decode" in kinds
    ev = by["compile:fused_decode"][0]
    assert ev["args"]["wall_ms"] > 0
    assert "slot" in ev["args"]["key"]     # the layout survives
    text = monitor.render_prometheus(eng.registry)
    assert "serving_compiles_total" in text
    # warm cache: a sibling engine compiles nothing new
    eng2 = _engine(model)
    r = eng2.submit(_prompts(1)[0], max_new_tokens=4)
    eng2.run_until_idle()
    r.result(timeout=1)
    assert eng2.registry.get("serving.compiles_total").value == 0


def test_tracing_disabled_is_null(tiny_gpt):
    """Engine(tracing=False): no events collected, debug endpoints
    still answer (empty trace), outputs identical to the traced
    engine."""
    p = _prompts(1)[0]
    on = _engine(tiny_gpt)
    off = _engine(tiny_gpt, tracing=False)
    r_on = on.submit(p, max_new_tokens=6)
    r_off = off.submit(p, max_new_tokens=6)
    on.run_until_idle()
    off.run_until_idle()
    np.testing.assert_array_equal(r_on.result(timeout=1),
                                  r_off.result(timeout=1))
    assert on.tracer.events()
    assert off.tracer.events() == []
    code, body, _ = _get_probe(off, "/debug/trace")
    assert code == 200 and json.loads(body)["traceEvents"] == []
    code, dbg, _ = _get_probe(off, "/debug/requests")
    assert code == 200 and dbg["engine"]["tracing"] is False


def test_trace_ring_bounded_in_engine(tiny_gpt):
    """trace_capacity bounds the engine's ring under sustained load —
    the flight recorder retains the latest ticks, never grows."""
    eng = _engine(tiny_gpt, trace_capacity=48)
    for _ in range(3):
        r = eng.submit(_prompts(1)[0], max_new_tokens=8)
        eng.run_until_idle()
        r.result(timeout=1)
    evs = [e for e in eng.tracer.events()]
    per_thread = {}
    for e in evs:
        per_thread[e.tid] = per_thread.get(e.tid, 0) + 1
    assert all(c <= 48 for c in per_thread.values())
    # the retained window is the most recent: the last tick is there
    tick_args = [e.args["tick"] for e in evs if e.name == "tick"]
    assert tick_args and max(tick_args) == eng.tick_no


def test_tracing_overhead_twin_mixed(tiny_gpt):
    """The mixed configuration (paged + chunked + spec + sampled
    requests) runs with tracing on and off, token streams must match
    exactly (tracing is pure observation), and the traced run must not
    be wildly slower — a LOOSE 50% ceiling on the CPU so CI noise
    cannot flap it; what tracing costs on the chip is PERF.md §6
    (PR 24)."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, (int(l),)).astype(np.int32)
               for l in rng.randint(4, 14, 4)]

    def run(tracing):
        eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8,
                      tick_token_budget=16, spec_k=3, tracing=tracing)
        for p in prompts:                        # warm the compiles
            eng.submit(p, max_new_tokens=2)
        eng.run_until_idle()
        best = float("inf")
        outs = None
        for _ in range(3):
            t0 = time.perf_counter()
            rs = [eng.submit(p, max_new_tokens=8, seed=i,
                             temperature=0.9, top_p=0.9)
                  for i, p in enumerate(prompts)]
            eng.run_until_idle()
            best = min(best, time.perf_counter() - t0)
            outs = [r.result(timeout=1).tolist() for r in rs]
        return best, outs

    dt_off, outs_off = run(False)
    dt_on, outs_on = run(True)
    assert outs_on == outs_off, \
        "tracing must not perturb the token streams"
    assert dt_on <= dt_off * 1.5, \
        f"traced tick {dt_on * 1e3:.1f}ms vs {dt_off * 1e3:.1f}ms"


def test_compile_listener_deregisters_on_stop(tiny_gpt):
    """stop() unsubscribes the engine from the model's compile events
    (a stopped engine must not keep counting sibling compiles) and
    start() re-subscribes for the restart path."""
    eng = _engine(tiny_gpt)
    listeners = tiny_gpt._compile_listeners
    assert eng._compile_cb in listeners
    eng.stop()
    assert eng._compile_cb not in listeners
    eng.stop()                       # idempotent
    assert eng._compile_cb not in listeners
    eng.start()
    assert listeners.count(eng._compile_cb) == 1
    eng.start()                      # no double-subscribe
    assert listeners.count(eng._compile_cb) == 1
    eng.stop()
    assert eng._compile_cb not in listeners
    # a synchronous driver that keeps ticking after stop() re-subscribes
    eng.step()
    assert listeners.count(eng._compile_cb) == 1


# ---------------------------------------------------------------------------
# ASYNC ENGINE LOOP (async_depth=2, the device-mode default): tick N+1
# dispatched before tick N is consumed, with the stop condition (EOS /
# max_new) checked on device — parity, the device-side done mask, the
# in-flight flight recorder, the event-driven idle wake, and the
# /healthz + /debug/requests async surface.
# ---------------------------------------------------------------------------

def _staggered_run(eng, prompts, max_new=8, **submit_kw):
    """Submit half the prompts, tick twice mid-decode, submit the
    rest, drain — the same arrival pattern for every engine under
    comparison, so streams are comparable token-for-token."""
    half = len(prompts) // 2
    reqs = [eng.submit(p, max_new_tokens=max_new, **submit_kw)
            for p in prompts[:half]]
    for _ in range(2):
        eng.step()
    reqs += [eng.submit(p, max_new_tokens=max_new, **submit_kw)
             for p in prompts[half:]]
    eng.run_until_idle()
    return [r.result(timeout=2).tolist() for r in reqs]


@pytest.mark.parametrize("cfg", [
    {},                                          # contiguous, plain
    {"kv_block_size": 8},                        # paged, plain
    {"spec_k": 2},                               # contiguous, spec
    {"kv_block_size": 8, "spec_k": 2},           # paged, spec
    {"kv_block_size": 8, "prefill_chunk": 8,
     "tick_token_budget": 16},                   # paged, chunked
], ids=["contiguous", "paged", "spec", "paged-spec", "paged-chunked"])
def test_async_sync_parity_layouts(tiny_gpt, cfg):
    """Greedy streams at async_depth=2 are token-identical to
    async_depth=1 across all four dispatch layouts (contiguous/paged
    x plain/spec) plus chunked prefill, under the same staggered
    arrivals — the pipelined loop reorders WHEN host work runs, never
    WHAT the device computes."""
    prompts = _prompts(4)
    eng1 = _engine(tiny_gpt, async_depth=1, **cfg)
    assert eng1.async_depth == 1
    got1 = _staggered_run(eng1, prompts)
    eng2 = _engine(tiny_gpt, **cfg)             # device default: 2
    assert eng2.async_depth == 2
    got2 = _staggered_run(eng2, prompts)
    assert got2 == got1
    # ...and the plain layouts stay pinned to per-request generate()
    if "spec_k" not in cfg and "prefill_chunk" not in cfg:
        for p, got in zip(prompts, got2):
            ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                                    max_new_tokens=8).numpy()[0]
            assert got == ref.tolist()


def test_async_prefix_adoption_parity(tiny_gpt):
    """Chunked + paged + prefix adoption under the async loop: the
    second wave adopts the first wave's cached prefix and the streams
    still match async_depth=1 exactly."""
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, 128, (16,)).astype(np.int32)
    tails = [rng.randint(0, 128, (n,)).astype(np.int32)
             for n in (5, 7, 3)]
    prompts = [np.concatenate([sysp, t]) for t in tails]

    def run(depth):
        eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8,
                      tick_token_budget=16, async_depth=depth)
        first = eng.submit(prompts[0], max_new_tokens=6)
        eng.run_until_idle()              # wave 1 caches the prefix
        rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        eng.run_until_idle()
        hits = eng.registry.get("serving.prefix_hits").value
        return ([first.result(timeout=2).tolist()]
                + [r.result(timeout=2).tolist() for r in rest], hits)

    got1, hits1 = run(1)
    got2, hits2 = run(2)
    assert got2 == got1
    assert hits2 == hits1 and hits2 >= 1      # adoption really ran


def test_async_seeded_topp_deterministic_across_restarts(tiny_gpt):
    """A seeded top-p request reproduces exactly across engine
    restarts at async_depth=2 (the device rng keys derive from
    seed + emitted-token counter, which the async cursor chain
    preserves) and matches the synchronous engine's draw."""
    p = _prompts(1)[0]

    def run(depth):
        eng = _engine(tiny_gpt, async_depth=depth)
        r = eng.submit(p, max_new_tokens=8, temperature=0.9,
                       top_p=0.9, seed=1234)
        eng.run_until_idle()
        return r.result(timeout=2).tolist()

    a, b, c = run(2), run(2), run(1)
    assert a == b == c


def test_async_steady_state_downloads_ids_and_done_mask(tiny_gpt):
    """Acceptance: a steady-state async tick downloads ONLY the [B]
    ids + the bit-packed done mask — no [B, V] logits, no early sync
    — and the overlap/d2h-wait stats actually record."""
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, registry=reg)
    assert eng.async_depth == 2
    r = eng.submit(_prompts(1)[0], max_new_tokens=10)
    eng.run_until_idle()
    r.result(timeout=2)
    # 4 slots: 4x int32 ids + ceil(4/8) = 1 done-mask byte
    assert reg.get("serving.d2h_bytes_per_tick").value == 4 * 4 + 1
    assert reg.get("serving.d2h_wait_ms").count > 0
    ov = reg.get("serving.tick_overlap_ms")
    assert ov.count > 0 and ov.sum > 0       # host work really hid
    assert reg.get("serving.async_depth").value == 2
    text = monitor.render_prometheus(reg)
    for name in ("serving_tick_overlap_ms_bucket",
                 "serving_d2h_wait_ms_bucket", "serving_async_depth"):
        assert name in text


def test_async_depth_validation_and_defaults(tiny_gpt):
    """The depth defaults to 2 (the pipelined loop), 1 keeps the
    synchronous tick, and anything under 1 is rejected."""
    assert _engine(tiny_gpt).async_depth == 2
    assert _engine(tiny_gpt, async_depth=1).async_depth == 1
    with pytest.raises(ValueError, match="async_depth"):
        _engine(tiny_gpt, async_depth=0)


def test_async_flight_recorder_snapshots_inflight_tick(tiny_gpt,
                                                       monkeypatch):
    """Satellite acceptance: a step() failure WHILE tick N+1 is in
    flight (tick N's consume raises) snapshots both cursor buffers —
    the host mirrors and the in-flight future's metadata — before
    recovery evicts; waiters unblock, paged refcounts rebuild to
    zero, and the engine serves on."""
    eng = _engine(tiny_gpt, kv_block_size=8)
    assert eng.async_depth == 2
    r1 = eng.submit(_prompts(1)[0], max_new_tokens=10)
    r2 = eng.submit(_prompts(2)[1], max_new_tokens=10)
    eng.step()          # admit + prefill + dispatch t1 (ring: [t1])
    eng.step()          # dispatch t2, consume t1      (ring: [t2])
    assert len(eng._ring) == 1

    real_emit = eng._emit

    def boom(slot, tok):
        raise RuntimeError("synthetic consume failure")

    monkeypatch.setattr(eng, "_emit", boom)
    # next step dispatches t3 BEFORE consuming t2, so the failure
    # happens with an un-consumed future in the ring
    with pytest.raises(RuntimeError, match="synthetic"):
        eng.step()
    monkeypatch.setattr(eng, "_emit", real_emit)
    fr = eng.last_flight["metadata"]["flight-recorder"]
    assert "synthetic consume failure" in fr["error"]
    a = fr["async"]
    assert a["async_depth"] == 2
    # the un-consumed tick N+1's future metadata, pre-eviction
    assert len(a["in_flight"]) == 1
    inf = a["in_flight"][0]
    assert inf["kind"] == "decode"
    assert sorted(inf["requests"]) == sorted([r1.id, r2.id])
    assert inf["cursors"]["pos"] and inf["cursors"]["rem"]
    # ...and the host-mirror ("next") buffer rides alongside
    assert len(a["next_buffer"]["rem"]) == eng.num_slots
    assert len(a["next_buffer"]["pos"]) == eng.num_slots
    # recovery: waiters unblocked, ring cleared, refcounts at zero
    for r in (r1, r2):
        with pytest.raises(RuntimeError, match="engine step failed"):
            r.result(timeout=1)
    assert eng._ring == []
    assert eng.scheduler.occupancy() == 0
    assert eng.block_pool.in_use() == 0
    # engine still serves to parity after the recovery
    p = _prompts(3)[2]
    r3 = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=6).numpy()[0]
    np.testing.assert_array_equal(r3.result(timeout=2), ref)


def test_async_healthz_and_debug_requests_inflight_marking(tiny_gpt):
    """/healthz carries async_depth + overlap/d2h-wait means next to
    the router load signals; /debug/requests marks which in-flight
    tick each slot's device cursor belongs to (None once consumed)."""
    eng = _engine(tiny_gpt)
    code, health, _ = _get_probe(eng, "/healthz")
    assert code == 200
    assert health["async_depth"] == 2
    assert isinstance(health["tick_overlap_ms"], float)
    assert isinstance(health["d2h_wait_ms"], float)
    r = eng.submit(_prompts(1)[0], max_new_tokens=8)
    eng.step()                          # dispatch t1, ring: [t1]
    assert len(eng._ring) == 1
    inflight_tick = eng._ring[-1].tick
    code, dbg, _ = _get_probe(eng, "/debug/requests")
    assert code == 200
    assert dbg["in_flight_ticks"] == [inflight_tick]
    assert dbg["engine"]["async_depth"] == 2
    slot0 = next(s for s in dbg["slots"] if s["state"] == "decoding")
    assert slot0["cursor_tick"] == inflight_tick
    eng.run_until_idle()
    r.result(timeout=2)
    code, dbg, _ = _get_probe(eng, "/debug/requests")
    assert dbg["in_flight_ticks"] == []
    assert all(s["cursor_tick"] is None for s in dbg["slots"])


def test_idle_loop_event_driven_wake(tiny_gpt):
    """The background loop blocks on the wake event while idle (no
    2 ms poll burn) and a submit() wakes it immediately — admission
    latency no longer pays poll jitter."""
    eng = _engine(tiny_gpt)
    assert not eng._wake.is_set()
    eng.start()
    try:
        time.sleep(0.1)                  # loop settles into the wait
        p = _prompts(1)[0]
        t0 = time.monotonic()
        r = eng.submit(p, max_new_tokens=4)
        out = r.result(timeout=5)
        assert out.shape[0] == len(p) + 4
        # generous bound: the point is "woke now", not "woke at the
        # next poll tick" — a hung wait would blow the result timeout
        assert time.monotonic() - t0 < 5.0
    finally:
        eng.stop()
    # submit marks the wake event even without a loop running
    eng2 = _engine(tiny_gpt)
    eng2._wake.clear()
    eng2.submit(p, max_new_tokens=1)
    assert eng2._wake.is_set()


def test_greedy_neighbor_does_not_perturb_seeded_stream(tiny_gpt):
    """rbg-PRNG regression: under the TPU-native rbg implementation a
    vmapped categorical's bits depend on the whole key batch, so a
    greedy lane binding its id-derived junk seed used to perturb a
    seeded neighbor's draws — mixed greedy+seeded batches were
    irreproducible because request ids are a process-global counter.
    Greedy lanes now bind constant zero seed words: the seeded
    request's stream must reproduce exactly across engines (ids
    advanced in between) whenever its own seed is pinned."""
    prompts = _prompts(2)

    def run():
        eng = _engine(tiny_gpt)
        greedy = eng.submit(prompts[0], max_new_tokens=8)   # no seed
        seeded = eng.submit(prompts[1], max_new_tokens=8,
                            temperature=0.9, top_p=0.9, seed=42)
        eng.run_until_idle()
        return (greedy.result(timeout=2).tolist(),
                seeded.result(timeout=2).tolist())

    g1, s1 = run()
    # burn some request ids so the second engine's greedy request gets
    # a different id — the old junk-key binding would shift the draws
    for _ in range(3):
        _engine(tiny_gpt).submit(prompts[0], max_new_tokens=1)
    g2, s2 = run()
    assert s1 == s2, "seeded stream must not depend on neighbors' ids"
    assert g1 == g2                      # greedy was always stable


# ---------------------------------------------------------------------------
# overload protection: priorities, preemption, fairness, shedding, drain
# ---------------------------------------------------------------------------

def _ref(model, p, n):
    return model.generate(paddle.to_tensor(p[None, :]),
                          max_new_tokens=n).numpy()[0]


@pytest.mark.parametrize("cfg", [
    {},                                                    # contiguous
    {"kv_block_size": 8},                                  # paged
    {"prefill_chunk": 8, "tick_token_budget": 16},         # chunked
    {"kv_block_size": 8, "prefill_chunk": 8,
     "tick_token_budget": 16},                             # paged+chunk
    {"spec_k": 2},                                         # spec
    {"kv_block_size": 8, "spec_k": 2},                     # paged+spec
    {"kv_block_size": 8, "async_depth": 2},                # depth 2
], ids=["contiguous", "paged", "chunked", "paged+chunked", "spec",
        "paged+spec", "paged+depth2"])
def test_preempt_resume_greedy_parity(tiny_gpt, cfg):
    """A high-priority arrival preempts the running low-priority
    stream mid-decode; BOTH finish token-identical to uninterrupted
    generate() — across every dispatch layout.  The resumed stream's
    continuation is exactly where the eviction interrupted it."""
    eng = _engine(tiny_gpt, num_slots=1, **cfg)
    p_low, p_high = _prompts(2)
    low = eng.submit(p_low, max_new_tokens=12, priority=0)
    for _ in range(5):
        eng.step()                 # low is mid-stream
    assert not low.done()
    high = eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    np.testing.assert_array_equal(high.result(timeout=1),
                                  _ref(tiny_gpt, p_high, 4))
    np.testing.assert_array_equal(low.result(timeout=1),
                                  _ref(tiny_gpt, p_low, 12))
    assert low.preemptions >= 1
    reg = eng.registry
    assert reg.get("serving.preemptions_total").value >= 1
    assert reg.get("serving.resumed_total").value >= 1
    # refcount hygiene after the preempt/resume cycle
    if eng._paged:
        if eng.prefix_cache is not None:
            eng.prefix_cache.clear()
        assert eng.block_pool.in_use() == 0


def test_preemption_returns_blocks_to_prefix_cache(tiny_gpt):
    """Paged preemption inserts the computed history's full blocks
    into the prefix cache, so the resume ADOPTS them instead of
    re-prefilling the whole interrupted stream."""
    eng = _engine(tiny_gpt, num_slots=1, kv_block_size=8)
    p_low, p_high = _prompts(2)
    low = eng.submit(p_low, max_new_tokens=12)
    for _ in range(6):             # len(prompt)=5, +6 tokens: past a
        eng.step()                 # full 8-token block boundary
    high = eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    low.result(timeout=1)
    # the resume adopted at least the first full block of the frozen
    # prompt+emitted context
    assert eng.registry.get("serving.prefix_hit_tokens").value >= 8
    assert eng.registry.get("serving.prefix_hits").value >= 1


@pytest.mark.parametrize("kw", [dict(kv_block_size=8), dict()],
                         ids=["paged", "contiguous"])
def test_preempt_seeded_stream_unchanged(tiny_gpt, kw):
    """Seeded top-p stream across a preemption == uninterrupted run:
    the device key folds the emitted-token counter, so resumption
    must not re-draw — with the cached span adopted (paged) or the
    whole context prefilled again (contiguous)."""
    p_low, p_high = _prompts(2)

    def run(interrupt):
        eng = _engine(tiny_gpt, num_slots=1, **kw)
        r = eng.submit(p_low, max_new_tokens=10, temperature=0.9,
                       top_p=0.9, seed=42)
        if interrupt:
            for _ in range(4):
                eng.step()
            eng.submit(p_high, max_new_tokens=3, priority=9)
        eng.run_until_idle()
        return r.result(timeout=1).tolist(), r.preemptions

    plain, n0 = run(False)
    interrupted, n1 = run(True)
    assert n0 == 0 and n1 >= 1
    assert plain == interrupted


def test_no_preemption_at_equal_priority_or_disabled(tiny_gpt):
    """Equal priority never preempts (strictly-lower only), and
    Engine(preemption=False) turns the mechanism off entirely."""
    p1, p2 = _prompts(2)
    eng = _engine(tiny_gpt, num_slots=1)
    a = eng.submit(p1, max_new_tokens=6, priority=3)
    eng.step()
    b = eng.submit(p2, max_new_tokens=4, priority=3)
    eng.run_until_idle()
    assert a.preemptions == 0 and b.preemptions == 0
    assert eng.registry.get("serving.preemptions_total").value == 0

    eng2 = _engine(tiny_gpt, num_slots=1, preemption=False)
    c = eng2.submit(p1, max_new_tokens=6, priority=0)
    eng2.step()
    d = eng2.submit(p2, max_new_tokens=4, priority=9)
    eng2.run_until_idle()
    assert c.preemptions == 0
    assert eng2.registry.get("serving.preemptions_total").value == 0
    # outputs still correct, just FIFO-ordered
    np.testing.assert_array_equal(c.result(timeout=1),
                                  _ref(tiny_gpt, p1, 6))
    np.testing.assert_array_equal(d.result(timeout=1),
                                  _ref(tiny_gpt, p2, 4))


def test_priority_orders_queue_service(tiny_gpt):
    """Queued high-priority requests are admitted before earlier-
    submitted low-priority ones (strict tiers)."""
    eng = _engine(tiny_gpt, num_slots=1, preemption=False)
    p = _prompts(1)[0]
    blocker = eng.submit(p, max_new_tokens=4, priority=0)
    eng.step()
    low = eng.submit(p, max_new_tokens=4, priority=0)
    high = eng.submit(p, max_new_tokens=4, priority=2)
    eng.run_until_idle()
    for r in (blocker, low, high):
        r.result(timeout=1)
    assert high.finished_at < low.finished_at


def test_weighted_fair_queue_pop_order():
    """SFQ unit: with weights {a: 1, b: 3} and equal token costs, a
    backlogged b gets ~3 of every 4 pops; within one tenant order
    stays FIFO."""
    q = RequestQueue(weights={"a": 1.0, "b": 3.0})
    a_reqs = [Request([1, 2, 3, 4], 4, tenant="a") for _ in range(12)]
    b_reqs = [Request([1, 2, 3, 4], 4, tenant="b") for _ in range(12)]
    for ra, rb in zip(a_reqs, b_reqs):
        q.put(ra)
        q.put(rb)
    order = []
    while q.depth():
        req, _ = q.pop_ready()
        order.append(req)
    share_b = [r.tenant for r in order[:8]].count("b")
    assert share_b >= 5, f"weight-3 tenant got {share_b}/8 early pops"
    got_a = [r for r in order if r.tenant == "a"]
    got_b = [r for r in order if r.tenant == "b"]
    assert [r.id for r in got_a] == [r.id for r in a_reqs]   # FIFO
    assert [r.id for r in got_b] == [r.id for r in b_reqs]
    # strict priority beats fairness
    q2 = RequestQueue()
    lo = Request([1], 2, priority=0)
    hi = Request([1], 2, priority=4)
    q2.put(lo)
    q2.put(hi)
    assert q2.best_priority() == 4
    assert q2.pop_ready()[0] is hi


def test_fairness_flooding_tenant_cannot_starve(tiny_gpt):
    """Engine-level fairness: tenant "flood" queues 12 requests ahead
    of tenant "paid" (weight 4); paid's 4 requests all finish well
    before flood's tail — the flood cannot starve paid past its
    weight."""
    eng = _engine(tiny_gpt, num_slots=2,
                  tenants={"paid": {"weight": 4.0}})
    p = _prompts(1)[0]
    flood = [eng.submit(p, max_new_tokens=4, tenant="flood")
             for _ in range(12)]
    paid = [eng.submit(p, max_new_tokens=4, tenant="paid")
            for _ in range(4)]
    eng.run_until_idle()
    done = sorted(flood + paid, key=lambda r: r.finished_at)
    worst_paid = max(done.index(r) for r in paid)
    assert worst_paid < 10, \
        f"paid tenant's last finish ranked {worst_paid}/16"


def test_tenant_token_bucket_rate_limit(tiny_gpt):
    """Sustained over-rate traffic from one tenant is shed at submit
    with RateLimited + honest retry_after; other tenants unaffected."""
    eng = _engine(tiny_gpt,
                  tenants={"free": TenantPolicy(rate=10.0,
                                                burst=20.0)})
    p = _prompts(1)[0]          # cost = 5 prompt + 4 new = 9 tokens
    eng.submit(p, max_new_tokens=4, tenant="free")
    eng.submit(p, max_new_tokens=4, tenant="free")  # burst exhausted
    with pytest.raises(RateLimited) as ei:
        for _ in range(5):
            eng.submit(p, max_new_tokens=4, tenant="free")
    assert ei.value.retry_after > 0
    assert eng.registry.get(
        "serving.shed_rate_limited_total").value >= 1
    # a different tenant still submits fine
    eng.submit(p, max_new_tokens=4, tenant="other")
    eng.run_until_idle()


def test_deadline_shed_at_submit(tiny_gpt):
    """Once the drain rate is measured, a request whose deadline the
    queue backlog already blows is rejected at submit (DeadlineShed,
    computed retry_after) instead of timing out in queue."""
    eng = _engine(tiny_gpt, num_slots=1)
    p = _prompts(1)[0]
    warm = eng.submit(p, max_new_tokens=8)
    eng.run_until_idle()                  # drain rate now measured
    warm.result(timeout=1)
    assert eng.drain_rate() is not None
    eng.submit(p, max_new_tokens=30)      # occupies the only slot
    for _ in range(30):                   # deep backlog
        eng.submit(p, max_new_tokens=30)
    with pytest.raises(DeadlineShed) as ei:
        eng.submit(p, max_new_tokens=4, timeout=0.001)
    assert ei.value.retry_after > 0
    assert eng.registry.get("serving.shed_deadline_total").value == 1
    # shed_deadlines=False keeps the old behavior (queue, then expire)
    eng2 = _engine(tiny_gpt, num_slots=1, shed_deadlines=False)
    w2 = eng2.submit(p, max_new_tokens=8)
    eng2.run_until_idle()
    eng2.submit(p, max_new_tokens=30)
    for _ in range(30):
        eng2.submit(p, max_new_tokens=30)
    doomed = eng2.submit(p, max_new_tokens=4, timeout=0.001)
    assert doomed is not None             # queued, not shed


def test_queue_full_retry_after_computed(tiny_gpt):
    """QueueFull's retry_after comes from the measured drain rate
    (backlog / rate / depth), not a constant."""
    eng = _engine(tiny_gpt, num_slots=1, max_queue=2)
    p = _prompts(1)[0]
    warm = eng.submit(p, max_new_tokens=8)
    eng.run_until_idle()
    warm.result(timeout=1)
    eng.submit(p, max_new_tokens=16)
    eng.step()                     # admitted into the only slot
    eng.submit(p, max_new_tokens=16)
    eng.submit(p, max_new_tokens=16)   # queue now at max_queue=2
    with pytest.raises(QueueFull) as ei:
        eng.submit(p, max_new_tokens=16)
    assert ei.value.retry_after is not None
    assert 0 < ei.value.retry_after < 60
    assert eng.registry.get(
        "serving.shed_queue_full_total").value == 1
    eng.run_until_idle()


def test_graceful_drain_finishes_inflight(tiny_gpt):
    """stop(drain=True): in-flight streams FINISH (waiters get
    complete outputs), queued-but-unadmitted requests fail, submits
    during the drain are shed, and the wait is bounded."""
    eng = _engine(tiny_gpt, num_slots=2)
    p = _prompts(1)[0]
    eng.start()
    inflight = [eng.submit(p, max_new_tokens=12) for _ in range(2)]
    time.sleep(0.05)               # both admitted, mid-stream
    t0 = time.monotonic()
    eng.stop(drain=True, drain_timeout=10.0)
    assert time.monotonic() - t0 < 10.0
    for r in inflight:
        out = r.result(timeout=1)  # complete output, no error
        assert out.shape[0] == len(p) + 12
    # while the drain flag is up, submission is closed (shed with the
    # Rejected shape the HTTP edge maps to 503)
    eng._draining = True
    with pytest.raises(QueueFull):
        eng.submit(p, max_new_tokens=2)
    eng._draining = False


def test_graceful_drain_bounds_at_timeout(tiny_gpt):
    """A drain that cannot finish inside drain_timeout falls back to
    the hard drain — shutdown always terminates, stragglers fail."""
    eng = _engine(tiny_gpt, num_slots=1)
    p = _prompts(1)[0]
    eng.start()
    r = eng.submit(p, max_new_tokens=40)
    time.sleep(0.02)
    eng.stop(drain=True, drain_timeout=0.0)   # no grace at all
    assert r.done()
    # either it squeaked through or it was failed — but never hangs
    if r.error is None:
        assert len(r.generated) == 40


def test_scheduler_debug_view_carries_priority_tenant(tiny_gpt):
    eng = _engine(tiny_gpt, num_slots=2)
    p = _prompts(1)[0]
    eng.submit(p, max_new_tokens=6, priority=3, tenant="acme")
    eng.step()
    view = eng.scheduler.debug_view()
    bound = [v for v in view if v["state"] != "free"]
    assert bound and bound[0]["priority"] == 3
    assert bound[0]["tenant"] == "acme"
    free = [v for v in view if v["state"] == "free"]
    assert free and free[0]["priority"] is None
    dbg = eng.debug_requests()
    assert dbg["engine"]["preemption"] is True
    assert dbg["engine"]["draining"] is False
    assert "preemptions" in dbg
    eng.run_until_idle()


def test_preempt_log_rides_flight_recorder(tiny_gpt, monkeypatch):
    """The flight-recorder dump carries the preemption/requeue history
    ring, so a post-mortem shows WHY a slot was evicted."""
    eng = _engine(tiny_gpt, num_slots=1, kv_block_size=8)
    p_low, p_high = _prompts(2)
    low = eng.submit(p_low, max_new_tokens=12)
    for _ in range(4):
        eng.step()
    eng.submit(p_high, max_new_tokens=4, priority=7)
    eng.step()                     # preemption happens here
    assert eng.registry.get("serving.preemptions_total").value >= 1
    boom = RuntimeError("injected")
    monkeypatch.setattr(
        eng, "_dispatch_decode",
        lambda *a, **k: (_ for _ in ()).throw(boom))
    with pytest.raises(RuntimeError):
        for _ in range(50):
            eng.step()
    meta = eng.last_flight["metadata"]["flight-recorder"]
    assert meta["preemptions"], "no preemption history in the dump"
    entry = meta["preemptions"][-1]
    assert entry["request"] == low.id and entry["priority"] == 0
    assert entry["generated"] >= 1


def test_httpd_overload_surface(tiny_gpt):
    """HTTP edge: priority/tenant ride the POST body, RateLimited maps
    to 429 with a Retry-After, and /healthz + /debug/requests expose
    the overload-protection signals."""
    eng = _engine(tiny_gpt, max_queue=8,
                  tenants={"free": TenantPolicy(rate=5.0, burst=10.0)})
    with EngineServer(eng, port=0) as srv:
        base = srv.address
        body = {"prompt": [1, 2, 3], "max_new_tokens": 4,
                "priority": 2, "tenant": "free"}
        req = urllib.request.Request(
            base + "/generate", json.dumps(body).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
        # second submit: the 10-token bucket cannot cover another 7
        try:
            urllib.request.urlopen(urllib.request.Request(
                base + "/generate", json.dumps(body).encode(),
                {"Content-Type": "application/json"}))
            raise AssertionError("expected 429")
        except urllib.error.HTTPError as e:
            assert e.code == 429
            assert int(e.headers["Retry-After"]) >= 1
        with urllib.request.urlopen(base + "/healthz") as resp:
            h = json.loads(resp.read())
        for key in ("preemptions_total", "resumed_total",
                    "shed_deadline_total", "shed_rate_limited_total",
                    "shed_queue_full_total", "watchdog_fires",
                    "drain_rate_tps", "draining"):
            assert key in h, key
        assert h["shed_rate_limited_total"] == 1
        assert h["draining"] is False
        with urllib.request.urlopen(base + "/debug/requests") as resp:
            dbg = json.loads(resp.read())
        assert "preemptions" in dbg
        assert dbg["engine"]["preemption"] is True


def test_rejected_exception_hierarchy():
    """QueueFull/RateLimited/DeadlineShed are all Rejected with a
    retry_after slot — the one shape the HTTP edge needs."""
    for cls in (QueueFull, RateLimited, DeadlineShed):
        e = cls("nope", retry_after=2.5)
        assert isinstance(e, Rejected)
        assert isinstance(e, RuntimeError)   # old callers keep working
        assert e.retry_after == 2.5
    assert QueueFull("x").retry_after is None


def test_rate_limit_oversized_request_is_permanent(tiny_gpt):
    """A request costing more than the bucket's burst can NEVER pass —
    it is rejected with retry_after=None (honest: no finite backoff
    admits it) instead of a finite hint that livelocks the client."""
    eng = _engine(tiny_gpt,
                  tenants={"t": TenantPolicy(rate=10.0, burst=12.0)})
    p = _prompts(1)[0]                 # 5 prompt + 20 new = 25 > 12
    with pytest.raises(RateLimited) as ei:
        eng.submit(p, max_new_tokens=20, tenant="t")
    assert ei.value.retry_after is None
    assert "never" in str(ei.value)


def test_bucket_refund_on_queue_full(tiny_gpt):
    """A QueueFull rejection refunds the token-bucket charge: shed
    classes must not cascade into RateLimited lockout."""
    eng = _engine(tiny_gpt, max_queue=1,
                  tenants={"t": TenantPolicy(rate=10.0, burst=20.0)})
    p = _prompts(1)[0]                 # cost 5 + 4 = 9 tokens
    eng.submit(p, max_new_tokens=4, tenant="t")   # bucket: 20 -> 11
    with pytest.raises(QueueFull):
        eng.submit(p, max_new_tokens=4, tenant="t")  # refunds the 9
    # without the refund the bucket would hold ~2 < 9 and this would
    # be RateLimited; with it the charge is back and the submit only
    # hits the (still) full queue
    with pytest.raises(QueueFull):
        eng.submit(p, max_new_tokens=4, tenant="t")
    eng.run_until_idle()


def test_estimate_wait_zero_with_free_slots(tiny_gpt):
    """A partially-loaded multi-slot engine must NOT deadline-shed a
    request that a free slot (or a preemptable victim) would serve
    immediately."""
    eng = _engine(tiny_gpt, num_slots=4)
    p = _prompts(1)[0]
    warm = eng.submit(p, max_new_tokens=8)
    eng.run_until_idle()               # drain rate measured
    warm.result(timeout=1)
    eng.submit(p, max_new_tokens=30)   # one long stream
    eng.step()                         # admitted; 3 slots free
    assert eng.estimate_queue_wait() == 0.0
    # a short-deadline submit is ACCEPTED, not shed
    r = eng.submit(p, max_new_tokens=4, timeout=0.5)
    eng.run_until_idle()
    assert r.error is None
    # and with every slot busy at pri 0, a HIGH-pri submit still
    # estimates 0 (preemption would place it next tick)
    for _ in range(4):
        eng.submit(p, max_new_tokens=30)
    eng.step()
    assert eng.scheduler.free_count() == 0
    assert eng.estimate_queue_wait(priority=5) == 0.0
    assert eng.estimate_queue_wait(priority=0) > 0.0
    eng.run_until_idle()


def test_drain_rate_ignores_stale_window(tiny_gpt):
    """An idle gap between bursts must not collapse the measured rate
    (a 10-minute-old window entry would make every post-gap estimate
    orders of magnitude too slow and shed everything)."""
    eng = _engine(tiny_gpt)
    now = time.monotonic()
    eng._rate_win.append((now - 600.0, 50))
    eng._rate_win.append((now - 599.9, 50))
    assert eng.drain_rate() is None          # all entries stale
    eng._rate_win.append((now - 0.2, 40))
    eng._rate_win.append((now, 40))
    rate = eng.drain_rate()
    assert rate is not None
    # the stale entries are excluded: rate reflects the recent pair
    # (~40 tokens / 0.2 s), not 130 tokens / 600 s
    assert rate > 50


def test_queue_vfin_map_stays_bounded():
    """Tenant names arrive from the network edge: the fairness
    finish-tag map must not grow with every name ever seen."""
    q = RequestQueue()
    for i in range(1000):
        q.put(Request([1, 2, 3], 4, tenant=f"drive-by-{i}"))
        got, _ = q.pop_ready()
        assert got is not None
    assert len(q._vfin) <= 300


# ---------------------------------------------------------------------------
# the device lane (dev.* spans), the tick's host spans, the HTTP edge
# ---------------------------------------------------------------------------

_DISPATCH_SPANS = {"decode.dispatch", "decode.ragged_stream",
                   "prefill.chunk", "prefill"}


def _spans(eng, cat=None, name=None):
    return [e for e in eng.chrome_trace()["traceEvents"]
            if e.get("ph") == "X"
            and (cat is None or e.get("cat") == cat)
            and (name is None or e["name"] == name)]


@pytest.mark.parametrize("kw", [
    dict(async_depth=1),
    dict(async_depth=2),
    dict(async_depth=1, kv_block_size=8, prefill_chunk=8),
    dict(async_depth=2, kv_block_size=8, prefill_chunk=8),
    dict(async_depth=1, kv_block_size=8, prefill_chunk=8,
         attn_impl="ragged"),
    dict(async_depth=2, kv_block_size=8, prefill_chunk=8,
         attn_impl="ragged"),
    dict(async_depth=2, kv_block_size=8, attn_impl="ragged"),
    dict(async_depth=2, kv_block_size=8, spec_k=2),
    dict(async_depth=2, kv_block_size=8, prefill_chunk=8,
         kv_dtype="int8"),
], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_dev_spans_one_per_dispatch(tiny_gpt, kw):
    """The device watcher emits one ``dev.*`` span per dispatch of a
    model program, on the shared ``device`` lane: ordered and disjoint,
    ending after the host span that dispatched it began, named
    ``dev.prefill`` when it carried prompt tokens and ``dev.decode``
    when none, with the program's probe kind, the tick that caused it
    and what it carried; ``serving.dev_busy_ms`` sums the durations."""
    eng = _engine(tiny_gpt, **kw)
    busy0 = eng.registry.get("serving.dev_busy_ms").value
    pre0 = eng.registry.get("serving.prefill_tokens").value
    reqs = [eng.submit(p, max_new_tokens=5) for p in _prompts(3)]
    eng.step()
    reqs += [eng.submit(p, max_new_tokens=4) for p in _prompts(5)[3:]]
    eng.run_until_idle()
    eng.stop()              # joins the watcher: every span is out
    assert all(r.done() for r in reqs)
    dev = sorted(_spans(eng, cat="device"), key=lambda e: e["ts"])
    host = sorted((e for e in _spans(eng)
                   if e["name"] in _DISPATCH_SPANS),
                  key=lambda e: e["ts"])
    assert len(dev) == len(host) > 0
    assert len({e["tid"] for e in dev}) == 1
    names = {e["args"]["name"]: e["tid"]
             for e in eng.chrome_trace()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names["device"] == dev[0]["tid"]
    for a, b in zip(dev, dev[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3      # disjoint
    ticks = {e["args"]["tick"]: e for e in _spans(eng, name="tick")}
    for d, h in zip(dev, host):
        a = d["args"]
        assert d["dur"] >= 0
        assert d["ts"] + d["dur"] >= h["ts"]    # ends after it began
        assert d["ts"] + d["dur"] >= ticks[a["tick"]]["ts"]
        assert isinstance(a["program"], str) and a["program"]
        assert d["name"] == ("dev.prefill" if a["n"] else "dev.decode")
        if h["name"] in ("prefill", "prefill.chunk"):
            assert a["n"] >= 1 and a["req"] == h["args"]["req"]
        else:
            assert a["batch"] + a["n"] >= 1
        if h["name"] == "decode.dispatch":
            assert (a["batch"], a["n"]) == (h["args"]["batch"], 0)
    # prompt tokens ride on dev.prefill spans, all of them
    assert sum(e["args"]["n"] for e in dev) == \
        eng.registry.get("serving.prefill_tokens").value - pre0
    busy = eng.registry.get("serving.dev_busy_ms").value - busy0
    assert busy == pytest.approx(sum(e["dur"] for e in dev) * 1e-3,
                                 rel=1e-6, abs=1e-6)


def _watchers():
    return [t for t in threading.enumerate()
            if t.name == "paddle_tpu-serving-device"]


def test_device_watcher_lifecycle(tiny_gpt):
    """One watcher thread per engine, started at the first dispatch,
    stopped and joined by ``stop()``; 50 engines built and stopped leak
    none, a collected engine takes its watcher with it, and
    ``tracing=False`` starts none."""
    import gc
    gc.collect()
    deadline = time.monotonic() + 10.0
    while _watchers() and time.monotonic() < deadline:
        time.sleep(0.05)    # watchers of engines other tests dropped
    base = len(_watchers())
    eng = _engine(tiny_gpt)
    assert len(_watchers()) == base          # lazy: nothing dispatched
    eng.submit(_prompts(1)[0], max_new_tokens=2)
    eng.run_until_idle()
    assert len(_watchers()) == base + 1
    eng.stop()
    assert len(_watchers()) == base and eng._dev_thread is None
    # a stopped engine that ticks again starts a new one
    eng.submit(_prompts(1)[0], max_new_tokens=2)
    eng.run_until_idle()
    assert len(_watchers()) == base + 1
    eng.stop()
    for _ in range(50):
        e = _engine(tiny_gpt)
        e.submit(_prompts(1)[0], max_new_tokens=2)
        e.run_until_idle()
        e.stop()
    assert len(_watchers()) == base
    # dropped without stop(): the watcher goes with the engine
    e = _engine(tiny_gpt)
    e.submit(_prompts(1)[0], max_new_tokens=2)
    e.run_until_idle()
    assert len(_watchers()) == base + 1
    del e
    deadline = time.monotonic() + 10.0
    while len(_watchers()) > base and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.05)
    assert len(_watchers()) == base
    off = _engine(tiny_gpt, tracing=False)
    off.submit(_prompts(1)[0], max_new_tokens=3)
    off.run_until_idle()
    assert len(_watchers()) == base and off._dev_q is None
    assert off.registry.get("serving.dev_busy_ms").value == 0
    assert off.chrome_trace()["traceEvents"] == []
    off.stop()


@pytest.mark.parametrize("kw", [
    dict(kv_block_size=8, prefill_chunk=8),
    dict(kv_block_size=8, prefill_chunk=8, attn_impl="ragged"),
    dict(async_depth=1, kv_block_size=8),
], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_tick_host_spans_cover_host_ms(tiny_gpt, kw):
    """Every phase of the tick runs under a span of its own: over a
    run, the tick's direct children leave under a tenth of ``host_ms``
    (the tick less the time blocked on the device) uncovered, and
    ``host_ms`` never exceeds the tick."""
    eng = _engine(tiny_gpt, **kw)
    # warm the programs: a first call's trace and compile is host time
    # inside the dispatch span, which would make the rest look small
    eng.submit(_prompts(1)[0], max_new_tokens=2)
    eng.run_until_idle()
    eng.tracer.clear()
    for p in _prompts(4):
        eng.submit(p, max_new_tokens=6)
    eng.step()
    for p in _prompts(6)[4:]:
        eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    eng.stop()
    ticks = _spans(eng, name="tick")
    lane = ticks[0]["tid"]
    inner = [e for e in _spans(eng)
             if e["tid"] == lane and e["name"] != "tick"]
    host_ms = uncovered_ms = 0.0
    seen = set()
    for t in ticks:
        t0, t1 = t["ts"], t["ts"] + t["dur"]
        assert 0 <= t["args"]["host_ms"] <= t["dur"] * 1e-3 + 1e-3
        kids = sorted((e for e in inner if t0 <= e["ts"] < t1),
                      key=lambda e: (e["ts"], -e["dur"]))
        end = t0
        direct = 0.0
        for e in kids:
            seen.add(e["name"])
            if e["ts"] >= end:          # not nested in the one before
                assert e["ts"] + e["dur"] <= t1 + 1e-3   # contained
                direct += e["dur"]
                end = e["ts"] + e["dur"]
        host_ms += t["args"]["host_ms"]
        uncovered_ms += (t["dur"] - direct) * 1e-3
    # warm since the first request: the whole state is never uploaded
    # again, every admission, chunk and eviction patches its own slot
    assert {"admit", "preempt", "post_admit", "state.patch"} <= seen
    assert "state.push" not in seen
    if "prefill_chunk" in kw:
        assert "chunk.plan" in seen
    if kw.get("attn_impl") != "ragged":
        assert {"first_token", "prefill.d2h"} <= seen
    if kw.get("async_depth") != 1:
        assert "ring.drain" in seen
        whys = {e["args"]["why"] for e in _spans(eng, name="ring.drain")}
        assert whys <= {"spec", "tail", "idle", "preempt",
                        "migrate", "adapter"}
    assert all(e["args"].get("bytes", 1) > 0
               for e in _spans(eng, name="state.patch"))
    assert uncovered_ms <= 0.1 * host_ms, (uncovered_ms, host_ms)


def test_compiled_programs_carry_their_probe_kind(tiny_gpt):
    """Each of the ten jitted programs of models/gpt.py is named
    after its ``_compile_probe`` kind (``jit_gpt_<kind>`` in a device
    trace's ``XLA Modules``), all distinct; the probed callables an
    engine holds say the same."""
    import inspect
    import re
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    src = inspect.getsource(gpt)
    pairs = re.findall(
        r'fn = _jit_named\("(\w+)", pure.*?_compile_probe\(\s*"(\w+)"',
        src, re.S)
    assert len(pairs) == 10
    assert all(a == b for a, b in pairs)
    assert len({a for a, _ in pairs}) == 10
    assert "= jax.jit(pure" not in src
    fn = gpt._jit_named("fused_decode", lambda x: x + 1)
    text = fn.lower(jnp.zeros(2)).as_text()
    assert "jit_gpt_fused_decode" in text
    # the programs engines of this module built on the shared model
    _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8).stop()
    eng = _engine(tiny_gpt)
    eng.submit(_prompts(1)[0], max_new_tokens=2)
    eng.run_until_idle()
    eng.stop()
    kinds = set()
    for attr in dir(tiny_gpt):
        if attr.endswith("_fn_cache"):
            for probed, _, _ in getattr(tiny_gpt, attr).values():
                assert probed.__name__ == "gpt_" + probed.kind
                kinds.add(probed.kind)
    assert {"prefill", "fused_decode"} <= kinds
    assert kinds <= {a for a, _ in pairs}
    # named scopes reach the lowered program's op metadata
    x = paddle.to_tensor(np.zeros((1, 4), np.int32))
    hlo = jax.jit(lambda ids: tiny_gpt(paddle.Tensor(ids))._data).lower(
        x._data).compile().as_text()
    for scope in ("attention", "mlp", "lm_head"):
        assert scope in hlo, scope


def test_http_events_and_requests_lane(tiny_gpt):
    """``http.ingest`` (a span from the top of ``do_POST`` to the
    return of ``submit``) and ``http.first_frame`` (after the first
    token frame's flush, or the whole body's) carry the ``req`` of the
    engine's own instants, in order queued < ingest's end, first_token
    <= first_frame; and a ``/debug/trace`` taken after 200 sequential
    connections at the default ``max_threads`` still holds the first
    connection's ``req.queued``."""
    from paddle_tpu.serving.stream import parse_sse
    eng = _engine(tiny_gpt, kv_block_size=8)
    assert eng.tracer.max_threads == 64
    ids = []
    with EngineServer(eng, port=0) as srv:
        def post(body):
            return urllib.request.urlopen(urllib.request.Request(
                f"{srv.address}/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"}))
        for k in range(200):
            body = {"prompt": [1 + k % 7, 2, 3], "max_new_tokens": 2}
            if k % 50 == 0:
                body["stream"] = True
                with post(body) as resp:
                    done = [json.loads(d) for ev, d in parse_sse(resp)
                            if ev == "done"]
                ids.append(done[0]["id"])
            else:
                with post(body) as resp:
                    ids.append(json.loads(resp.read())["id"])
        with urllib.request.urlopen(f"{srv.address}/debug/trace") as r:
            trace = json.loads(r.read())["traceEvents"]
    assert len(set(ids)) == 200
    by = {}
    for e in trace:
        req = (e.get("args") or {}).get("req")
        if req is not None and e["name"] in (
                "req.queued", "http.ingest", "req.first_token",
                "http.first_frame"):
            by.setdefault(e["name"], {})[req] = e
    for name in ("req.queued", "http.ingest", "req.first_token",
                 "http.first_frame"):
        assert set(by[name]) >= set(ids), name      # the first too
    for req in ids:
        ing, q = by["http.ingest"][req], by["req.queued"][req]
        assert ing["ph"] == "X" and ing["args"]["prompt"] == 3
        assert ing["args"]["bytes"] > 0
        assert ing["ts"] <= q["ts"] <= ing["ts"] + ing["dur"]
        assert by["http.first_frame"][req]["ph"] == "i"
        assert by["req.first_token"][req]["ts"] <= \
            by["http.first_frame"][req]["ts"]
    lanes = {e["args"]["name"]: e["tid"] for e in trace
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {e["tid"] for n in by for e in by[n].values()} == \
        {lanes["requests"]}
    assert len(lanes) < 10      # handler threads burned no lanes


# ---------------------------------------------------------------------------
# NO DIRTY EVENT DRAINS THE RING: an admission, a chunk's progress, a
# final chunk's first token and an eviction reach the device as per-slot
# patches queued in dispatch order behind the ticks in flight; the whole
# state is uploaded once.  Token-for-token parity with the synchronous
# engine (which queues the same patches over nothing in flight), the
# counters that say how often each engages, and the compile-once rule of
# the patch and first-token programs.
# ---------------------------------------------------------------------------

def _eventful_run(eng, max_new=7):
    """More requests than slots over a pool that fits the running ones
    and little more, so every eviction's blocks are adopted at once by
    the admission behind it; prompts of one to four chunks; one request
    whose first token is its EOS, one whose budget is one token, and
    one that times out in the queue while the slots are busy."""
    lens = (5, 19, 11, 27, 8, 14, 21, 6)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 128, (n,)).astype(np.int32) for n in lens]
    firsts = [int(_ref(eng.model, p, 1)[-1]) for p in prompts[:2]]
    reqs = [eng.submit(prompts[0], max_new_tokens=max_new,
                       eos_token_id=firsts[0]),     # EOS at token one
            eng.submit(prompts[1], max_new_tokens=1)]
    reqs += [eng.submit(p, max_new_tokens=max_new) for p in prompts[2:5]]
    for _ in range(3):
        eng.step()
    late = eng.submit(prompts[5], max_new_tokens=max_new, timeout=0.0)
    reqs += [eng.submit(p, max_new_tokens=max_new) for p in prompts[5:]]
    eng.run_until_idle()
    with pytest.raises(RequestTimeout):
        late.result(timeout=1)
    return [r.result(timeout=2).tolist() for r in reqs]


PATCHED_LAYOUTS = [
    pytest.param(dict(), id="contiguous"),
    pytest.param(dict(prefill_chunk=8), id="contiguous-chunked"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14), id="paged"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8),
                 id="paged-chunked"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8,
                      tick_token_budget=8), id="paged-chunked-budget"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8,
                      prefix_cache=False), id="paged-chunked-nocache"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8,
                      kv_dtype="int8"), id="paged-chunked-int8"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8,
                      attn_impl="ragged"), id="ragged"),
    pytest.param(dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8,
                      spec_k=2), id="paged-chunked-spec"),
]


def _eventful_engine(model, **kw):
    return _engine(model, num_slots=3, max_seq_len=64,
                   shed_deadlines=False, **kw)


@pytest.mark.parametrize("cfg", PATCHED_LAYOUTS)
def test_patched_engine_matches_the_synchronous_one(tiny_gpt, cfg):
    """With one and with two decodes in flight, admissions, chunk
    progress, first tokens (one an EOS, one a budget of one) and
    evictions whose blocks the next admission adopts at once give every
    request the tokens the synchronous engine gives it, and per-request
    ``generate()``'s where the pool keeps the compute dtype."""
    want = _eventful_run(_eventful_engine(tiny_gpt, async_depth=1, **cfg))
    assert len(want[0]) == 5 + 1 and len(want[1]) == 19 + 1
    if "kv_dtype" not in cfg:
        for n, ids in zip((11, 27, 8), want[2:5]):
            assert ids == _ref(tiny_gpt, np.asarray(ids[:n], np.int32),
                               7).tolist()
    for depth in (2, 3):
        eng = _eventful_engine(tiny_gpt, async_depth=depth, **cfg)
        assert _eventful_run(eng) == want, depth
        reg = eng.registry
        assert reg.get("serving.state_pushes").value == 1
        assert reg.get("serving.state_patches").value > 0
        whys = {e["args"]["why"] for e in _spans(eng, name="ring.drain")}
        assert whys <= {"spec", "tail", "idle"}, depth
        assert eng.scheduler.occupancy() == 0 and not eng._ring
        if eng._paged:
            if eng.prefix_cache is not None:
                eng.prefix_cache.clear()
            assert eng.block_pool.in_use() == 0


def test_bf16_pools_patched_parity():
    """The same run over bf16 pools (a model that computes in bf16):
    pipelined and synchronous engines agree token for token."""
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.to(dtype="bfloat16")
    m.eval()
    cfg = dict(kv_block_size=8, kv_blocks=14, prefill_chunk=8)
    want = _eventful_run(_eventful_engine(m, async_depth=1, **cfg))
    eng = _eventful_engine(m, async_depth=3, **cfg)
    assert _eventful_run(eng) == want
    assert "bfloat16" in str(eng.k_pools[0].dtype)
    assert eng.registry.get("serving.state_pushes").value == 1


def test_eviction_under_two_decodes_blocks_adopted_at_once(tiny_gpt):
    """THE HAZARD the drain used to hide.  ``async_depth=3``: a stream
    ends at consume of tick N with N+1 and N+2 queued, its blocks are
    released under them (each still writes one row of the frozen lane)
    and the pool, which holds nothing else, hands those very blocks to
    the request waiting at the gate in the next tick.  Sound because
    the device runs in order: the new owner's chunk program is queued
    behind both decodes and rewrites what it reads.  Tokens equal the
    synchronous engine's and ``generate()``'s."""
    rng = np.random.RandomState(5)
    a, b = (rng.randint(0, 128, (n,)).astype(np.int32) for n in (13, 17))

    def run(depth):
        eng = _engine(tiny_gpt, num_slots=2, max_seq_len=32,
                      kv_block_size=8, kv_blocks=4, prefill_chunk=8,
                      prefix_cache=False, async_depth=depth)
        ra = eng.submit(a, max_new_tokens=6)   # 3 of the 4 blocks
        rb = eng.submit(b, max_new_tokens=6)   # needs 3: waits for a's
        adopted = None
        for _ in range(200):
            if eng.scheduler.idle():
                break
            before = list(eng._slot_blocks[0])
            in_flight = len(eng._ring)
            eng.step()
            if ra.done() and adopted is None:
                # the tick that consumed a's last token released its
                # blocks with this many decodes still queued
                adopted = (in_flight, before)
        assert eng.scheduler.idle()
        owned_by_b = adopted[1]
        return (ra.result(timeout=1).tolist(),
                rb.result(timeout=1).tolist(), adopted[0], owned_by_b,
                eng)

    ta, tb, _, _, _ = run(1)
    ga, gb, in_flight, blocks, eng = run(3)
    assert (ga, gb) == (ta, tb)
    assert ga == _ref(tiny_gpt, a, 6).tolist()
    assert gb == _ref(tiny_gpt, b, 6).tolist()
    assert in_flight == 2              # two decodes were in flight
    assert len(blocks) == 3            # ...over a's three blocks
    assert "dirty" not in {e["args"]["why"]
                           for e in _spans(eng, name="ring.drain")}
    assert eng.block_pool.in_use() == 0


def test_first_token_read_behind_the_decode_it_feeds(tiny_gpt):
    """The final chunk's first token is picked on the device: the
    decode that follows is dispatched BEFORE the host reads the id
    (``first_token`` > ``prefill.d2h`` lies after ``dispatch`` in the
    tick), the read is 4 bytes behind the chunk program, and a first
    token that ends its request frees the slot while that decode's
    lane for it is dropped at consume, not called drift."""
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)
    long_run = eng.submit(_prompts(1)[0], max_new_tokens=20)
    for _ in range(3):
        eng.step()                      # a stream is decoding
    p = _prompts(4)[3]
    eos = int(_ref(tiny_gpt, p, 1)[-1])
    eng.tracer.clear()
    r = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    for _ in range(2):      # 9 tokens: two chunks, the second final
        eng.step()
    assert r.done() and r.result(timeout=1).tolist() == p.tolist() + [eos]
    tick = _spans(eng, name="tick")[-1]
    t0, t1 = tick["ts"], tick["ts"] + tick["dur"]
    inside = {e["name"]: e for e in _spans(eng)
              if t0 <= e["ts"] < t1 and e["name"] != "tick"}
    assert inside["dispatch"]["ts"] < inside["first_token"]["ts"]
    assert inside["first_token"]["ts"] <= inside["prefill.d2h"]["ts"]
    # the decode queued behind the chunk carried r's lane, live
    inf = eng._ring[-1]
    assert inf.dropped == {inf.slots[inf.reqs.index(r)].index}
    eng.run_until_idle()                # consume drops it: no drift
    assert long_run.result(timeout=1).tolist() == \
        _ref(tiny_gpt, _prompts(1)[0], 20).tolist()


def test_seeded_first_token_is_the_one_it_was(tiny_gpt):
    """A seeded sampled request whose prompt takes three chunks: the
    first token picked by the ``first_token`` program (the lane's own
    filters and fold(request_key, 0)) and the tokens after it are the
    ones the host-side pick gave before this change (recorded at the
    parent commit), on every layout and depth."""
    p = np.concatenate(_prompts(4))      # 24 tokens
    want = [86, 87, 20, 17, 82, 13, 105, 43]
    for kw in (dict(), dict(kv_block_size=8),
               dict(kv_block_size=8, prefill_chunk=8),
               dict(prefill_chunk=8, async_depth=3),
               dict(kv_block_size=8, prefill_chunk=8, async_depth=1)):
        eng = _engine(tiny_gpt, **kw)
        r = eng.submit(p, max_new_tokens=8, temperature=0.9, top_p=0.9,
                       top_k=20, seed=1234)
        eng.run_until_idle()
        assert r.result(timeout=2).tolist()[len(p):] == want, kw


def test_many_admissions_one_push_and_no_dirty_drain(tiny_gpt):
    """Forty requests through four slots: the state is uploaded whole
    ONCE, everything after it is a patch, the ring is never consumed
    for a dirty slot, and ``serving.ring_drains.<why>`` counts what
    the ``ring.drain`` spans say."""
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8,
                  trace_capacity=1 << 16)
    reqs = []
    for i, p in enumerate(_prompts(40)):
        reqs.append(eng.submit(p, max_new_tokens=3 + i % 5))
        if i % 3 == 0:
            eng.step()
    eng.run_until_idle()
    for p, r in zip(_prompts(40), reqs):
        assert r.result(timeout=1).tolist() == \
            _ref(tiny_gpt, p, r.max_new_tokens).tolist()
    reg = eng.registry
    assert reg.get("serving.state_pushes").value == 1
    assert len(_spans(eng, name="state.push")) == 1
    patches = [e for e in _spans(eng, name="state.patch")
               if "first_token" not in e["args"]]
    assert reg.get("serving.state_patches").value >= len(patches) >= 40
    picks = [e for e in _spans(eng, name="state.patch")
             if "first_token" in e["args"]]
    assert len(picks) == 40
    drains = _spans(eng, name="ring.drain")
    assert all(e["args"]["why"] != "dirty" for e in drains)
    for why in ("spec", "tail", "idle", "preempt", "migrate", "adapter"):
        assert reg.get(f"serving.ring_drains.{why}").value == \
            sum(e["args"]["why"] == why for e in drains), why
    assert reg.get("serving.ring_drains.dirty") is None
    # greedy requests only: the sampled pick (its sorts over the
    # vocabulary are the dear part to compile) was never built
    first = eng._state_fns[2]
    assert (first[False]._cache_size(), first[True]._cache_size()) == (1, 0)
    text = monitor.render_prometheus(reg)
    for name in ("serving_state_pushes", "serving_state_patches",
                 "serving_ring_drains_tail"):
        assert name in text


class backend_compiles:
    """Counts JAX's own backend compilations while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        self.count += event == self.EVENT

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


def test_state_programs_compile_once(tiny_gpt):
    """The patch, first-token and unpack programs are compiled once an
    engine, in its first requests: after those, slots and values
    (greedy and sampled lanes, every slot, one to four dirty slots a
    tick) are data, and nothing compiles."""
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)

    def wave(n0):
        for i, p in enumerate(_prompts(n0 + 12)[n0:]):
            kw = (dict(temperature=0.8, top_k=5, seed=i) if i % 3 == 0
                  else {})
            eng.submit(p, max_new_tokens=2 + i % 4, **kw)
            if i % 2:
                eng.step()
        eng.run_until_idle()

    wave(0)         # every program of this configuration, warm
    before = eng.registry.get("serving.state_patches").value
    with backend_compiles() as seen:
        wave(5)
    assert seen.count == 0
    assert eng.registry.get("serving.state_patches").value >= before + 6
    assert eng.registry.get("serving.state_pushes").value == 1
    unpack, patch, first = eng._state_fns
    assert [fn._cache_size() for fn in
            (unpack, patch, first[False], first[True])] == [1, 1, 1, 1]
