"""Paged KV cache (serving/kvcache.py): BlockPool alloc/free/refcount/
COW invariants, PrefixCache trie + LRU eviction, and the engine
integration — prefix-hit parity (greedy outputs token-identical with
the cache on vs off vs the contiguous engine vs generate()), deferred
admission + eviction under pool pressure, and the monitor surface.
All CPU, tiny model, tier-1 safe."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import (BlockPool, Engine, NoFreeBlocks,
                                PrefixCache)


# ---------------------------------------------------------------------------
# BlockPool invariants (pure host-side metadata, no jax)
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_free_roundtrip(self):
        pool = BlockPool(8, 4, reserved_blocks=1)
        assert pool.managed_blocks == 7
        assert pool.free_count() == 7 and pool.in_use() == 0
        a = pool.alloc(3)
        assert len(a) == 3 and len(set(a)) == 3
        assert all(b >= 1 for b in a)       # reserved block 0 never leaves
        assert pool.in_use() == 3
        assert all(pool.refcount(b) == 1 for b in a)
        freed = pool.decref(a)
        assert sorted(freed) == sorted(a)
        assert pool.free_count() == 7

    def test_alloc_exhaustion_raises(self):
        pool = BlockPool(4, 2)
        pool.alloc(3)
        with pytest.raises(NoFreeBlocks):
            pool.alloc(2)
        pool.alloc(1)  # exactly the remainder still works

    def test_refcount_sharing(self):
        pool = BlockPool(4, 2)
        (b,) = pool.alloc(1)
        pool.incref(b)
        pool.incref([b])
        assert pool.refcount(b) == 3
        assert pool.decref(b) == []          # still shared
        assert pool.decref(b) == []
        assert pool.decref(b) == [b]         # last ref frees
        with pytest.raises(RuntimeError, match="double free"):
            pool.decref(b)
        with pytest.raises(RuntimeError, match="free block"):
            pool.incref(b)

    def test_cow_sole_owner_no_copy(self):
        pool = BlockPool(4, 2)
        (b,) = pool.alloc(1)
        nb, copied = pool.cow(b)
        assert nb == b and not copied
        assert pool.refcount(b) == 1

    def test_cow_shared_moves_ref(self):
        pool = BlockPool(4, 2)
        (b,) = pool.alloc(1)
        pool.incref(b)                       # a second owner
        nb, copied = pool.cow(b)
        assert copied and nb != b
        assert pool.refcount(b) == 1         # original keeps one owner
        assert pool.refcount(nb) == 1        # caller owns the copy
        assert pool.in_use() == 2

    def test_cow_exhausted_pool_keeps_ref(self):
        pool = BlockPool(3, 2)               # 3 managed
        (b,) = pool.alloc(1)
        pool.incref(b)
        pool.alloc(2)                        # pool now empty
        with pytest.raises(NoFreeBlocks):
            pool.cow(b)
        assert pool.refcount(b) == 2         # failure left the ref intact


# ---------------------------------------------------------------------------
# PrefixCache trie + LRU eviction
# ---------------------------------------------------------------------------

class TestPrefixCache:
    def _cache(self, blocks=16, bs=4):
        pool = BlockPool(blocks, bs)
        return pool, PrefixCache(pool)

    def test_insert_match_roundtrip(self):
        pool, pc = self._cache()
        toks = np.arange(13, dtype=np.int32)          # 3 full blocks + 1
        blocks = pool.alloc(3)
        pc.insert(toks, blocks)
        assert all(pool.refcount(b) == 2 for b in blocks)  # slot + cache
        pool.decref(blocks)                            # slot evicted
        assert all(pool.refcount(b) == 1 for b in blocks)  # cache-held
        got, m = pc.match(toks)
        assert got == blocks and m == 12
        assert all(pool.refcount(b) == 2 for b in got)     # adopter refs

    def test_match_leaves_one_token_for_prefill(self):
        pool, pc = self._cache()
        toks = np.arange(8, dtype=np.int32)           # exactly 2 blocks
        blocks = pool.alloc(2)
        pc.insert(toks, blocks)
        got, m = pc.match(toks)
        # a full match is capped at 1 block: admission still needs a
        # last-position logit from the adopter's own tail forward
        assert m == 4 and got == blocks[:1]
        pool.decref(got)

    def test_partial_match_divergent_tail(self):
        pool, pc = self._cache()
        toks = np.arange(12, dtype=np.int32)
        blocks = pool.alloc(3)
        pc.insert(toks, blocks)
        other = np.concatenate([toks[:8], toks[8:] + 50]).astype(np.int32)
        got, m = pc.match(other)
        assert m == 8 and got == blocks[:2]
        pool.decref(got)
        miss, m0 = pc.match(np.arange(100, 110, dtype=np.int32))
        assert miss == [] and m0 == 0

    def test_duplicate_insert_keeps_first(self):
        pool, pc = self._cache()
        toks = np.arange(8, dtype=np.int32)
        first = pool.alloc(2)
        pc.insert(toks, first)
        dup = pool.alloc(2)                   # same-tick second request
        pc.insert(toks, dup)
        assert all(pool.refcount(b) == 2 for b in first)
        assert all(pool.refcount(b) == 1 for b in dup)  # stays slot-only
        got, _ = pc.match(np.concatenate([toks, [99]]))
        assert got == first
        pool.decref(got)

    def test_lru_eviction_leaves_first(self):
        pool, pc = self._cache()
        a = np.arange(0, 9, dtype=np.int32)           # 2 full blocks
        b = np.arange(50, 59, dtype=np.int32)
        ba, bb = pool.alloc(2), pool.alloc(2)
        pc.insert(a, ba)
        pc.insert(b, bb)
        pool.decref(ba)
        pool.decref(bb)
        touched, _ = pc.match(b)       # refresh b's LRU stamp
        pool.decref(touched)
        # evict 1: the LRU leaf is a's DEEPEST block (parents with
        # children are never evictable)
        freed = pc.evict(1)
        assert freed == [ba[1]]
        got, m = pc.match(np.concatenate([a, [99]]))
        assert m == 4 and got == ba[:1]       # a's root block survives
        pool.decref(got)
        freed = pc.evict(10)                  # drain everything evictable
        assert set(freed) == {ba[0], bb[0], bb[1]}
        assert pc.cached_blocks() == 0

    def test_eviction_skips_blocks_in_use(self):
        pool, pc = self._cache()
        toks = np.arange(9, dtype=np.int32)
        blocks = pool.alloc(2)
        pc.insert(toks, blocks)               # refcount 2 (slot + cache)
        assert pc.evict(2) == []              # adopters alive: nothing
        pool.decref(blocks)
        assert set(pc.evict(2)) == set(blocks)


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

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
    kw.setdefault("kv_block_size", 8)
    return Engine(model, **kw)


def _prompts(n, lens=(5, 7, 3, 9, 4, 6)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def _refs(model, prompts, n_new):
    return [model.generate(paddle.to_tensor(p[None, :]),
                           max_new_tokens=n_new).numpy()[0].tolist()
            for p in prompts]


def test_paged_parity_staggered(tiny_gpt):
    """The acceptance-criterion case: staggered concurrent requests on
    the PAGED engine decode token-identically to the contiguous engine
    and to per-request generate()."""
    eng = _engine(tiny_gpt)
    ref_eng = Engine(tiny_gpt, num_slots=4, max_seq_len=48,
                     registry=monitor.StatRegistry())   # contiguous
    prompts = _prompts(4)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(p, max_new_tokens=8) for p in prompts[2:]]
    eng.run_until_idle()
    ref_reqs = [ref_eng.submit(p, max_new_tokens=8) for p in prompts]
    ref_eng.run_until_idle()
    gen_refs = _refs(tiny_gpt, prompts, 8)
    for r, rr, g in zip(reqs, ref_reqs, gen_refs):
        got = r.result(timeout=1).tolist()
        assert got == rr.result(timeout=1).tolist()
        assert got == g


def test_prefix_hit_parity_and_metrics(tiny_gpt):
    """Shared-system-prompt traffic: adopters skip prefill for the
    cached span yet decode token-identically to a prefix-cache-OFF
    paged engine (and generate()); hit counters land in monitor."""
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, 128, (20,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(0, 128, (k,))
                               .astype(np.int32)]) for k in (3, 5, 4, 6)]
    gen_refs = _refs(tiny_gpt, prompts, 6)

    reg_on = monitor.StatRegistry()
    eng_on = _engine(tiny_gpt, registry=reg_on)
    reg_off = monitor.StatRegistry()
    eng_off = _engine(tiny_gpt, registry=reg_off, prefix_cache=False)

    for eng, reg in ((eng_on, reg_on), (eng_off, reg_off)):
        first = eng.submit(prompts[0], max_new_tokens=6)
        eng.run_until_idle()          # prompt 0's blocks now cached
        rest = [eng.submit(p, max_new_tokens=6) for p in prompts[1:]]
        eng.run_until_idle()
        outs = [first.result(timeout=1).tolist()] + \
            [r.result(timeout=1).tolist() for r in rest]
        assert outs == gen_refs

    assert reg_on.get("serving.prefix_hits").value == 3
    # 20-token shared prefix -> 2 full 8-token blocks adopted per hit
    assert reg_on.get("serving.prefix_hit_tokens").value == 3 * 16
    assert reg_off.get("serving.prefix_hits").value == 0
    # the hits are real work saved: fewer prefill tokens computed
    on_tok = reg_on.get("serving.prefill_tokens").value
    off_tok = reg_off.get("serving.prefill_tokens").value
    assert on_tok == off_tok - 3 * 16
    text = monitor.render_prometheus(reg_on)
    assert "serving_prefix_hits 3" in text
    assert "serving_kv_blocks_in_use" in text
    assert "serving_prefix_evictions 0" in text


def test_blocks_released_on_finish(tiny_gpt):
    """At idle only cached prefix blocks stay referenced; decode-span
    blocks return to the free list (no leaks across requests)."""
    eng = _engine(tiny_gpt)
    reqs = [eng.submit(p, max_new_tokens=8) for p in _prompts(4)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=1)
    assert eng.scheduler.occupancy() == 0
    assert eng.block_pool.in_use() == eng.prefix_cache.cached_blocks()
    # every live block is exactly the cache's own single reference
    for node in eng.prefix_cache._iter_nodes():
        assert eng.block_pool.refcount(node.block) == 1


def test_deferred_admission_under_block_pressure(tiny_gpt):
    """kv_blocks below the slot pool's worst case: admission defers on
    block reservation (not slot count) and every request still decodes
    to parity once blocks free up."""
    eng = _engine(tiny_gpt, kv_blocks=7)   # ~2 concurrent max requests
    prompts = [p for p in _prompts(4)]
    gen_refs = _refs(tiny_gpt, prompts, 8)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    assert eng.scheduler.occupancy() < 4    # slots idle for lack of blocks
    assert eng.queue.depth() > 0
    eng.run_until_idle()
    for r, g in zip(reqs, gen_refs):
        assert r.result(timeout=1).tolist() == g


def test_eviction_under_pool_pressure(tiny_gpt):
    """A cached prefix occupying most of a tight pool is LRU-evicted
    the moment an unrelated admission needs its blocks."""
    reg = monitor.StatRegistry()
    eng = _engine(tiny_gpt, num_slots=1, kv_blocks=6, registry=reg)
    rng = np.random.RandomState(5)
    a = rng.randint(0, 128, (17,)).astype(np.int32)   # caches 2 blocks
    b = rng.randint(0, 128, (18,)).astype(np.int32)
    ref_a = _refs(tiny_gpt, [a], 8)[0]
    ref_b = _refs(tiny_gpt, [b], 15)[0]
    ra = eng.submit(a, max_new_tokens=8)
    eng.run_until_idle()
    assert eng.prefix_cache.cached_blocks() == 2
    # b needs ceil(33/8)=5 blocks but only 4 are free: admission must
    # LRU-evict one of a's cached prefix blocks to proceed
    rb = eng.submit(b, max_new_tokens=15)
    eng.run_until_idle()
    assert ra.result(timeout=1).tolist() == ref_a
    assert rb.result(timeout=1).tolist() == ref_b
    assert reg.get("serving.prefix_evictions").value >= 1
    assert "serving_prefix_evictions" in monitor.render_prometheus(reg)


def test_paged_step_failure_recovers(tiny_gpt, monkeypatch):
    """The engine's failure recovery extends to the paged state: pools,
    block pool, prefix cache, and tables are rebuilt and serving
    continues (the cached prefixes die with the device rows they
    described)."""
    eng = _engine(tiny_gpt)
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
    assert eng.block_pool.in_use() == 0
    p = _prompts(2)[1]
    r2 = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    assert r2.result(timeout=1).tolist() == _refs(tiny_gpt, [p], 6)[0]


def test_paged_sampling_and_eos(tiny_gpt):
    """Non-greedy requests and mid-sequence EOS ride the paged path
    unchanged (block release on early eviction included)."""
    eng = _engine(tiny_gpt)
    p = _prompts(1)[0]
    full = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                             max_new_tokens=8).numpy()[0]
    eos = int(full[len(p) + 3])
    ref = tiny_gpt.generate(paddle.to_tensor(p[None, :]),
                            max_new_tokens=8,
                            eos_token_id=eos).numpy()[0].tolist()
    r_eos = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    r_samp = eng.submit(p, max_new_tokens=5, temperature=0.8, top_k=20,
                        seed=3)
    eng.run_until_idle()
    assert r_eos.result(timeout=1).tolist() == ref
    assert r_samp.result(timeout=1).shape[0] == len(p) + 5
    assert eng.block_pool.in_use() == eng.prefix_cache.cached_blocks()


def test_refresh_params_flushes_prefix_cache(tiny_gpt):
    """Cached prefixes hold K/V computed under the OLD weights — a
    weight mutation + refresh_params must flush them, or an adopter
    would silently decode against stale state."""
    eng = _engine(tiny_gpt)
    p = np.random.RandomState(9).randint(0, 128, (17,)).astype(np.int32)
    r = eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    r.result(timeout=1)
    assert eng.prefix_cache.cached_blocks() > 0
    eng.refresh_params()
    assert eng.prefix_cache.cached_blocks() == 0
    assert eng.block_pool.in_use() == 0


def test_engine_param_validation(tiny_gpt):
    with pytest.raises(ValueError, match="divide"):
        _engine(tiny_gpt, kv_block_size=7)       # 48 % 7 != 0
    with pytest.raises(ValueError, match="max-length"):
        _engine(tiny_gpt, kv_blocks=2)           # < one full request
