"""The block-diffusion / routed-experts decoder (models/sdar_moe.py)
against its plain reference (benchmarks/configs/sdar_moe_reference.py),
at a tiny size on the CPU with seeded float32 weights: the step that
carries a block of rows a lane through ``serving.Engine``
(``models/programs.py`` ``StepSpec``), the grouped-query cache (K and V
of a position in one flat row of one pool a layer), the softmax gate."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.distributed import moe
from paddle_tpu.models.mla_moe import RoutedFFN
from paddle_tpu.models.sdar_moe import SDARMoEModel
from paddle_tpu.serving import Engine
from paddle_tpu.serving.stream import TokenStream

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU: the program and the reference order their sums
# differently (a walk over cached chunks with a running maximum against
# one softmax, grouped heads against repeated ones, sorted pairs against
# a loop over experts); the largest difference in logits of magnitude
# ~1 measured over these cases is 9e-7
TOL = 1e-4

GEN = dict(block_length=4, denoising_steps=4, mask_token_id=127,
           remasking_strategy="low_confidence_static")
DIMS = dict(
    vocab_size=128, max_position_embeddings=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
    rope_theta=1000000, rope_scaling=None, generation=GEN)
ENGINE = dict(num_slots=3, max_seq_len=64, kv_block_size=8, kv_blocks=40,
              prefill_chunk=16)


def _load(name, *path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, *path))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def _reference():
    return _load("sdar_moe_reference_under_test", "benchmarks", "configs",
                 "sdar_moe_reference.py")


def _fault():
    return _load("planted_fault_commit_under_test", "tests", "benchmarks",
                 "planted_fault_commit.py")


def seeded(seed=0, dims=DIMS):
    """The model with every leaf drawn from ``seed`` (matrices normal
    0.08, gains 1 + normal 0.1), and ``get(names)`` that hands the same
    leaves to the reference."""
    model = SDARMoEModel(dims, **dims["generation"])
    model.eval()
    leaves = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v = jax.random.normal(key, tuple(p.shape), jnp.float32)
        v = 1.0 + 0.1 * v if len(p.shape) == 1 else 0.08 * v
        p.set_value(v)
        leaves[name] = v
    return model, (lambda names: {n: leaves[n] for n in names})


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, DIMS["vocab_size"], n).tolist()


def served(model, requests, **options):
    """``[(prompt, max_new)]`` through one engine; the generated
    lists."""
    eng = Engine(model, registry=monitor.StatRegistry(),
                 **{**ENGINE, **options})
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in requests]
    eng.run_until_idle()
    return [list(r.generated) for r in reqs], eng


# -- (a) the engine's path against the published loop --------------------

# prompts of every n % 4, one shorter than a block, answers that end
# inside a block, at its edge and one past it, a prompt one chunk short
# of the table's end, chunks that are no whole number of blocks
CASES = {
    "n%4=0": (dict(), [(16, 4), (8, 5)]),
    "n%4=1": (dict(), [(17, 3), (5, 6)]),
    "n%4=2": (dict(), [(18, 1), (6, 9)]),
    "n%4=3": (dict(), [(19, 5), (11, 4)]),
    "shorter than a block": (dict(), [(2, 7), (3, 1), (1, 4)]),
    "a chunk short of the table's end": (dict(), [(48, 16), (47, 17)]),
    "chunk 12, blocks of 8": (dict(prefill_chunk=12, max_seq_len=96,
                                   kv_blocks=48), [(29, 6), (40, 3)]),
    "one slot": (dict(num_slots=1), [(9, 4), (21, 5)]),
}


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_engine_serves_what_the_published_loop_generates(case, depth):
    options, shapes = CASES[case]
    model, get = seeded(seed=1)
    requests = [(tokens(n, seed=n), m) for n, m in shapes]
    got, eng = served(model, requests, async_depth=depth, **options)
    for (prompt, m), out in zip(requests, got):
        assert out == _reference().generate(get, DIMS, prompt, m)
        assert len(out) == m
    assert eng.registry.get("serving.compiles_total").value <= 2


def test_a_lane_opens_its_first_step_behind_two_steps_in_flight():
    """``_open_step`` reaches the device as a patch of the lane's rows,
    cursor and flags queued behind the steps in flight: five requests
    through two slots of a pool that fits two, at ``async_depth=3``,
    are served what the published loop generates, with one whole
    upload of the state and no drain for a dirty slot."""
    model, get = seeded(seed=1)
    requests = [(tokens(n, seed=n), m)
                for n, m in ((17, 6), (8, 5), (30, 9), (5, 4), (19, 7))]
    got, eng = served(model, requests, async_depth=3, num_slots=2,
                      kv_blocks=12, trace_capacity=1 << 16)
    for (prompt, m), out in zip(requests, got):
        assert out == _reference().generate(get, DIMS, prompt, m)
    reg = eng.registry
    assert reg.get("serving.state_pushes").value == 1
    assert reg.get("serving.state_patches").value >= len(requests)
    whys = {e["args"]["why"]
            for e in eng.chrome_trace()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "ring.drain"}
    assert whys <= {"tail", "idle"}
    assert [fn._cache_size() for fn in eng._state_fns[:2]] == [1, 1]


def paged_block_logits(model, prompt, block, masked, chunk=8, bs=8,
                       nb=10):
    """Logits [W, V] of one block's rows (``masked`` [W] bool) after
    ``prompt`` (whole blocks) went through the chunk program into paged
    pools: the step's blocks and head, without its unmasking."""
    L = 64
    shape = (nb + 1, bs, 2 * model.config["num_key_value_heads"]
             * model.config["head_dim"])
    pools = [jnp.zeros(shape, jnp.float32) for _ in model.blocks]
    table = np.zeros(L // bs, np.int32)
    table[:nb - 2] = 3 + np.arange(nb - 2)      # blocks 3.. are the slot's
    table = jnp.asarray(table)
    for p0 in range(0, len(prompt), chunk):
        part = prompt[p0:p0 + chunk]
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :len(part)] = part
        _, pools, _, _ = model._chunk_prefill_tick_paged(
            jnp.asarray(ids), pools, table, p0, len(part), 0)
    pos = jnp.asarray([len(prompt)], jnp.int32)
    x = model.embed._data[jnp.where(
        jnp.asarray(masked), model.mask_token_id,
        jnp.asarray(block, jnp.int32))][None]
    for j, blk in enumerate(model.blocks):
        x, _, _ = blk.step_slots_paged(
            x, pools[j], table[None], pos, jnp.ones((1,), bool))
    return np.asarray(model._head(x)[0])


@pytest.mark.parametrize("n, masked", [
    (0, [True] * 4), (8, [True] * 4), (12, [False, True, False, True]),
    (20, [False] * 4), (28, [True, False, False, False])])
def test_a_block_over_the_paged_cache_equals_the_reference(n, masked):
    """The grouped-query attention over the pools of 2-head rows (the
    walk, the block's own rows, the block-causal chunk program before
    it) against the reference's one softmax with K and V repeated."""
    model, get = seeded(seed=2)
    prompt, block = tokens(n, seed=3), tokens(4, seed=4)
    got = paged_block_logits(model, prompt, block, masked)
    want = np.asarray(_reference().logits(
        get, DIMS, prompt + block, [False] * n + masked))[n:]
    assert np.abs(got - want).max() < TOL
    assert np.abs(want).max() > 0.1


def test_whole_forward_equals_the_reference():
    model, get = seeded(seed=5)
    ids = tokens(22, seed=6)
    masked = np.arange(22) >= 17
    got = np.asarray(model.forward(jnp.asarray(ids)[None],
                                   masked[None])._data[0])
    want = np.asarray(_reference().logits(get, DIMS, ids, masked))
    assert np.abs(got - want).max() < TOL


def _flat_pool_case(seed, positions, bs=8, table_rows=512):
    """A seeded ``GQAttention`` (4 query / 2 K/V heads of 16, blocks of
    4), hidden states [T, D] a slot, and a pool of noise in which slot
    b's rows below ``positions[b]`` are laid through its own table by
    plain indexing (not by the programs under test).  Returns (attn,
    hidden [B, T, D], pool, tables [B, table_rows // bs], every
    position's row [B, T, 64])."""
    from paddle_tpu.models.sdar_moe import GQAttention
    attn = GQAttention(64, 4, 2, 16, 1e6, 1e-6, 4)
    for i, (_, p) in enumerate(attn.named_parameters()):
        v = jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(seed), i), tuple(p.shape), jnp.float32)
        p.set_value(1.0 + 0.1 * v if len(p.shape) == 1 else 0.3 * v)
    rng = np.random.default_rng(seed)
    n, per = len(positions), table_rows // bs
    T = max(positions) + 16
    hidden = rng.normal(size=(n, T, 64)).astype(np.float32)
    # block 0 is the scratch block; the last 5 belong to nobody
    pool = rng.normal(size=(1 + n * per + 5, bs, 64)).astype(np.float32)
    tables = 1 + rng.permutation(n * per).reshape(n, per).astype(np.int32)
    at = np.broadcast_to(np.arange(T)[None, :], (n, T))
    _, k, v = attn.project(jnp.asarray(hidden), jnp.asarray(at))
    rows = np.asarray(attn.cache_rows(k, v))
    for b, pos in enumerate(positions):
        for i in range(pos):
            pool[tables[b, i // bs], i % bs] = rows[b, i]
    return attn, hidden, pool, tables, rows


# a walk chunk is 256 rows here (``walk_chunk``: 32 blocks of 8)
@pytest.mark.parametrize("program", ["step", "chunk"])
@pytest.mark.parametrize("pos", [264, 268, 256, 0], ids=[
    "at a block's edge", "inside a block", "at a chunk's edge",
    "at 0 (a parked lane)"])
def test_the_flat_pool_gives_what_the_uncached_attention_gives(pos,
                                                               program):
    """``step_slots_paged`` (three slots: the case's, one two chunks
    deep, one parked on the scratch block) and ``prefill_chunk_paged``
    (16 rows of which 12 are real) over the one flat pool against
    ``GQAttention.forward`` on the same hidden states; every row of the
    pool outside ``[pos, pos + rows written)`` of the writing slots,
    other slots' blocks and nobody's blocks included, comes back bit
    for bit, and the written rows are ``cache_rows`` of the projection
    (to rounding: one product over all positions against the
    program's over its own rows)."""
    positions = [pos, 300, 0] if program == "step" else [pos]
    attn, hidden, pool, tables, rows = _flat_pool_case(31, positions)
    bs = pool.shape[1]
    wrote = 4 if program == "step" else 12
    if program == "step":
        tables[2, :] = 0                     # a parked lane: scratch
        at = np.asarray(positions, np.int32)
        h = np.stack([hidden[b, p:p + 4] for b, p in enumerate(positions)])
        out, new_pool = attn.step_slots_paged(
            jnp.asarray(h), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(at), jnp.asarray(at))
    else:
        out, new_pool = attn.prefill_chunk_paged(
            jnp.asarray(hidden[:, pos:pos + 16]), jnp.asarray(pool),
            jnp.asarray(tables[0]), jnp.int32(pos), jnp.int32(wrote),
            jnp.int32(0))
    out, new_pool = np.asarray(out), np.asarray(new_pool)
    written = np.zeros(pool.shape[:2], bool)
    for b, p in enumerate(positions):
        want = np.asarray(attn.forward(
            jnp.asarray(hidden[b:b + 1, :p + wrote])))[0, p:]
        assert np.abs(out[b, :wrote] - want).max() < TOL
        assert np.abs(want).max() > 0.05
        for i in range(p, p + wrote):
            where = tables[b, i // bs], i % bs
            written[where] = True
            assert np.abs(new_pool[where] - rows[b, i]).max() < TOL
    assert written.sum() == len(positions) * wrote
    assert np.array_equal(new_pool[~written], pool[~written])


def test_the_mask_is_block_causal():
    """Inside a block a row's logits follow a LATER row of the block;
    they do not follow the next block."""
    model, _ = seeded(seed=7)
    ids = np.asarray(tokens(12, seed=8))
    base = np.asarray(model.forward(jnp.asarray(ids)[None])._data[0])
    for at, moved in ((6, True), (8, False)):
        other = ids.copy()
        other[at] = (other[at] + 1) % 100 + 1
        out = np.asarray(model.forward(jnp.asarray(other)[None])._data[0])
        assert (np.abs(out[5] - base[5]).max() > 1e-3) == moved


# -- (b) the prefix cache holds committed rows only ----------------------

def test_a_second_turn_adopts_committed_blocks_and_is_served_the_same():
    model, get = seeded(seed=9)
    prompt = tokens(21, seed=10)
    (answer,), eng = served(model, [(prompt, 14)])
    turn = prompt + answer + tokens(5, seed=11)
    hits = eng.registry.get("serving.prefix_hit_tokens")
    before = hits.value
    r = eng.submit(turn, max_new_tokens=7)
    eng.run_until_idle()
    # prompt + answer is 35 tokens: 32 of its rows were committed (the
    # answer's last block, positions 32-34, was sent and never
    # committed), so 4 blocks of 8 are adopted and no more
    assert hits.value - before == 32
    (cold,), _ = served(seeded(seed=9)[0], [(turn, 7)],
                        prefix_cache=False)
    assert list(r.generated) == cold \
        == _reference().generate(get, DIMS, turn, 7)


def test_a_prompt_of_adopted_blocks_alone_runs_no_chunk():
    model, get = seeded(seed=12)
    prompt = tokens(18, seed=13)
    eng = Engine(model, registry=monitor.StatRegistry(), **ENGINE)
    first = eng.submit(prompt, max_new_tokens=3)
    eng.run_until_idle()
    chunks = eng.registry.get("serving.prefill_chunks")
    before = chunks.value
    again = eng.submit(prompt[:16] + [5, 6], max_new_tokens=3)
    eng.run_until_idle()
    assert chunks.value == before
    assert list(again.generated) == _reference().generate(
        get, DIMS, prompt[:16] + [5, 6], 3)
    assert len(first.generated) == 3


@pytest.mark.parametrize("option, value", [("kv_block_size", 6),
                                           ("prefill_chunk", 6)])
def test_blocks_and_chunks_keep_the_model_s_alignment(option, value):
    model, _ = seeded(seed=14)
    with pytest.raises(ValueError, match="multiple of 4"):
        Engine(model, **{**ENGINE, "max_seq_len": 48, option: value})


# -- (c) the stream's rule ----------------------------------------------

@pytest.mark.parametrize("depth", [1, 2])
def test_frames_arrive_in_position_order(depth):
    model, get = seeded(seed=15)
    eng = Engine(model, async_depth=depth, **ENGINE)
    prompts = [tokens(n, seed=n) for n in (7, 12, 18)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (11, 6, 9))]
    streams = [TokenStream(r) for r in reqs]
    eng.run_until_idle()
    for p, r, s in zip(prompts, reqs, streams):
        events = [ev for ev in s if ev.kind == "token"]
        assert [ev.index for ev in events] == list(range(r.max_new_tokens))
        assert [ev.token for ev in events] == list(r.generated) \
            == _reference().generate(get, DIMS, p, r.max_new_tokens)


def test_the_first_final_eos_in_position_order_ends_the_stream():
    model, get = seeded(seed=16)
    prompt = tokens(10, seed=17)
    free = _reference().generate(get, DIMS, prompt, 12)
    eos = free[5]
    eng = Engine(model, **ENGINE)
    r = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    eng.run_until_idle()
    assert list(r.generated) == free[:free.index(eos) + 1]


def test_the_step_s_counters_and_healthz_field():
    model, _ = seeded(seed=18)
    (out,), eng = served(model, [(tokens(8, seed=19), 8)])
    value = {n: eng.registry.get("serving." + n).value for n in (
        "denoise_passes", "commit_passes", "block_tokens_fixed",
        "blocks_committed", "moe_routed_pairs")}
    # two blocks of four: 8 denoise passes; the first is committed, the
    # second finishes the request and never is; the prompt's 2 blocks
    # were committed by the chunk program
    assert value["denoise_passes"] == 8 == value["block_tokens_fixed"]
    assert value["commit_passes"] == 1 and value["blocks_committed"] == 3
    # 9 step passes x 4 rows + 8 prompt rows, top 2, over 2 layers
    assert value["moe_routed_pairs"] == (9 * 4 + 8) * 2 * 2
    assert eng.step_report() == {"rows": 4, "steps": 4}
    # K and V of 2 heads of 16 in one flat row of one pool a layer
    assert eng.kv_geometry() == {"block_size": 8,
                                 "rows": [["kv", [2 * 2 * 16]]],
                                 "n_layers": 2}
    assert len(eng.k_pools) == 2 and eng.v_pools == []
    assert eng.k_pools[0].shape == (41, 8, 64)
    assert eng.registry.get("serving.kv_row_bytes").value \
        == 2 * 2 * 2 * 16 * 4


def test_sampled_requests_and_unwritten_options_are_refused_by_name():
    model, _ = seeded(seed=20)
    eng = Engine(model, **ENGINE)
    with pytest.raises(ValueError, match="sampled request"):
        eng.submit(tokens(5), max_new_tokens=4, temperature=0.7)
    for options, named in ((dict(kv_block_size=None, kv_blocks=None),
                            "contiguous"),
                           (dict(spec_k=2), "spec_k"),
                           (dict(kv_dtype="int8"), "kv_dtype"),
                           (dict(attn_impl="ragged"), "ragged")):
        with pytest.raises(ValueError, match="SDARMoEModel") as err:
            Engine(model, **{**ENGINE, **options})
        assert named in str(err.value)


def test_weight_only_int8_relayouts_the_four_projections():
    model, _ = seeded(seed=21)
    (out,), _ = served(model, [(tokens(9), 5)], weight_dtype="int8")
    assert len(out) == 5
    assert {type(getattr(model.blocks[1].attn, n)).__name__
            for n in ("q_proj", "k_proj", "v_proj", "o_proj")} \
        == {"WeightOnlyInt8Linear"}


# -- (d) the gate and the routed layer -----------------------------------

@pytest.mark.parametrize("normalize", [True, False])
def test_softmax_topk_routing_against_a_loop(normalize):
    logits = jax.random.normal(jax.random.PRNGKey(3), (9, 16)) * 2.0
    choice, weights = moe.softmax_topk_routing(logits, 3, normalize)
    for t in range(9):
        p = np.exp(np.asarray(logits[t], np.float64))
        p /= p.sum()
        best = np.argsort(-p)[:3]
        assert list(choice[t]) == list(best)
        want = p[best] / (p[best].sum() if normalize else 1.0)
        assert np.allclose(weights[t], want, atol=1e-6)


def test_the_routed_layer_without_a_shared_expert_against_a_loop():
    layer = RoutedFFN(16, 8, 6, 2, 0, 1.0, True, gate="softmax")
    assert layer.shared is None and not hasattr(layer, "gate_bias")
    assert sorted(n for n, _ in layer.named_parameters()) \
        == ["experts_in", "experts_out", "gate_weight"]
    x = jax.random.normal(jax.random.PRNGKey(4), (7, 16))
    live = jnp.asarray([True] * 5 + [False, True])
    y, stats = layer(x, live)
    w_g, w_in, w_out = (np.asarray(p._data, np.float64) for p in (
        layer.gate_weight, layer.experts_in, layer.experts_out))
    for t in range(7):
        h = np.asarray(x[t], np.float64)
        p = np.exp(h @ w_g)
        p /= p.sum()
        want = np.zeros(16)
        for e in np.argsort(-p)[:2]:
            a = h @ w_in[e]
            act = a[:8] / (1 + np.exp(-a[:8])) * a[8:]
            want += p[e] / np.sort(p)[-2:].sum() * (act @ w_out[e])
        assert np.allclose(y[t], want if live[t] else 0.0, atol=1e-5)
    assert int(stats[0]) == 12        # six live rows, two experts each


# -- (f) the comparison that decides ``correct`` -------------------------

def _regret(get, prompt, answer, pad=64):
    ids = np.zeros((1, pad), np.int32)
    ids[0, :len(prompt) + len(answer)] = prompt + answer
    out = np.full((1, pad), -1, np.int32)
    n = len(prompt)
    out[0, n - 1:n - 1 + len(answer)] = answer
    regret, valid, top = _reference().served_regret(get, DIMS, ids, out)
    return regret[valid], valid, top, out


@pytest.mark.parametrize("seed", [22, 23, 24])
def test_served_regret_is_zero_on_the_engine_s_own_output(seed):
    model, get = seeded(seed=seed)
    prompt = tokens(13, seed=seed)
    (answer,), _ = served(model, [(prompt, 14)])
    regret, valid, top, out = _regret(get, prompt, answer)
    # positions 13..23: the answer's whole blocks (its last, 24-26, is
    # partly sent and left out)
    assert valid.sum() == 11
    assert np.abs(regret).max() < 1e-4
    assert (top[valid] == out[valid]).all()


def test_served_regret_charges_a_token_that_was_not_the_best():
    model, get = seeded(seed=25)
    prompt = tokens(13, seed=25)
    (answer,), _ = served(model, [(prompt, 14)])
    answer[5] = (answer[5] + 1) % 100 + 1
    regret, _, _, _ = _regret(get, prompt, answer)
    assert regret.max() > 1e-2


def test_served_regret_charges_a_block_fixed_left_to_right():
    """The same model made to fix the leftmost masked position instead
    of the most confident one: every token is some state's best, and
    the order is what is charged."""
    _, get = seeded(seed=26)
    prompt = tokens(13, seed=26)
    forced = _reference().generate(get, DIMS, prompt, 14,
                                   order=lambda open_, c: open_[:1])
    assert forced != _reference().generate(get, DIMS, prompt, 14)
    regret, _, _, _ = _regret(get, prompt, forced)
    assert regret.max() > 1e-3


def test_served_regret_charges_a_skipped_commit_pass():
    model, get = seeded(seed=27)
    prompt = tokens(13, seed=27)
    step = _fault().plant()
    try:
        (stale,), eng = served(model, [(prompt, 14)])
    finally:
        SDARMoEModel._fused_step_slots = step
    assert eng.registry.get("serving.commit_passes").value == 0
    regret, _, _, _ = _regret(get, prompt, stale)
    assert regret.max() > 1e-3


# -- the tiles of the grouped product ------------------------------------

@pytest.mark.parametrize("shape, tiles", [
    # kimi-vl-a3b-serve's two products, as they were (chip run, PR 28)
    ((192, 2048, 2816), (192, 2048, 1408)),
    ((192, 1408, 2048), (192, 1408, 1024)),
    ((1536, 2048, 2816), (128, 2048, 1408)),
    ((1536, 1408, 2048), (128, 1408, 1024)),
    # this model's two, a step's and a chunk's pairs (chip run, PR 35:
    # the docstring)
    ((1024, 2048, 1536), (128, 2048, 1536)),
    ((1024, 768, 2048), (128, 768, 2048)),
    ((2048, 2048, 1536), (128, 2048, 1536)),
    ((2048, 768, 2048), (128, 768, 2048)),
])
def test_the_tiles_of_the_grouped_products(shape, tiles):
    assert moe._gmm_tiling(*shape) == tiles
