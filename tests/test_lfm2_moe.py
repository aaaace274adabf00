"""The decoder of short-convolution and attention layers
(models/lfm2_moe.py) against its plain reference
(benchmarks/configs/lfm2_moe_reference.py), at a tiny size on the CPU
with seeded float32 weights: the convolution's state in the tails of
the paged cache's blocks (``models/programs.py``
``KVRowSpec.block_rows``) beside the attention layers' rows, through
the chunk program, the decode step and ``serving.Engine``."""
import importlib.util
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.models.lfm2_moe import KINDS, Lfm2MoeModel, ShortConv
from paddle_tpu.serving import Engine, EngineServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU: the program and the reference order their sums
# differently (a walk over cached chunks with a running maximum against
# one softmax over masked blocks, sorted pairs against a loop over
# experts, a convolution continued from a stored state against one from
# a zero start); the largest difference in logits of magnitude ~1
# measured over these cases is 3e-6
TOL = 1e-4
BS, CHUNK = 4, 8
DIMS = dict(
    vocab_size=128, max_position_embeddings=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=9,
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    norm_eps=1e-5, rope_theta=1000000,
    layer_types=["conv"] + ["full_attention", "conv", "conv", "conv"] * 2)
ENGINE = dict(num_slots=3, max_seq_len=64, kv_block_size=BS, kv_blocks=60,
              prefill_chunk=CHUNK)


def _reference():
    name = "lfm2_moe_reference_under_test"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            ROOT, "benchmarks", "configs", "lfm2_moe_reference.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def seeded(dims=DIMS, seed=0, dtype=None):
    """The model with every leaf drawn from ``seed`` (matrices normal
    0.08, the taps normal 0.5, gains and the router's bias 1 + / 0 +
    normal 0.1), and ``get(names)`` that hands the same leaves to the
    reference."""
    model = Lfm2MoeModel(dims)
    model.eval()
    leaves = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v = jax.random.normal(key, tuple(p.shape), jnp.float32)
        if name.endswith("gate_bias"):
            v = 0.1 * v
        elif name.endswith("conv_weight"):
            v = 0.5 * v
        else:
            v = 1.0 + 0.1 * v if len(p.shape) == 1 else 0.08 * v
        p.set_value(v)
        leaves[name] = v
    if dtype is not None:
        model.to(dtype=dtype)
    return model, (lambda names: {n: leaves[n] for n in names})


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, DIMS["vocab_size"], n).tolist()


def best_of_the_reference(get, prompt, out):
    """Every served token is the reference's best for its position."""
    lg = np.asarray(_reference().logits(
        get, DIMS, np.asarray([list(prompt) + list(out)])))[0]
    return all(lg[len(prompt) - 1 + i].max() - lg[len(prompt) - 1 + i][t]
               < TOL for i, t in enumerate(out))


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_moe_reference.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
    assert 'HIGHEST = jax.lax.Precision.HIGHEST' in src


def test_forward_against_the_reference():
    """48 positions through both kinds of layer, the dense first layer
    and the routed ones: logits to 1e-4."""
    model, get = seeded()
    ids = np.asarray([tokens(48, seed=1), tokens(48, seed=2)])
    got = np.asarray(model(jnp.asarray(ids))._data)
    want = np.asarray(_reference().logits(get, DIMS, ids))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_the_conv_operator_is_no_small_thing_beside_the_residual():
    """What the cell's seeded weights are held to at their size
    (``assumed.weights``): the conv operators' outputs are of the
    residual's order here, so a wrong state shows in the logits."""
    model, _ = seeded()
    x = model.embed._data[jnp.asarray([tokens(40, seed=3)])]
    blk = model.blocks[0]
    out = blk.conv(blk.operator_norm(x))
    assert float(jnp.sqrt(jnp.mean(out ** 2))) \
        > 0.05 * float(jnp.sqrt(jnp.mean(x ** 2)))


# -- the chunk program and the decode step over pools and tails -----------

NB = 2 * (64 // BS) + 2
SCRATCH = 0


def fresh_pools(model):
    """(row pools, tail pools) whose unwritten rows and tails hold a
    large number: one the programs let through shows."""
    cfg = model.config
    width = 2 * cfg["num_key_value_heads"] * (
        cfg["hidden_size"] // cfg["num_attention_heads"])
    return ([jnp.full((NB, BS, width), 1e4, jnp.float32)
             for _ in range(model.layers_of(KINDS[1]))],
            [jnp.full((NB, model.layers_of(KINDS[0]) * 2
                       * cfg["hidden_size"]), 1e4, jnp.float32)])


def slot_table(first=2, step=2):
    """A slot's blocks scattered over the pool."""
    return jnp.asarray(first + step * np.arange(64 // BS), jnp.int32)


def prefill(model, pools, tails, table, seq, start, end, between=None):
    """Positions ``[start, end)`` of ``seq`` through the chunk program,
    ``CHUNK`` at a time; ``between(pools, tails, p0)`` runs before each
    chunk but the first.  Returns (last logits, pools, tails,
    counters)."""
    stats = []
    for p0 in range(start, end, CHUNK):
        if between is not None and p0 > start:
            pools, tails = between(pools, tails, p0)
        part = seq[p0:min(p0 + CHUNK, end)]
        ids = np.zeros((1, CHUNK), np.int32)
        ids[0, :len(part)] = part
        last, pools, tails, st = model._chunk_prefill_tick_paged(
            jnp.asarray(ids), pools, tails, table, p0, len(part), SCRATCH)
        stats.append(np.asarray(st))
    return np.asarray(last[0]), pools, tails, stats


def decode(model, pools, tails, table, tok, p, rem=5):
    """One decode step of a lane at position ``p`` beside a parked
    one; returns (logits of the live lane, pools, tails)."""
    tables = jnp.stack([table, jnp.full_like(table, SCRATCH)])
    z = jnp.zeros((2,), jnp.int32)
    out = model._fused_decode_tick_slots(
        jnp.asarray([[tok], [0]], jnp.int32), pools, tails, tables,
        jnp.asarray([p, 0], jnp.int32), jnp.zeros((2,), jnp.float32), z,
        jnp.ones((2,), jnp.float32), z.astype(jnp.uint32),
        z.astype(jnp.uint32), z, z - 1, jnp.asarray([rem, 0], jnp.int32))
    return out[-1], out[6], out[7]


def step_logits(model, pools, tails, table, tok, p):
    """The live lane's logits of one decode step (the step program
    returns ids: the blocks are run again as it runs them)."""
    tables = jnp.stack([table, jnp.full_like(table, SCRATCH)])
    pos = jnp.asarray([p, 0], jnp.int32)
    live = jnp.asarray([True, False])
    x = model.embed._data[jnp.asarray([tok, 0])][:, None, :]
    held = {KINDS[0]: model._states(
        tails[0], tables[jnp.arange(2), jnp.maximum(pos - 1, 0) // BS],
        pos), KINDS[1]: list(pools)}
    for blk, j in zip(model.blocks, model._pool_of):
        x, _, held[blk.kind][j] = blk.step_slots_paged(
            x, held[blk.kind][j], tables, pos, live)
    return np.asarray(model._head(x)[0, 0])


def paged_logits(model, seq, n_prompt, between=None):
    """Logits of the positions from ``n_prompt - 1`` on: the prompt
    through the chunk program, the rest a token at a time."""
    pools, tails = fresh_pools(model)
    table = slot_table()
    last, pools, tails, _ = prefill(model, pools, tails, table, seq, 0,
                                    n_prompt, between)
    rows = [last]
    for p in range(n_prompt, len(seq)):
        rows.append(step_logits(model, pools, tails, table, seq[p], p))
        _, pools, tails = decode(model, pools, tails, table, seq[p], p)
    return np.stack(rows)


@pytest.mark.parametrize("n_prompt", [21, 24, 1, 2, 8],
                         ids=["ends_inside_a_block", "ends_on_a_block_s_"
                              "last_row", "one_token", "two_tokens",
                              "one_chunk"])
def test_prefill_then_decode_through_rows_and_tails(n_prompt):
    """Prefill in chunks and then decode, through paged pools whose
    unwritten rows and tails would show, against the reference's full
    forward over the whole sequence (40+ positions)."""
    model, get = seeded(seed=3)
    seq = tokens(max(n_prompt + 9, 41), seed=n_prompt)
    got = paged_logits(model, seq, n_prompt)
    want = np.asarray(_reference().logits(
        get, DIMS, np.asarray([seq])))[0, n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bfloat16_in_place_of_float32_does_not_pass():
    """The same comparison with the program's leaves and arithmetic in
    bfloat16 is outside the tolerance: 1e-4 holds float32 and nothing
    coarser."""
    model, get = seeded(seed=3, dtype="bfloat16")
    ids = np.asarray([tokens(48, seed=1)])
    got = np.asarray(model(jnp.asarray(ids))._data, np.float32)
    want = np.asarray(_reference().logits(get, DIMS, ids))
    assert np.abs(got - want).max() > 10 * TOL


@pytest.mark.parametrize("tails_kept", [True, False],
                         ids=["adopted_tails", "tails_zeroed"])
def test_a_request_that_adopts_blocks_continues_from_their_tails(
        tails_kept):
    """A second request shares 16 tokens (4 whole blocks) with the
    first and adopts its blocks: its chunk programs start at position
    16 from the adopted fourth block's tail and give the logits of a
    cold run; with the adopted blocks' tails zeroed they do NOT."""
    model, get = seeded(seed=4)
    first = tokens(27, seed=5)
    second = first[:16] + tokens(13, seed=6)
    pools, tails = fresh_pools(model)
    _, pools, tails, _ = prefill(model, pools, tails, slot_table(), first,
                                 0, len(first))
    adopted = np.asarray(slot_table())[:4]
    table = jnp.asarray(np.concatenate(
        [adopted, 3 + 2 * np.arange(12)]), jnp.int32)
    if not tails_kept:
        tails = [t.at[adopted].set(0.0) for t in tails]
    got, _, _, stats = prefill(model, pools, tails, table, second, 16,
                               len(second))
    cold = paged_logits(model, second, len(second))[0]
    want = np.asarray(_reference().logits(
        get, DIMS, np.asarray([second])))[0, -1]
    np.testing.assert_allclose(cold, want, atol=TOL, rtol=0)
    if tails_kept:
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    else:
        assert np.abs(got - want).max() > 100 * TOL
    # every chunk program started from a tail
    assert [int(s[6]) for s in stats] == [1] * len(stats)
    assert [int(s[7]) for s in stats] == [0] * len(stats)


def test_the_discarded_step_of_a_prefilling_lane_changes_nothing():
    """A lane that is still prefilling takes a decode step in every
    tick at its next chunk's first row, with budget and some token:
    the row and the tail it writes lie in the block the next chunk
    rewrites, so the prompt's logits and every later step's are the
    same numbers."""
    model, _ = seeded(seed=7)
    seq = tokens(33, seed=8)

    def discarded(pools, tails, p0):
        return decode(model, pools, tails, slot_table(), 77, p0)[1:]
    np.testing.assert_array_equal(
        paged_logits(model, seq, 27, between=discarded),
        paged_logits(model, seq, 27))


def test_a_parked_lane_writes_the_scratch_block_only():
    model, _ = seeded(seed=9)
    pools, tails = fresh_pools(model)
    table = slot_table()
    seq = tokens(11, seed=10)
    _, pools, tails, _ = prefill(model, pools, tails, table, seq, 0, 10)
    stats, new_pools, new_tails = decode(model, pools, tails, table,
                                         seq[10], 10)
    # one live lane through 7 conv and 2 attention layers at row 10
    assert list(np.asarray(stats)[4:]) == [7, 0, 0, 0, 2 * 11, 0]
    mine = int(table[10 // BS])
    for before, after in zip(pools + tails, new_pools + new_tails):
        changed = {int(b) for b in np.nonzero(np.any(
            np.asarray(before != after).reshape(NB, -1), axis=1))[0]}
        assert changed == {SCRATCH, mine}


def test_the_counters_of_a_chunk_and_of_a_step():
    """``LFM2_COUNTERS`` behind the routed layers' four: a chunk of 8
    at position 16 of a 21-token prompt, then a step at 23 (a block's
    last row)."""
    model, _ = seeded(seed=11)
    pools, tails = fresh_pools(model)
    seq = tokens(24, seed=12)
    _, pools, tails, stats = prefill(model, pools, tails, slot_table(),
                                     seq, 0, 21)
    first, last = stats[0], stats[-1]
    # 7 conv layers, 2 attention layers
    assert list(first[4:]) == [7 * 8, 7 * 2, 0, 1, 0, 2 * 8]
    assert list(last[4:]) == [7 * 5, 7 * 1, 1, 0, 0, 2 * 21]
    spec = model.serving_spec()
    assert [n for n, _ in spec.counters][4:] == [
        "conv_positions", "conv_tails_final", "conv_starts_from_tail",
        "conv_starts_from_zero", "attn_rows_seen", "attn_rows_seen_chunk"]


# -- through the engine ----------------------------------------------------

def test_served_through_the_engine_and_healthz_names_the_state():
    """Prompts of several lengths at once through ``Engine`` behind
    ``EngineServer`` (the long ones prefill in chunks beside the
    others' decode steps): every served token is the reference's best;
    ``/healthz`` names the state and the tail row; the counters
    count."""
    model, get = seeded(seed=13)
    eng = Engine(model, registry=monitor.StatRegistry(), **ENGINE)
    prompts = [tokens(n, seed=n) for n in (37, 3, 24, 50, 1)]
    with EngineServer(eng, port=0) as srv:
        reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
        for r in reqs:
            r.result(timeout=300)
        outs = [[int(t) for t in r.generated] for r in reqs]
        with urllib.request.urlopen(srv.address + "/healthz") as resp:
            health = json.loads(resp.read())
    for p, out in zip(prompts, outs):
        assert len(out) == 7 and best_of_the_reference(get, p, out)
    assert health["layer_state"] == {"conv": [2, 64],
                                     "layers": {"conv": 7,
                                                "attention": 2},
                                     "per": "block"}
    assert health["kv_geometry"]["rows"] == [["kv", [64]]]
    assert health["kv_geometry"]["block_rows"] == [["conv", 7 * 128]]
    assert health["kv_geometry"]["n_layers"] == 2
    # 2 layers of 64 numbers a position; a block's 4 rows and 7 tails
    assert health["kv_row_bytes"] == 2 * 64 * 4
    assert health["kv_block_bytes"] == (BS * 2 * 64 + 7 * 128) * 4
    reg = eng.registry
    assert reg.get("serving.conv_positions").value >= 7 * (
        sum(len(p) for p in prompts) + 6 * len(prompts))
    assert reg.get("serving.conv_starts_from_zero").value == 5
    assert reg.get("serving.conv_starts_from_tail").value \
        == sum(-(-len(p) // CHUNK) - 1 for p in prompts)
    assert reg.get("serving.conv_tails_final").value > 0
    assert reg.get("serving.attn_rows_seen").value > 0
    assert reg.get("serving.compiles_total").value <= 2


def test_a_prefix_hit_continues_from_the_adopted_tail():
    """The second request shares 21 tokens with the first: the engine
    adopts its 5 whole blocks and the chunk program continues from the
    fifth block's tail; the tokens are those of an engine without a
    prefix cache and the reference's best."""
    model, get = seeded(seed=14)
    shared = tokens(21, seed=15)
    prompts = [shared + tokens(6, seed=16), shared + tokens(9, seed=17)]
    outs = {}
    for cache in (True, False):
        eng = Engine(model, registry=monitor.StatRegistry(),
                     prefix_cache=cache, **ENGINE)
        outs[cache] = []
        for p in prompts:
            req = eng.submit(p, max_new_tokens=6)
            eng.run_until_idle()
            outs[cache].append(list(req.generated))
        hits = eng.registry.get("serving.prefix_hit_tokens").value
        assert hits == (20 if cache else 0)
    assert outs[True] == outs[False]
    assert all(best_of_the_reference(get, p, o)
               for p, o in zip(prompts, outs[True]))


def test_a_preempted_request_resumes_to_the_same_tokens():
    """One slot: a high-priority arrival evicts the running stream
    mid-decode; its whole blocks (rows and tails) enter the prefix
    cache, the resume adopts them and continues from the last one's
    tail to the tokens of an uninterrupted run."""
    model, get = seeded(seed=18)
    p_low, p_high = tokens(14, seed=19), tokens(9, seed=20)

    def run(interrupt):
        eng = Engine(model, registry=monitor.StatRegistry(),
                     **dict(ENGINE, num_slots=1))
        low = eng.submit(p_low, max_new_tokens=12, priority=0)
        high = None
        if interrupt:
            for _ in range(8):
                eng.step()
            assert not low.done()
            high = eng.submit(p_high, max_new_tokens=4, priority=5)
        eng.run_until_idle()
        return (list(low.generated), low.preemptions,
                high and list(high.generated), eng)

    plain, n0, _, _ = run(False)
    resumed, n1, out_high, eng = run(True)
    assert n0 == 0 and n1 >= 1
    assert resumed == plain and best_of_the_reference(get, p_low, plain)
    assert best_of_the_reference(get, p_high, out_high)
    assert eng.registry.get("serving.prefix_hit_tokens").value >= 2 * BS


@pytest.mark.parametrize("options, named", [
    (dict(kv_block_size=None), "kv_block_size=None"),
    (dict(prefill_chunk=None), "prefill_chunk=None"),
    (dict(attn_impl="ragged"), "attn_impl='ragged'"),
    (dict(spec_k=2), "spec_k"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(mesh=1), "mesh="),
    (dict(max_adapters=2), "adapters"),
    (dict(kv_host_mb=1.0), "kv_host_mb"),
])
def test_the_engine_refuses_by_name_what_the_model_cannot_honour(
        options, named):
    model = Lfm2MoeModel(DIMS)
    with pytest.raises(ValueError) as err:
        Engine(model, **{**ENGINE, **options})
    assert named in str(err.value) and "lacks" in str(err.value)
    assert "Lfm2MoeModel" in str(err.value)


def test_migration_and_a_chunk_inside_a_block_are_refused():
    model = Lfm2MoeModel(DIMS)
    assert set(model.serving_spec().unsupported) <= set(Engine._REFUSABLE)
    eng = Engine(model, **ENGINE)
    for call in (lambda: eng.migrate_out(wait=False),
                 lambda: eng.export_prefix([1, 2, 3], wait=False)):
        with pytest.raises(ValueError, match="KV migration"):
            call()
    with pytest.raises(ValueError, match="multiple of kv_block_size"):
        Engine(model, **dict(ENGINE, kv_block_size=16, prefill_chunk=8))


@pytest.mark.parametrize("feature", Engine._ROWS_ONLY)
def test_a_model_with_tails_has_to_refuse_what_pairs_the_pools(feature):
    """The engine's second list of pools holds this model's tails, and
    these features' code pairs it with the first a layer at a time as K
    with V: a ``ServingSpec`` with per-block rows that does not name
    one of them under ``unsupported`` is refused at construction,
    whether or not the option is in use."""
    model = Lfm2MoeModel(DIMS)
    spec = model.serving_spec()
    del spec.unsupported[feature]
    model.serving_spec = lambda: spec
    with pytest.raises(ValueError, match="per-block rows") as err:
        Engine(model, **ENGINE)
    assert feature in str(err.value)


def test_what_is_not_written_is_refused_by_name():
    with pytest.raises(ValueError, match="conv_bias"):
        Lfm2MoeModel(dict(DIMS, conv_bias=True))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeModel(dict(DIMS, layer_types=DIMS["layer_types"][:3]))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeModel(dict(DIMS, num_hidden_layers=2,
                          layer_types=["conv", "conv"]))
    with pytest.raises(ValueError, match="conv_L_cache"):
        ShortConv(64, 1)


@pytest.mark.parametrize("shape, tiles", [
    # a decode step's 256 pair rows (64 slots x 4) and a chunk's 1,024
    # through [2,048 -> 3,584] and [1,792 -> 2,048] over 32 experts:
    # the widest weight tile that fits fast memory is 2,048 x 1,536, so
    # n goes in tiles of 896, and at 256 rows a row tile of 128 wins
    # (``_gmm_tiling``'s docstring, PR 46) ...
    ((256, 2048, 3584), (128, 2048, 896)),
    ((1024, 2048, 3584), (128, 2048, 896)),
    ((256, 1792, 2048), (128, 1792, 1024)),
    ((1024, 1792, 2048), (128, 1792, 1024)),
    # ... and the other routed configurations' as they were
    ((128, 3072, 6144), (128, 3072, 768)),
    ((192, 2048, 2816), (192, 2048, 1408)),
    ((1024, 2048, 1536), (128, 2048, 1536)),
    ((128, 3584, 2048), (128, 512, 2048)),
    ((1024, 1024, 3584), (128, 1024, 1792)),
])
def test_the_tiles_of_the_grouped_products(shape, tiles):
    from paddle_tpu.distributed import moe
    assert moe._gmm_tiling(*shape) == tiles
    tm, tk, tn = tiles
    assert tk * tn <= moe._GMM_WEIGHT_TILE or tk > 2048
