"""The decoder of sliding-window and full-attention layers
(models/afmoe.py) against its plain reference
(benchmarks/configs/afmoe_reference.py), at a tiny size on the CPU with
seeded float32 weights: both kinds of layer behind ONE block table a
slot, each walked to its own reach (``models/programs.py``
``walk_plan``), the gate on the attention's output, the four norms, and
one chip's share of the routed experts (``mla_moe.RoutedFFN`` ``held``,
``distributed/moe.py``)."""
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
from paddle_tpu.models.afmoe import AfmoeModel, GatedGQAttention
from paddle_tpu.models.mla_moe import RoutedFFN
from paddle_tpu.models.programs import (
    walk_chunk, walk_group, walk_plan, walk_rows)
from paddle_tpu.serving import Engine, EngineServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU: the program and the reference order their sums
# differently (a walk over cached chunks with a running maximum against
# one softmax over masked blocks, sorted pairs against a loop over
# experts); the largest difference in logits of magnitude ~1 measured
# over these cases is 2e-6
TOL = 1e-4
W = 8
DIMS = dict(
    vocab_size=128, max_position_embeddings=1024, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=4,
    num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2,
    num_shared_experts=1, route_norm=True, route_scale=2.448,
    score_func="sigmoid", n_group=1, topk_group=1, mup_enabled=True,
    sliding_window=W, rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None,
    layer_types=["sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention"])


def one_layer(kind):
    return dict(DIMS, num_hidden_layers=1, layer_types=[kind])


def _reference():
    name = "afmoe_reference_under_test"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            ROOT, "benchmarks", "configs", "afmoe_reference.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def seeded(dims=DIMS, seed=0, **share):
    """The model with every leaf drawn from ``seed`` (matrices normal
    0.08, gains and the router's bias 1 + / 0 + normal 0.1), and
    ``get(names)`` that hands the same leaves to the reference."""
    model = AfmoeModel(dims, **share)
    model.eval()
    leaves = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v = jax.random.normal(key, tuple(p.shape), jnp.float32)
        if name.endswith("gate_bias"):
            v = 0.1 * v
        else:
            v = 1.0 + 0.1 * v if len(p.shape) == 1 else 0.08 * v
        p.set_value(v)
        leaves[name] = v
    return model, (lambda names: {n: leaves[n] for n in names})


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, DIMS["vocab_size"], n).tolist()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "afmoe_reference.py")) as f:
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


def paged_logits(model, seq, n_prompt, chunk, bs=8, L=64):
    """Logits [len(seq) - n_prompt + 1, V] of the positions from
    ``n_prompt - 1`` on: the first ``n_prompt`` tokens through the
    chunk program ``chunk`` at a time into paged pools (the blocks of
    the slot scattered over the pool), the rest a token at a time
    through the decode step's blocks and head."""
    cfg = model.config
    nb = L // bs
    shape = (2 * nb + 2, bs,
             2 * cfg["num_key_value_heads"] * cfg["head_dim"])
    # rows nobody wrote hold a large number: a row the mask lets
    # through shows
    pools = [jnp.full(shape, 1e4, jnp.float32) for _ in model.blocks]
    table = jnp.asarray(2 + 2 * np.arange(nb), jnp.int32)
    rows = []
    for p0 in range(0, n_prompt, chunk):
        part = seq[p0:min(p0 + chunk, n_prompt)]
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :len(part)] = part
        last, pools, _, _ = model._chunk_prefill_tick_paged(
            jnp.asarray(ids), pools, table, p0, len(part), 0)
    rows.append(np.asarray(last[0]))
    tables = jnp.stack([table, jnp.zeros_like(table)])
    for p in range(n_prompt, len(seq)):
        pos = jnp.asarray([p, 0], jnp.int32)
        live = jnp.asarray([True, False])
        x = model._embed(jnp.asarray([seq[p], 0]))[:, None, :]
        for j, blk in enumerate(model.blocks):
            x, _, pools[j] = blk.step_slots_paged(x, pools[j], tables,
                                                  pos, live)
        rows.append(np.asarray(model._head(x)[0, 0]))
    return np.stack(rows)


@pytest.mark.parametrize("n_prompt, chunk", [
    (29, 12),     # chunks that start inside a block of 8, each
                  # straddling the window's edge (W = 8 < 12)
    (40, 16),     # whole blocks
    (5, 16),      # a prompt inside the window
])
def test_prefill_then_decode_through_the_paged_cache(n_prompt, chunk):
    """Prefill in chunks and then decode, through paged pools whose
    unwritten rows would show, against the reference's full forward
    over the whole sequence."""
    model, get = seeded(seed=3)
    seq = tokens(n_prompt + 9, seed=n_prompt)
    got = paged_logits(model, seq, n_prompt, chunk)
    want = np.asarray(_reference().logits(
        get, DIMS, np.asarray([seq])))[0, n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("back, seen", [(W, False), (W - 1, True)])
def test_the_window_holds_w_keys_the_query_s_own_among_them(back, seen):
    """One sliding layer: the logits at position i depend on the token
    ``back`` positions before it iff ``back < W``, through the chunk
    program and the decode step alike."""
    model, _ = seeded(one_layer("sliding_attention"), seed=4)
    seq, n_prompt = tokens(30, seed=5), 21
    base = paged_logits(model, seq, n_prompt, 12)
    for i in (n_prompt - 1, 27):        # a prefilled and a decoded row
        other = list(seq)
        other[i - back] = (seq[i - back] + 1) % 127 + 1
        got = paged_logits(model, other, n_prompt, 12)[i - n_prompt + 1]
        same = np.array_equal(got, base[i - n_prompt + 1])
        assert same != seen, (i, back)


@pytest.mark.parametrize("kind, moves", [("sliding_attention", True),
                                         ("full_attention", False)])
def test_only_a_sliding_layer_knows_positions(kind, moves):
    """The same rows placed 37 positions later: a full layer's output
    is the same numbers, a sliding layer's is not."""
    model, _ = seeded(one_layer(kind), seed=6)
    attn = model.blocks[0].attn
    assert isinstance(attn, GatedGQAttention)
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 12, 64))
    pool = jnp.zeros((3, 8, 64))
    tables = jnp.zeros((1, 2), jnp.int32)

    def out(first):
        q, k, v = attn.project(h, first + jnp.arange(12)[None, :])
        return np.asarray(attn.attend(
            q, attn.cache_rows(k, v), pool, tables,
            jnp.zeros((1,), jnp.int32)))
    assert np.array_equal(out(0), out(37)) != moves


# -- the walks: a first chunk as well as a last one -----------------------

@pytest.mark.parametrize("lanes, S, L, bs, width, reach", [
    ([0, 37, 257, 767, 1500, 2047], 1, 2048, 8, None, 300),
    ([5] * 32, 1, 2048, 16, 2048, 512),
    ([2047] * 32, 1, 2048, 16, 2048, 512),
    ([900] * 16 + [0] * 16, 1, 2048, 16, 2048, 4096),   # wider than any
    ([700, 40, 0, 1030, 2000] + [0] * 27, 0, 2048, 16, 2048, 256),
    ([1, 255, 256, 548, 1800], 4, 2048, 8, None, 257),
    ([0, 0, 0, 0], 1, 2048, 8, None, 100),
], ids=["mixed", "short", "full", "reach_past_row_0", "rows_below_pos",
        "several_rows", "all_parked"])
def test_walk_plan_with_a_reach_and_its_host_twin(lanes, S, L, bs, width,
                                                  reach):
    """``walk_plan`` with a reach gives slot b the chunks that hold a
    row some query of its window sees (rows ``pos - reach + 1 .. pos +
    S - 1``), each once and in order, in a list as much shorter; and
    ``walk_rows`` counts the same trips on the host."""
    chunk, group = walk_chunk(L, bs), walk_group(len(lanes), width)
    slot_of, chunk_of, valid, n_trips = (np.asarray(a) for a in walk_plan(
        jnp.asarray(lanes, jnp.int32), S, L, chunk, group, reach))
    want = []
    for b, p in enumerate(lanes):
        if p:
            lo, hi = max(p + 1 - reach, 0), min(p + S, L)   # rows [lo, hi)
            want += [(b, c) for c in range(lo // chunk,
                                           -(-hi // chunk))]
    assert list(zip(slot_of[valid], chunk_of[valid])) == want
    assert int(n_trips) == -(-len(want) // group)
    most = min(-(-L // chunk), (reach + S - 2) // chunk + 2)
    assert len(valid) == -(-len(lanes) * most // group) * group
    assert walk_rows(np.asarray(lanes), S, L, bs, width, reach) \
        == int(n_trips) * group * chunk
    # one slot walks as the chunk program does: from the first chunk
    # its first query sees to the end of its window
    p = lanes[-1]
    alone = walk_rows(np.asarray([p]), S, L, bs, width, reach)
    assert alone == (-(-min(max(p + S, 1), L) // chunk)
                     - min(max(p + 1 - reach, 0) // chunk,
                           -(-min(max(p + S, 1), L) // chunk))) * chunk


def test_a_sliding_layer_s_walk_is_short_and_the_counters_say_so():
    """Lanes deep in a table of four chunks, one request at a time (a
    lane that is still prefilling takes a discarded decode step beside
    the others, which would count too): the full layer's decode walk
    fetches every chunk below the lane, the sliding layers' only the
    one that holds the window; ``attn_rows_seen`` is ``min(p + 1, W)``
    a sliding layer and ``p + 1`` a full one."""
    model, _ = seeded(seed=8)
    eng = Engine(model, num_slots=3, max_seq_len=1024, kv_block_size=8,
                 kv_blocks=400, prefill_chunk=16,
                 registry=monitor.StatRegistry())
    reg = eng.registry
    names = ("attn_rows_seen", "attn_rows_seen_chunk",
             "attn_rows_walked_sliding", "attn_rows_walked_full")
    for n in (300, 530, 790):
        before = [reg.get("serving." + k).value for k in names]
        req = eng.submit(tokens(n, seed=n), max_new_tokens=3)
        eng.run_until_idle()
        assert len(req.generated) == 3
        seen, seen_chunk, sliding, full = (
            reg.get("serving." + k).value - v
            for k, v in zip(names, before))
        # token 0 comes from the prefill; two steps, at n and n + 1
        assert seen == sum(3 * min(p + 1, W) + p + 1 for p in (n, n + 1))
        starts = range(0, n, 16)
        assert seen_chunk == sum(
            3 * (min(p0 + 1, W) + min(16, n - p0) - 1) + p0 + min(16, n - p0)
            for p0 in starts)
        # a decode trip is 3 items of 256 rows (the lane's and two of
        # padding); the chunk program walks whole chunks of its own
        assert sliding == 3 * 256 * (2 * 3 + sum(1 for p0 in starts if p0))
        assert full == 256 * (sum(-(-(-(-p // 256)) // 3) * 3
                                  for p in (n, n + 1))
                              + sum(-(-p0 // 256) for p0 in starts))
    # the host twin: the mean over the four layers
    assert model.decode_rows(np.asarray([300, 530, 790]), 1, 1024, 8) \
        == (3 * 3 * 256 + -(-(2 + 3 + 4) // 3) * 3 * 256) // 4


# -- one chip's share of the experts --------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts top 2 and a shared one: the held parts of all 8 shares
    of a layer (one expert each, the same router), with the shared
    expert counted once, equal the reference's uncut layer; each
    share's own output equals the reference's for that share; and the
    pairs computed and fallen elsewhere are all the pairs."""
    ref = _reference()
    whole, _ = seeded(seed=9)
    ffn = whole.blocks[1].ffn
    w = {"ffn." + n: p._data for n, p in ffn.named_parameters()}
    x = jax.random.normal(jax.random.PRNGKey(10), (40, 64))
    live = jnp.arange(40) < 37
    want = np.asarray(ref.routed(w, x, DIMS, "highest"))
    shared = np.asarray(ffn.shared_expert(x))
    total = shared.copy()
    for first in range(8):
        share = RoutedFFN(64, 32, 8, 2, 1, 2.448, True, held=(first, 1))
        for name, p in share.named_parameters():
            v = w["ffn." + name]
            p.set_value(v[first:first + 1] if name.startswith("experts_")
                        else v)
        y, stats = share(x, live)
        assert stats.shape == (4,)
        assert int(stats[0]) + int(stats[3]) == 37 * 2
        total += np.asarray(y) - shared
        dims = dict(DIMS, num_experts=1, share={"experts_first": first})
        cut = {**w, "ffn.experts_in": w["ffn.experts_in"][first:first + 1],
               "ffn.experts_out": w["ffn.experts_out"][first:first + 1]}
        np.testing.assert_allclose(
            np.asarray(y)[:37],
            np.asarray(ref.routed(cut, x, dims, "highest"))[:37],
            atol=1e-5, rtol=0)
    np.testing.assert_allclose(total[:37], want[:37], atol=1e-5, rtol=0)
    # a dead row adds nothing but the shared expert
    np.testing.assert_allclose(total[37:], shared[37:], atol=1e-6)


def test_a_share_is_served_and_healthz_says_what_is_held():
    """Experts 2-5 of 8 through ``Engine`` behind ``EngineServer``:
    every served token is the best of the reference given the same
    share, ``/healthz`` names the window, the kinds and the share, and
    pairs fall elsewhere."""
    dims = dict(DIMS, num_experts=4)
    share = {"experts_first": 2, "experts_of": 8}
    model, get = seeded(dims, seed=11, **share)
    assert model.blocks[1].ffn.experts_in.shape == [4, 64, 64]
    assert model.blocks[1].ffn.gate_weight.shape == [64, 8]
    eng = Engine(model, num_slots=4, max_seq_len=128, kv_block_size=8,
                 kv_blocks=72, prefill_chunk=16,
                 registry=monitor.StatRegistry())
    prompts = [tokens(n, seed=n) for n in (70, 3, 33, 100)]
    with EngineServer(eng, port=0) as srv:
        outs = []
        for p in prompts:
            req = urllib.request.Request(
                srv.address + "/generate",
                data=json.dumps({"prompt": p,
                                 "max_new_tokens": 7}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                outs.append(json.loads(resp.read())["generated"])
        with urllib.request.urlopen(srv.address + "/healthz") as resp:
            health = json.loads(resp.read())
    assert health["attention"] == {"window": W,
                                   "layers": {"sliding": 3, "full": 1}}
    assert health["experts"] == {"held": [2, 4], "of": 8}
    assert health["kv_row_bytes"] == 4 * 64 * 4
    assert health["kv_geometry"]["rows"] == [["kv", [64]]]
    ref_dims = dict(dims, share=share)
    for p, out in zip(prompts, outs):
        assert len(out) == 7
        lg = np.asarray(_reference().logits(
            get, ref_dims, np.asarray([p + out])))[0]
        for i, tok in enumerate(out):
            row = lg[len(p) - 1 + i]
            assert row.max() - row[tok] < TOL
    pairs = eng.registry.get("serving.moe_routed_pairs").value
    away = eng.registry.get("serving.moe_pairs_elsewhere").value
    assert pairs > 0 and away > 0
    # every live row of the three routed layers brings two pairs
    assert (pairs + away) % (3 * 2) == 0
    assert eng.registry.get("serving.compiles_total").value <= 2


@pytest.mark.parametrize("shape, tiles", [
    # a decode step's 128 pair rows and a chunk's 1,024 through
    # [3,072 -> 6,144] and [3,072 -> 3,072] over the 32 held experts
    # (chip run, PR 44: ``_gmm_tiling``'s docstring) ...
    ((128, 3072, 6144), (128, 3072, 768)),
    ((128, 3072, 3072), (128, 3072, 768)),
    ((1024, 3072, 6144), (128, 3072, 768)),
    ((1024, 3072, 3072), (128, 3072, 768)),
    # ... and the other routed configurations' as they were
    ((192, 2048, 2816), (192, 2048, 1408)),
    ((1536, 1408, 2048), (128, 1408, 1024)),
    ((1024, 2048, 1536), (128, 2048, 1536)),
    ((1024, 768, 2048), (128, 768, 2048)),
    ((128, 3584, 2048), (128, 512, 2048)),
    ((1024, 1024, 3584), (128, 1024, 1792)),
])
def test_the_tiles_of_the_grouped_products(shape, tiles):
    from paddle_tpu.distributed import moe
    assert moe._gmm_tiling(*shape) == tiles


def test_what_is_not_written_is_refused_by_name():
    with pytest.raises(ValueError, match="n_group"):
        AfmoeModel(dict(DIMS, n_group=2))
    with pytest.raises(ValueError, match="layer_types"):
        AfmoeModel(dict(DIMS, layer_types=DIMS["layer_types"][:3]))
    with pytest.raises(ValueError, match="held"):
        RoutedFFN(64, 32, 8, 2, 1, 1.0, held=(6, 4))
    whole = AfmoeModel(DIMS)
    spec = whole.serving_spec()
    assert whole.held is None and spec.experts == {"held": [0, 8], "of": 8}
    with pytest.raises(ValueError, match="kv_int8|QuantKV"):
        Engine(whole, num_slots=2, max_seq_len=64, kv_block_size=8,
               kv_blocks=20, prefill_chunk=16, kv_dtype="int8",
               registry=monitor.StatRegistry())
