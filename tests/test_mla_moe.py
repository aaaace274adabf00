"""The latent-attention / routed-experts decoder (models/mla_moe.py)
against its plain reference (benchmarks/configs/mla_moe_reference.py),
at a tiny size on the CPU with seeded float32 weights; the engine/model
seam (models/programs.py) with both models behind it; ``nn.LazyGuard``;
``kvcache.KVRowSpec``."""
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn
from paddle_tpu.distributed import moe
from paddle_tpu.models import GPTModel, mla_moe, programs
from paddle_tpu.models.mla_moe import MLAMoEModel
from paddle_tpu.serving import Engine
from paddle_tpu.serving.kvcache import KVRowSpec, per_shard_block_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU: the program and the reference order their sums
# differently (absorbed against expanded attention, sorted pairs against
# a loop over experts); the largest difference in logits of magnitude
# ~1 measured over these cases is 6e-7
TOL = 1e-4

DIMS = dict(
    vocab_size=128, max_position_embeddings=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, n_shared_experts=2, n_routed_experts=8,
    routed_scaling_factor=2.446, kv_lora_rank=32, q_lora_rank=None,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    num_experts_per_tok=2, first_k_dense_replace=1, norm_topk_prob=True,
    rms_norm_eps=1e-5, rope_theta=800000)


def _reference():
    name = "mla_moe_reference_under_test"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmarks", "configs",
                               "mla_moe_reference.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def seeded(dims=DIMS, seed=0, bias=0.1):
    """The model with every leaf drawn from ``seed`` (matrices normal
    0.08, gains 1 + normal 0.1, the correction bias normal ``bias``),
    and ``get(names)`` that hands the same leaves to the reference."""
    model = MLAMoEModel(dims)
    model.eval()
    leaves = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v = jax.random.normal(key, tuple(p.shape), jnp.float32)
        if "gate_bias" in name:
            v = bias * v
        elif len(p.shape) == 1:
            v = 1.0 + 0.1 * v
        else:
            v = 0.08 * v
        p.set_value(v)
        leaves[name] = v
    return model, leaves


def getter(leaves, **replace):
    return lambda names: {n: replace.get(n, leaves[n]) for n in names}


def tokens(n, rows=1, seed=0):
    return np.random.default_rng(seed).integers(
        1, DIMS["vocab_size"], (rows, n))


def paged_logits(model, ids, chunk, n_decode, bs=8, nb=12):
    """Logits of positions ``len(ids) - n_decode - 1 ...`` through the
    paged latent cache: chunked prefill of the first tokens, then one
    decode step a token, each teacher-forced from ``ids``."""
    n = len(ids) - n_decode
    row = model.blocks[0].attn.row
    pools = [jnp.zeros((nb, bs, row), jnp.float32) for _ in model.blocks]
    table = jnp.arange(1, nb, dtype=jnp.int32)       # block 0: scratch
    out, p0 = [], 0
    while p0 < n:
        m = min(chunk, n - p0)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :m] = ids[p0:p0 + m]
        last, pools, _, stats = model._chunk_prefill_tick_paged(
            jnp.asarray(toks), pools, table, p0, m, 0)
        p0 += m
    out.append(last[0])
    for t in range(n, len(ids)):
        x = model.embed._data[jnp.asarray([[ids[t]]])]
        pos = jnp.asarray([t], jnp.int32)
        new = []
        for blk, pool in zip(model.blocks, pools):
            x, pool, _ = blk.decode_slots_paged(
                x, pool, table[None, :], pos, jnp.asarray([True]))
            new.append(pool)
        pools = new
        out.append(model._head(x)[0, -1])
    return np.asarray(jnp.stack(out)), stats


@pytest.mark.parametrize("n, chunk, n_decode", [
    (40, 16, 6),      # whole chunks and a tail, several blocks
    (23, 8, 3),       # a chunk that ends inside a block
    (9, 16, 5),       # one short chunk: the absorbed form (9 < 170)
    # 84 rows of a table of 88: the last chunk starts at 80 and its
    # pad lanes' block lies past the table's end
    (86, 16, 2),
    (30, 12, 4),      # chunks of 1.5 blocks: every other starts mid-block
    # ragged lengths side by side through the engine's decode program,
    # whose walk is the work list (items of 16 rows, see ``short_walk``)
    ((70, 3, 33, 17, 100), 16, 9),    # one long lane among short ones
    ((15, 16, 17, 31, 32, 33), 8, 6),  # lengths at an item's edge
    ((5, 90, 5, 60), 32, 12),         # 2 slots: lanes parked between
    # an engine whose chunk is no whole number of blocks: chunks start
    # mid-block, and the longest prompt's last one (108..112 of 120)
    # could touch a block past the table's end
    ((30, 7, 41, 113), 12, 5),
])
def test_paged_prefill_then_decode_equals_the_reference(
        n, chunk, n_decode, short_walk):
    model, leaves = seeded()
    if isinstance(n, tuple):
        # every served token is the reference's best token over what
        # came before it (teacher-forced, one forward a request)
        eng = Engine(model, num_slots=2 if len(n) == 4 else 4,
                     max_seq_len=128 - 128 % chunk, kv_block_size=8,
                     kv_blocks=72,
                     prefill_chunk=chunk, prefix_cache=False,
                     registry=monitor.StatRegistry())
        prompts = [tokens(k, seed=k)[0].tolist() for k in n]
        reqs = [eng.submit(p, max_new_tokens=n_decode) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            ids = np.asarray(list(r.result()))
            want = np.asarray(_reference().logits(
                getter(leaves), DIMS, ids[None]))[0]
            assert list(np.argmax(want, axis=-1)[len(p) - 1:-1]) \
                == list(ids[len(p):])
        # the work list engaged: fewer rows walked than 'all slots as
        # far as the longest' would read, never fewer than are live
        reg = eng.registry
        walked = reg.get("serving.decode_rows_walked").value
        assert reg.get("serving.decode_rows_live").value <= walked \
            < reg.get("serving.decode_rows_table").value
        return
    ids = tokens(n)[0]
    want = np.asarray(_reference().logits(getter(leaves), DIMS,
                                          ids[None]))[0]
    got, _ = paged_logits(model, ids, chunk, n_decode)
    assert np.abs(got - want[n - n_decode - 1:]).max() < TOL


def _scattered(pool, rows, table, pos, true_len, scratch):
    """What the chunk program did before PR 32, kept as the oracle:
    one scatter of the chunk's rows through the table, the pad lanes
    (``>= true_len``) into row 0 of the ``scratch`` block."""
    bs = pool.shape[1]
    at = pos + jnp.arange(rows.shape[0])
    valid = jnp.arange(rows.shape[0]) < true_len
    safe = jnp.where(valid, at, 0)
    return pool.at[jnp.where(valid, table[safe // bs], scratch),
                   jnp.where(valid, safe % bs, 0), :rows.shape[1]].set(rows)


@pytest.mark.parametrize("chunk, bs, owned, pos, true_len", [
    (32, 8, 12, 16, 32),     # a whole chunk at a block's edge
    (32, 8, 12, 16, 13),     # a last chunk: it ends inside a block
    (32, 8, 12, 64, 32),     # ... and ends with the table
    (32, 8, 12, 80, 9),      # blocks of pad lanes past the table's end
    (32, 8, 5, 24, 11),      # ... and past the slot's reservation
    (32, 8, 12, 19, 32),     # a chunk that starts inside a block
    (32, 8, 12, 91, 5),      # ... in the table's last block
    (12, 8, 12, 36, 12),     # a chunk of one and a half blocks
    (5, 8, 12, 17, 5),       # a chunk inside one block
    (16, 16, 6, 32, 1),      # one row
])
def test_a_chunk_writes_the_rows_the_scatter_wrote(chunk, bs, owned, pos,
                                                  true_len):
    """``write_chunk_rows`` against the scatter it replaced, bit for
    bit: every row of every block but ``scratch`` (the slot's rows
    below its new cursor among them), the pool's lane padding, and —
    where the scatter parked its pad lanes — the ``scratch`` block as
    it was.  The table is 12 blocks long; the slot owns the first
    ``owned`` and the rest are ``scratch``."""
    rng = np.random.default_rng(pos * 100 + true_len)
    pool = jnp.asarray(rng.normal(size=(40, bs, 24)), jnp.float32)
    table = np.zeros(12, np.int32)
    table[:owned] = rng.permutation(np.arange(1, 40))[:owned]
    rows = jnp.asarray(rng.normal(size=(chunk, 20)), jnp.float32)
    assert pos + true_len <= owned * bs
    args = (jnp.asarray(table), jnp.int32(pos), jnp.int32(true_len),
            jnp.int32(0))
    got = np.asarray(jax.jit(mla_moe.write_chunk_rows)(pool, rows, *args))
    want = np.asarray(_scattered(pool, rows, *args))
    assert np.array_equal(got[1:], want[1:])
    assert np.array_equal(got[0], np.asarray(pool)[0])
    # (what the oracle itself holds: the chunk's rows where the table
    # puts them, nothing else moved)
    flat = want[table].reshape(-1, 24)
    assert np.array_equal(flat[pos:pos + true_len, :20],
                          np.asarray(rows)[:true_len])
    moved = np.any(want != np.asarray(pool), axis=(1, 2))
    assert set(np.flatnonzero(moved)) <= set(
        table[pos // bs:(pos + true_len - 1) // bs + 1]) | {0}


def test_a_chunk_after_an_adopted_prefix_leaves_the_shared_blocks():
    """Two live requests over one cached prefix: the second adopts the
    first's whole blocks and its chunk starts at their end.  The
    shared blocks hold the same bytes before and after in every
    layer's pool, and both requests are served the reference's
    tokens."""
    model, leaves = seeded(seed=3)
    eng = Engine(model, num_slots=2, max_seq_len=128, kv_block_size=8,
                 kv_blocks=40, prefill_chunk=16,
                 registry=monitor.StatRegistry())
    shared = tokens(37, seed=1)[0].tolist()
    seed_req = eng.submit(shared + [5, 6, 7], max_new_tokens=2)
    eng.run_until_idle()         # 4 whole blocks of ``shared`` cached
    first = eng.submit(shared + tokens(9, seed=2)[0].tolist(),
                       max_new_tokens=12)
    while len(first.generated) < 3:
        eng.step()
    slot, = [i for i, blocks in enumerate(eng._slot_blocks) if blocks]
    adopted = np.asarray(eng._slot_blocks[slot][:4])
    before = [np.asarray(p)[adopted] for p in eng.k_pools]
    hits = eng.registry.get("serving.prefix_hit_tokens").value
    second = eng.submit(shared + tokens(30, seed=4)[0].tolist(),
                        max_new_tokens=6)
    while not second.generated:
        eng.step()
    assert not first.done()                 # still live beside it
    assert eng.registry.get("serving.prefix_hit_tokens").value \
        == hits + 32
    other, = [i for i, blocks in enumerate(eng._slot_blocks)
              if blocks and i != slot]
    assert list(eng._slot_blocks[other][:4]) == list(adopted)
    for pool, was in zip(eng.k_pools, before):
        assert np.array_equal(np.asarray(pool)[adopted], was)
    eng.run_until_idle()
    for r in (seed_req, first, second):
        ids = np.asarray(list(r.result()))
        want = np.asarray(_reference().logits(
            getter(leaves), DIMS, ids[None]))[0]
        n = len(ids) - len(r.generated)
        assert list(np.argmax(want, axis=-1)[n - 1:-1]) == list(ids[n:])


@pytest.mark.parametrize("absorbed", [False, True])
def test_whole_forward_equals_the_reference(absorbed):
    model, leaves = seeded(seed=1)
    ids = tokens(24, rows=2, seed=1)
    want = np.asarray(_reference().logits(getter(leaves), DIMS, ids))
    got = np.asarray(model(ids, absorbed=absorbed)._data)
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("window", [1, 5, 16])
def test_absorbed_equals_expanded_over_the_paged_cache(window):
    model, _ = seeded(seed=2)
    attn = model.blocks[1].attn
    rng = np.random.default_rng(window)
    B, bs, nbt = 3, 8, 6
    pool = jnp.asarray(rng.normal(size=(B * nbt + 1, bs, attn.row)),
                       jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(B * nbt).reshape(B, nbt), jnp.int32)
    pos = jnp.asarray([0, 13, 30], jnp.int32)
    q_n = jnp.asarray(rng.normal(size=(B, window, 4, attn.d_n)),
                      jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(B, window, 4, attn.d_r)),
                      jnp.float32)
    a = attn.attend(q_n, q_r, pool, tables, pos, absorbed=True)
    e = attn.attend(q_n, q_r, pool, tables, pos, absorbed=False)
    assert np.abs(np.asarray(a - e)).max() < 1e-4


@pytest.fixture
def short_walk(monkeypatch):
    """Items of 16 rows, 4 a trip: tables of 64 rows are then four
    items long, and a few slots make several trips."""
    monkeypatch.setattr(programs, "_WALK_ROWS", 16)
    monkeypatch.setattr(programs, "_WALK_GROUP", 4)


def _one_shot(attn, q_n, q_r, pool, tables, pos):
    """float64 softmax attention of each slot's query over its own
    rows ``<= pos``, expanded form, one shot."""
    import math
    w = np.asarray(attn._w_kvb(), np.float64)
    out = np.zeros((len(pos), attn.num_heads * attn.d_v))
    for b, last in enumerate(pos):
        rows = np.asarray(pool, np.float64)[np.asarray(tables[b])]
        rows = rows.reshape(-1, rows.shape[-1])[:last + 1]
        kv = np.einsum("kr,rhe->khe", rows[:, :attn.rank], w)
        sc = (np.einsum("hn,khn->hk", np.asarray(q_n[b, 0], np.float64),
                        kv[..., :attn.d_n])
              + np.einsum("hr,kr->hk", np.asarray(q_r[b, 0], np.float64),
                          rows[:, attn.rank:attn.row])
              ) / math.sqrt(attn.d_n + attn.d_r)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[b] = np.einsum("hk,khv->hv", p / p.sum(-1, keepdims=True),
                           kv[..., attn.d_n:]).reshape(-1)
    return out


# bs 8, chunk 16 rows, 4 items a trip (``short_walk``); pos 0 = parked
RAGGED = {
    "all 32 equal": ([37] * 32, 8),
    "one long lane among short ones": ([3, 5, 63, 2, 9, 1, 4, 7], 8),
    "parked lanes between live ones": ([0, 22, 0, 0, 47, 0, 13, 0], 8),
    "lengths at an item's edge": ([14, 15, 16, 30, 31, 32], 8),
    "every slot full": ([63] * 6, 8),
    "a table of three items and a half": ([55, 9, 40, 17, 33], 7),
    "16 items: whole trips": ([63, 63, 63, 63, 1, 0], 8),
    "15 items: the last trip padded": ([63, 63, 63, 47, 0, 0], 8),
    "one item": ([0, 0, 6, 0], 8),
    "nothing live": ([0, 0, 0], 8),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_the_decode_walk_equals_one_shot_attention(case, short_walk):
    """The decode form of ``attend`` (several slots, one query each:
    the work list) against a one-shot softmax over each slot's own
    rows; a parked slot returns zeros."""
    pos, nbt = RAGGED[case]
    model, _ = seeded(seed=2)
    attn = model.blocks[1].attn
    rng = np.random.default_rng(len(case))
    B, bs = len(pos), 8
    pool = jnp.asarray(rng.normal(size=(B * nbt + 1, bs, attn.row)),
                       jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(B * nbt).reshape(B, nbt), jnp.int32)
    q_n = jnp.asarray(rng.normal(size=(B, 1, 4, attn.d_n)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(B, 1, 4, attn.d_r)), jnp.float32)
    got = np.asarray(jax.jit(
        lambda *a: attn.attend(*a, absorbed=True))(
            q_n, q_r, pool, tables, jnp.asarray(pos, jnp.int32)))[:, 0]
    live = np.asarray(pos) > 0
    want = _one_shot(attn, q_n, q_r, pool, tables, pos)
    assert np.abs(got - want)[live].max(initial=0) < 2e-6
    assert np.all(got[~live] == 0)


def test_the_decode_walk_at_the_real_item_size():
    """256-row items: windows that end one row before, on and one row
    after an item's edge (lengths 255, 256, 257), in both forms and for
    a window of several queries."""
    model, _ = seeded(seed=2)
    attn = model.blocks[1].attn
    rng = np.random.default_rng(9)
    pos, B, bs, nbt = [254, 255, 256, 600, 0], 5, 16, 40
    pool = jnp.asarray(rng.normal(size=(B * nbt + 1, bs, attn.row)),
                       jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(B * nbt).reshape(B, nbt), jnp.int32)
    q_n = jnp.asarray(rng.normal(size=(B, 3, 4, attn.d_n)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(B, 3, 4, attn.d_r)), jnp.float32)
    for absorbed in (True, False):
        got = np.asarray(attn.attend(q_n, q_r, pool, tables,
                                     jnp.asarray(pos, jnp.int32),
                                     absorbed=absorbed))
        for s in range(3):
            want = _one_shot(attn, q_n[:, s:s + 1], q_r[:, s:s + 1],
                             pool, tables, [p + s for p in pos])
            assert np.abs(got[:4, s] - want[:4]).max() < 2e-6


@pytest.mark.parametrize("pos, table_rows", [
    ([37] * 32, 64), ([3, 5, 63, 2, 9, 1, 4, 7], 64),
    ([0, 22, 0, 0, 47, 0, 13, 0], 64), ([15, 16, 17], 64),
    ([63] * 6, 64), ([55, 9, 40, 17, 33], 56), ([0, 0, 0], 64),
    ([100, 0], 64),                     # past the table: clipped
])
def test_the_work_list_is_the_item_rule(pos, table_rows, short_walk):
    """``sum(ceil((pos + 1) / chunk))`` items over the slots that hold
    a position, ``ceil(items / group)`` trips, every (slot, chunk) pair
    once and in order; the host twin counts the same rows."""
    chunk, group = 16, min(4, len(pos))
    slot_of, chunk_of, valid, n_trips = (np.asarray(a) for a in
                                         programs.walk_plan(
        jnp.asarray(pos, jnp.int32), 1, table_rows, chunk, group))
    n = [min(-(-(p + 1) // chunk), -(-table_rows // chunk)) if p else 0
         for p in pos]
    assert int(valid.sum()) == sum(n)
    assert int(n_trips) == -(-sum(n) // group)
    assert len(valid) % group == 0 and len(valid) >= sum(n)
    assert list(zip(slot_of[valid], chunk_of[valid])) == [
        (b, c) for b, k in enumerate(n) for c in range(k)]
    assert programs.walk_rows(np.asarray(pos), 1, table_rows, 8) \
        == int(n_trips) * group * chunk


def _decode_rows(eng):
    reg = eng.registry
    return tuple(reg.get("serving.decode_rows_" + k).value
                 for k in ("walked", "live", "table"))


def test_the_rows_counters_add_up_to_the_rule(short_walk):
    """Through a tiny engine: every decode dispatch adds the rule's
    rows for the position mirror it was issued from."""
    model, _ = seeded(seed=6)
    eng = Engine(model, num_slots=4, max_seq_len=128, kv_block_size=8,
                 kv_blocks=72, prefill_chunk=16, trace_capacity=4096,
                 registry=monitor.StatRegistry())
    seen = []
    rule = eng._serving_spec.decode_rows

    def spy(pos, ahead, table_rows, block_size):
        seen.append((pos.copy(), ahead))
        return rule(pos, ahead, table_rows, block_size)
    eng._serving_spec.decode_rows = spy
    for k in (70, 3, 33):
        eng.submit(tokens(k, seed=k)[0].tolist(), max_new_tokens=7)
    eng.run_until_idle()
    assert seen
    walked = sum(programs.walk_rows(p, a, 128, 8) for p, a in seen)
    live = sum(int(np.minimum(p[p > 0] + a, 128).sum()) for p, a in seen)
    assert _decode_rows(eng) == (walked, live, len(seen) * 4 * 128)
    assert live <= walked < len(seen) * 4 * 128
    rows = [e["args"]["rows"] for e in eng.chrome_trace()["traceEvents"]
            if e["name"] == "decode.dispatch"]
    assert rows == [programs.walk_rows(p, a, 128, 8) // 4 for p, a in seen]


def test_gpt_counts_by_the_same_rule():
    """``GPTModel`` and the latent model both count by ``walk_rows``,
    the one item rule; GPT hands it the width of a position's K and V,
    by which its trips are sized (to the longest window these requests
    read 23,808 rows of 46,080; the parent's run)."""
    paddle.seed(0)
    model = GPTModel.from_config("tiny", max_position=1024, dropout=0.0)
    model.eval()
    rule = model.serving_spec().decode_rows
    assert rule.func is programs.walk_rows
    assert rule.keywords == {"row_width": 2 * 4 * 16}
    assert MLAMoEModel(DIMS).serving_spec().decode_rows \
        is programs.walk_rows
    # 1 + 2 items of 3 a trip: one trip of three chunks
    assert rule(np.asarray([5, 0, 300]), 2, 2048, 16) == 3 * 256
    assert rule(np.asarray([5, 0, 300]), 2, 2048, None) == 3 * 256
    eng = Engine(model, num_slots=3, max_seq_len=1024, kv_block_size=8,
                 kv_blocks=160, prefill_chunk=64,
                 registry=monitor.StatRegistry())
    rng = np.random.default_rng(7)
    for k in (37, 300, 520, 20):
        eng.submit(rng.integers(1, 128, k).tolist(), max_new_tokens=6)
    eng.run_until_idle()
    walked, live, table = _decode_rows(eng)
    assert table == 46080
    assert 0 < live <= walked < 23808


def test_the_form_is_chosen_from_the_shape():
    attn = MLAMoEModel(dict(
        DIMS, num_attention_heads=16, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
        hidden_size=256)).blocks[0].attn
    # 34.8 against 10.2 kFLOP a pair, 4.19 MFLOP a position expanded
    assert [attn.absorbed_wins(w) for w in (1, 128, 170, 171, 256)] \
        == [True, True, True, False, False]


@pytest.mark.parametrize("broken, change", [
    ("the correction bias", lambda d, w: (d, {
        n: jnp.zeros_like(v) for n, v in w.items() if "gate_bias" in n})),
    ("the scaling factor", lambda d, w: (
        dict(d, routed_scaling_factor=1.0), {})),
    ("the shared expert", lambda d, w: (d, {
        n: jnp.zeros_like(v) for n, v in w.items()
        if "shared.down_proj" in n})),
    ("the kv norm", lambda d, w: (d, {
        n: jnp.ones_like(v) * jnp.sqrt(jnp.mean(v * v))
        for n, v in w.items() if "kv_norm" in n})),
])
def test_a_reference_without_a_piece_differs(broken, change):
    """Each piece of the mathematics shows in the comparison: the
    reference computed without it is far from the program (and the
    whole reference is near)."""
    model, leaves = seeded(seed=3, bias=0.5)
    ids = tokens(24, seed=3)
    got = np.asarray(model(ids)._data)
    whole = np.asarray(_reference().logits(getter(leaves), DIMS, ids))
    assert np.abs(got - whole).max() < TOL
    dims, replace = change(DIMS, leaves)
    without = np.asarray(_reference().logits(
        getter(leaves, **replace), dims, ids))
    assert np.abs(got - without).max() > 100 * TOL, broken


@pytest.mark.parametrize("skew", [0.0, 50.0])
def test_every_pair_is_computed_and_counted(skew):
    """Under a routing skewed onto two experts (a correction bias that
    decides the selection) nothing is dropped: the logits still equal
    the reference's, and the counters read what a NumPy count of the
    routing reads."""
    model, leaves = seeded(seed=4)
    ffn = model.blocks[1].ffn
    bias = np.zeros(8, np.float32)
    bias[[2, 5]] = skew
    ffn.gate_bias.set_value(bias)
    leaves["blocks.1.ffn.gate_bias"] = jnp.asarray(bias)
    n, chunk = 21, 32
    ids = tokens(n, seed=4)[0]
    want = np.asarray(_reference().logits(getter(leaves), DIMS,
                                          ids[None]))[0]
    got, stats = paged_logits(model, ids, chunk, 0)
    assert np.abs(got[0] - want[-1]).max() < TOL
    # the routing, counted by hand from the reference's side
    x0 = model.blocks[0](model.embed._data[jnp.asarray(ids)][None])
    x1 = x0 + model.blocks[1].attn(model.blocks[1].input_norm(x0))
    h = np.asarray(model.blocks[1].post_norm(x1))[0]
    s = 1 / (1 + np.exp(-(h @ np.asarray(leaves[
        "blocks.1.ffn.gate_weight"]))))
    chosen = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :2]
    load = np.bincount(chosen.reshape(-1), minlength=8)
    assert list(np.asarray(stats)) == [2 * n, int((load > 0).sum()), 8,
                                       int(load.max())]
    if skew:
        assert sorted(np.unique(chosen)) == [2, 5] and load.max() == n


def test_dead_rows_hit_no_expert():
    choice = jnp.asarray([[0, 1], [1, 2], [3, 4]], jnp.int32)
    live = jnp.asarray([True, False, True])
    order, sizes = moe.sort_pairs_by_expert(choice, live, 6)
    assert list(np.asarray(sizes)) == [1, 1, 0, 1, 1, 0]
    assert sorted(np.asarray(order)[:4] // 2) == [0, 0, 2, 2]
    x = jnp.ones((3, 4))
    w_in, w_out = jnp.ones((6, 4, 6)), jnp.ones((6, 3, 4))
    y, stats = moe.dropless_experts(x, choice, jnp.ones((3, 2)), live,
                                    w_in, w_out)
    assert np.all(np.asarray(y[1]) == 0) and np.all(np.asarray(y[0]) > 0)
    assert list(np.asarray(stats)) == [4, 4, 1]


def test_sigmoid_topk_routing_selects_with_the_bias_and_weighs_without():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    choice, w = moe.sigmoid_topk_routing(logits, bias, 2, scale=2.0)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert list(np.asarray(choice[0])) == [3, 0]
    assert np.allclose(np.asarray(w[0]),
                       2.0 * s[[3, 0]] / s[[3, 0]].sum(), atol=1e-6)


# -- the seam ----------------------------------------------------------

def _follows_greedily(model, prompt, generated):
    """Every generated token is the argmax of the model's own uncached
    logits over what came before it (one forward, teacher-forced)."""
    ids = np.asarray([list(prompt) + list(generated)])
    out = model(paddle.to_tensor(ids) if isinstance(model, GPTModel)
                else ids)
    top = np.argmax(np.asarray(out._data)[0], axis=-1)
    return list(top[len(prompt) - 1:-1]) == list(generated)


def _gpt():
    paddle.seed(0)
    model = GPTModel.from_config("tiny", max_position=128, dropout=0.0)
    model.eval()
    return model


@pytest.mark.parametrize("build", [
    pytest.param(_gpt, id="gpt"),
    pytest.param(lambda: seeded(seed=5)[0], id="mla_moe")])
def test_one_engine_serves_both_models(build):
    """The same Engine call, chunked paged prefill, the fused decode
    tick, a prefix adopted from the cache: token-identical to each
    model's own uncached greedy decoding."""
    model = build()
    # its own registry: whether the moe counters exist is asserted below,
    # and the default one keeps what earlier engines of the process made
    eng = Engine(model, registry=monitor.StatRegistry(), num_slots=3,
                 max_seq_len=128, kv_block_size=8, kv_blocks=48,
                 prefill_chunk=16)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 128, n).tolist() for n in (37, 5, 50, 20)]
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert _follows_greedily(model, p, list(r.result())[len(p):])
    hits = eng.registry.get("serving.prefix_hit_tokens").value
    again = eng.submit(prompts[0], max_new_tokens=8)   # adopted
    eng.run_until_idle()
    assert eng.registry.get("serving.prefix_hit_tokens").value \
        == hits + 32
    assert list(again.result()) == list(reqs[0].result())
    spec = model.serving_spec()
    assert eng.registry.get("serving.kv_row_bytes").value \
        == spec.kv.position_bytes()
    assert eng.registry.get("serving.kv_block_bytes").value \
        == spec.kv.block_bytes(8)
    pairs = eng.registry.get("serving.moe_routed_pairs")
    assert (pairs is not None) == bool(spec.counters)
    # a model that picks an implementation by the backend says which
    assert eng.debug_requests()["engine"]["kernels"] == (
        {"moe.experts": "ragged_dot"} if spec.counters else {})


def test_sampled_decoding_and_counters_through_the_engine():
    model, _ = seeded(seed=6)
    eng = Engine(model, num_slots=2, max_seq_len=64, kv_block_size=8,
                 kv_blocks=24, prefill_chunk=8,
                 trace_capacity=4096)
    a = eng.submit(tokens(12)[0].tolist(), max_new_tokens=10,
                   temperature=0.8, top_k=20, seed=11)
    eng.run_until_idle()
    b = eng.submit(tokens(12)[0].tolist(), max_new_tokens=10,
                   temperature=0.8, top_k=20, seed=11)
    eng.run_until_idle()
    assert list(a.result()) == list(b.result())
    reg = eng.registry
    pairs = reg.get("serving.moe_routed_pairs").value
    # (the last chunk's vector waits for the next download)
    assert pairs > 0 and pairs % 2 == 0
    assert reg.get("serving.moe_experts_hit").value \
        <= reg.get("serving.moe_expert_slots").value
    dev = [e for e in eng.chrome_trace()["traceEvents"]
           if e["name"] in ("dev.decode", "dev.prefill")]
    assert dev and all({"pairs", "experts_hit"} <= set(e["args"])
                       for e in dev)
    assert all("_stats" not in e["args"] for e in dev)


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
    model, _ = seeded(seed=7)
    kw = dict(num_slots=2, max_seq_len=64, kv_block_size=8,
              kv_blocks=24, prefill_chunk=8)
    kw.update(options)
    with pytest.raises(ValueError) as err:
        Engine(model, **kw)
    assert named in str(err.value) and "lacks" in str(err.value)
    assert "MLAMoEModel" in str(err.value)


def _sdar():
    from paddle_tpu.models import SDARMoEModel
    return SDARMoEModel(dict(
        vocab_size=128, max_position_embeddings=64, hidden_size=32,
        moe_intermediate_size=16, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        num_experts=4, num_experts_per_tok=2, rms_norm_eps=1e-6,
        rope_theta=1e6), mask_token_id=127)


@pytest.mark.parametrize("build", [
    lambda: seeded(seed=7)[0],
    lambda: GPTModel.from_config("tiny", dropout=0.0),
    _sdar,
], ids=["MLAMoEModel", "GPTModel", "SDARMoEModel"])
def test_every_refusal_a_served_model_declares_is_read(build):
    """Every key of a served model's ``ServingSpec.unsupported`` is a
    feature the engine asks about (its construction-time table, which
    also names ``migration`` for the migration entry points): a key
    nothing reads is a refusal that never fires."""
    declared = set(build().serving_spec().unsupported)
    assert declared <= set(Engine._REFUSABLE), \
        declared - set(Engine._REFUSABLE)


def test_migration_is_refused_when_asked_for():
    model, _ = seeded(seed=7)
    eng = Engine(model, num_slots=2, max_seq_len=64, kv_block_size=8,
                 kv_blocks=24, prefill_chunk=8)
    for call in (lambda: eng.migrate_out(wait=False),
                 lambda: eng.export_prefix([1, 2, 3], wait=False)):
        with pytest.raises(ValueError, match="KV migration"):
            call()


def test_weight_only_int8_relayouts_the_linear_projections():
    model, _ = seeded(seed=8)
    eng = Engine(model, num_slots=2, max_seq_len=64, kv_block_size=8,
                 kv_blocks=24, prefill_chunk=8, weight_dtype="int8")
    r = eng.submit(tokens(12)[0].tolist(), max_new_tokens=4)
    eng.run_until_idle()
    assert len(r.result()) == 16
    assert type(model.blocks[1].attn.q_proj).__name__ \
        == "WeightOnlyInt8Linear"


# -- the row spec --------------------------------------------------------

@pytest.mark.parametrize("spec, block, want", [
    # 9 layers x 16 rows x 640 numbers x 2 bytes: a row of 576 is
    # stored in whole tiles of 128 lanes, and the bytes are the pool's
    (KVRowSpec(9, "bfloat16", (("latent", (576,)),)), 16, 184_320),
    # a row inside one tile, or of whole tiles, is stored as it is
    (KVRowSpec(2, "float32", (("latent", (40,)),)), 8, 2_560),
    # 24 layers x K and V x 16 rows x 16 heads x 128 x 2 bytes
    (KVRowSpec.heads(24, 16, 128, "bfloat16"), 16, 3_145_728),
    (KVRowSpec.heads(24, 16, 64, "bfloat16"), 16, 1_572_864),
])
def test_block_bytes_come_from_the_row_spec(spec, block, want):
    assert spec.block_bytes(block) == want
    assert spec.position_bytes() * block == want
    # what the bytes count is what the pools hold
    held = sum(int(np.prod(shape)) for shape in
               spec.pool_shapes((1, block))) * spec.n_layers
    assert held * np.dtype(spec.dtype).itemsize == want
    if spec.heads_axis:
        assert per_shard_block_bytes(block, 16, spec.head_dim,
                                     "bfloat16", 24) == want
        assert spec.block_bytes(block, mp=2) == want // 2
        assert spec.geometry(block) == {
            "block_size": 16, "num_heads": 16,
            "head_dim": spec.head_dim, "n_layers": 24}
    else:
        # the row as the model wrote it, without the padding
        assert spec.geometry(block)["rows"] == [
            ["latent", list(spec.rows[0][1])]]
        with pytest.raises(ValueError, match="no head axis"):
            spec.block_bytes(block, mp=2)


LFM2_SPEC = dict(n_layers=3, dtype="bfloat16", rows=(("kv", (1024,)),),
                 block_rows=(("conv", 40960),))


@pytest.mark.parametrize("tails", [(("conv", 40960),),
                                   (("conv", 32768), ("gate", 8192))],
                         ids=["one_pool", "two_pools"])
@pytest.mark.parametrize("block, want", [(16, 180_224), (32, 278_528),
                                         (64, 475_136)])
def test_block_bytes_with_a_row_a_block(block, want, tails):
    """3 layers keep a row of 1,024 a position, 10 keep a tail of 4,096
    a BLOCK, side by side in one pool's width as
    ``models/lfm2_moe.py`` declares them (``KVRowSpec.block_rows``: a
    pool a name): a position is 3 x 1,024 x 2 B, a block its rows and
    10 x 4,096 x 2 B of tails, whatever its size."""
    spec = KVRowSpec(**dict(LFM2_SPEC, block_rows=tails))
    assert spec.position_bytes() == 6_144
    assert spec.block_bytes(block) == block * 6_144 + 81_920 == want
    # what the bytes count is what the pools hold
    held = (3 * int(np.prod(spec.pool_shapes((1, block))[0]))
            + sum(int(np.prod(s)) for s in spec.block_pool_shapes(1)))
    assert held * 2 == want
    assert spec.geometry(block) == {
        "block_size": block, "rows": [["kv", [1024]]], "n_layers": 3,
        "block_rows": [list(t) for t in tails]}
    with pytest.raises(ValueError, match="no head axis"):
        spec.block_bytes(block, mp=2)


def test_a_row_a_block_is_one_flat_axis_of_whole_tiles():
    """The tile rule for ``[blocks, width]``: a per-block pool's last
    two axes are (blocks, width), the width padded to whole 128-lane
    tiles where it is wider than one and no multiple; and it takes
    the pools' second list, so the rows are one flat pool a layer."""
    spec = KVRowSpec(**LFM2_SPEC)
    assert spec.block_pool_shapes(7) == [(7, 40960)]
    padded = KVRowSpec(1, "float32", (("kv", (64,)),),
                       block_rows=(("conv", 4100), ("gate", 4100)))
    assert padded.block_pool_shapes(5) == [(5, 4224)] * 2
    assert padded.block_bytes(8) == (8 * 64 + 2 * 4224) * 4
    assert padded.geometry(8)["block_rows"] == [["conv", 4100],
                                                ["gate", 4100]]
    with pytest.raises(ValueError, match="second list"):
        KVRowSpec(2, "float32", (("k", (4, 16)), ("v", (4, 16))),
                  heads_axis=True, block_rows=(("conv", 128),))
    # a spec without them reports none and allocates none
    plain = KVRowSpec(2, "float32", (("latent", (40,)),))
    assert plain.block_rows == () and plain.block_pool_shapes(9) == []
    assert "block_rows" not in plain.geometry(8)


def test_a_budget_buys_the_blocks_the_pools_hold():
    """A latent row wider than one tile of 128 lanes and no multiple
    of it (160 + 8 -> 256 stored): ``kv_budget_mb`` is spent on the
    stored bytes, and the gauges read what the pools hold."""
    model = MLAMoEModel(dict(DIMS, kv_lora_rank=160))
    eng = Engine(model, num_slots=2, max_seq_len=64, kv_block_size=8,
                 kv_budget_mb=1, prefill_chunk=16)
    block = eng.registry.get("serving.kv_block_bytes").value
    assert block == 2 * 8 * 256 * 4
    assert eng.registry.get("serving.kv_row_bytes").value == 2 * 256 * 4
    managed = eng.registry.get("serving.kv_blocks_total").value
    assert managed == 2 ** 20 // block
    held = sum(p.nbytes for p in eng.k_pools)
    assert tuple(eng.k_pools[0].shape[1:]) == (8, 256)
    # the pools hold the managed blocks and the reserved scratch block
    assert managed * block <= 2 ** 20 < held + block
    assert eng.kv_geometry()["rows"] == [["latent", [168]]]
    got = eng.submit(list(range(1, 20)), max_new_tokens=4)
    eng.run_until_idle()
    assert _follows_greedily(model, list(range(1, 20)),
                             list(got.result())[19:])


# -- LazyGuard -----------------------------------------------------------

def test_lazy_guard_declares_without_values():
    with nn.LazyGuard():
        model = MLAMoEModel(DIMS)
    params = dict(model.named_parameters())
    assert all(p.is_declared for p in params.values())
    assert params["embed"].shape == [128, 64]
    model.to(dtype="bfloat16")
    assert all(str(p._data.dtype) == "bfloat16" for p in params.values())
    p = params["blocks.1.ffn.gate_bias"]
    p.set_value(jnp.ones((8,)))
    assert not p.is_declared and str(p._data.dtype) == "bfloat16"
    q = params["norm.weight"].initialize()
    assert not q.is_declared and np.all(np.asarray(
        q._data, np.float32) == 1.0)


def test_outside_the_guard_parameters_are_initialised_as_before():
    lin = nn.Linear(4, 3)
    assert not lin.weight.is_declared
    assert isinstance(lin.weight._data, jax.Array)
    with nn.LazyGuard():
        lazy = nn.Linear(4, 3)
    assert lazy.weight.is_declared and not nn.Linear(4, 3).bias.is_declared
