"""``MLAMoEModel`` with what Xing4.0-29B-A4B's config declares on top
of the DeepSeek-V3 layer family — a residual of ``hc_mult`` streams
whose mappings are projected by Sinkhorn in every sub-layer, a low-rank
query, YaRN positions — against its plain reference
(benchmarks/configs/mhc_mla_moe_reference.py), at a tiny size on the
CPU with seeded float32 weights.  ``original_max_position_embeddings``
is small, so the sequences cross the interpolated band."""
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
from paddle_tpu.models import mla_moe
from paddle_tpu.models.mla_moe import MLAMoEModel
from paddle_tpu.serving import Engine, EngineServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on the CPU; the program and the reference order their sums
# differently (absorbed against expanded attention, sorted pairs against
# a loop over experts, Sinkhorn over separate arrays against reductions)
TOL = 1e-4

DIMS = dict(
    vocab_size=128, max_position_embeddings=256, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, n_shared_experts=1, n_routed_experts=8,
    routed_scaling_factor=2.0, kv_lora_rank=32, q_lora_rank=24,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    num_experts_per_tok=2, first_k_dense_replace=2, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16},
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)


def _reference():
    name = "mhc_mla_moe_reference_under_test"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "benchmarks", "configs",
                               "mhc_mla_moe_reference.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def seeded(dims=DIMS, seed=0):
    """The model with every leaf drawn from ``seed`` (matrices normal
    0.08, gains and the mappings' ``alpha`` 1 + normal 0.1, biases
    normal 0.1), and the leaves for the reference."""
    model = MLAMoEModel(dims)
    model.eval()
    leaves = {}
    for i, (name, p) in enumerate(model.named_parameters()):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        v = jax.random.normal(key, tuple(p.shape), jnp.float32)
        if name.endswith(("gate_bias", "beta")):
            v = 0.1 * v
        elif len(p.shape) == 1:
            v = 1.0 + 0.1 * v
        else:
            v = 0.08 * v
        p.set_value(v)
        leaves[name] = v
    return model, leaves


def getter(leaves):
    return lambda names: {n: leaves[n] for n in names}


def tokens(n, rows=1, seed=0):
    return np.random.default_rng(seed).integers(
        1, DIMS["vocab_size"], (rows, n))


def test_the_leaves_are_the_reference_s():
    model, leaves = seeded()
    ref = _reference()
    want = {"embed", "norm.weight", "lm_head.weight"} | {
        f"blocks.{i}.{n}" for i in range(DIMS["num_hidden_layers"])
        for n in ref.layer_leaves(DIMS, i)}
    assert set(leaves) == want
    n, d = DIMS["hc_mult"], DIMS["hidden_size"]
    hc = model.blocks[0].attn_hc
    assert sum(int(np.prod(p.shape)) for p in hc.parameters()) \
        == n * d * (n * (n + 2) + 1) + n * (n + 2) + 3


@pytest.mark.parametrize("absorbed", [False, True])
def test_forward_equals_the_reference_in_both_forms(absorbed):
    model, leaves = seeded()
    ids = tokens(48, rows=2)
    got = np.asarray(model(jnp.asarray(ids), absorbed=absorbed)._data)
    want = np.asarray(_reference().logits(getter(leaves), DIMS, ids))
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < TOL


def paged_logits(model, ids, chunk, n_decode, bs=8, nb=14):
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
        last, pools, _, _ = model._chunk_prefill_tick_paged(
            jnp.asarray(toks), pools, table, p0, m, 0)
        p0 += m
    out.append(last[0])
    for t in range(n, len(ids)):
        x = model._widen(model.embed._data[jnp.asarray([[ids[t]]])])
        pos = jnp.asarray([t], jnp.int32)
        new = []
        for blk, pool in zip(model.blocks, pools):
            x, pool, _ = blk.decode_slots_paged(
                x, pool, table[None, :], pos, jnp.asarray([True]))
            new.append(pool)
        pools = new
        out.append(model._head(x)[0, -1])
    return np.asarray(jnp.stack(out))


@pytest.mark.parametrize("n, chunk, n_decode", [
    (40, 16, 6),      # whole chunks and a tail, past YaRN's band
    (23, 8, 3),       # a chunk that ends inside a block
    (30, 12, 4),      # chunks of 1.5 blocks: every other starts mid-block
    (86, 16, 2),      # 84 rows: every position past the original 16
])
def test_paged_prefill_then_decode_equals_the_reference(n, chunk,
                                                        n_decode):
    model, leaves = seeded()
    ids = tokens(n, seed=n)[0]
    got = paged_logits(model, ids, chunk, n_decode)
    want = np.asarray(_reference().logits(
        getter(leaves), DIMS, ids[None, :]))[0, -n_decode - 1:]
    assert np.abs(got - want).max() < TOL


def test_h_res_is_doubly_stochastic_and_neither_identity_nor_uniform():
    model, _ = seeded()
    hc = model.blocks[1].ffn_hc
    X = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 5, 64))
    h_pre, h_post, h_res = hc.maps(X)
    m = np.asarray(jnp.stack([jnp.concatenate(r, -1) for r in h_res], -2))
    assert m.shape == (2, 5, 4, 4) and (m > 0).all()
    assert np.abs(m.sum(-1) - 1).max() < 1e-5         # rows
    assert np.abs(m.sum(-2) - 1).max() < 1e-3         # columns
    assert np.abs(m - np.eye(4)).max() > 0.2
    assert np.abs(m - 0.25).max() > 0.05
    pre = np.asarray(jnp.stack(h_pre))[..., 0]
    post = np.asarray(jnp.stack(h_post))[..., 0]
    assert ((pre > 0) & (pre < 1)).all() and ((post > 0) & (post < 2)).all()
    # the reference's mappings of the same streams
    ref = _reference()
    w = {"norm.weight": hc.norm.weight._data, "phi": hc.phi._data,
         "alpha": hc.alpha._data, "beta": hc.beta._data}
    r_pre, r_post, r_res = ref.mappings(
        w, "", jnp.transpose(X, (1, 2, 0, 3)).reshape(10, 4, 64), DIMS)
    assert np.abs(np.asarray(r_res).reshape(m.shape) - m).max() < 1e-5
    assert np.abs(np.asarray(r_pre).T.reshape(pre.shape) - pre).max() < 1e-5
    assert np.abs(np.asarray(r_post).T.reshape(post.shape)
                  - post).max() < 1e-5


def test_the_clamp_bounds_what_sinkhorn_sees():
    """Raw values far outside the clamp: the kernel's exp stays finite
    and the rows still sum to 1; the kernel (interpreted off the TPU)
    equals ``sinkhorn`` run on plain arrays."""
    raw = jnp.zeros((24, 3), jnp.float32).at[8, :].set(1e4) \
        .at[9, :].set(-1e4).at[:, 1].add(jnp.arange(24) * 0.1)
    out = np.asarray(mla_moe.mhc_maps(raw, 4, 20, 1e-6, (-30.0, 30.0)))
    m = out[8:].reshape(4, 4, 3)
    assert np.isfinite(out).all() and np.abs(m.sum(1) - 1).max() < 1e-5
    want = mla_moe.sinkhorn(
        list(jnp.exp(jnp.clip(raw[8:], -30, 30))), 4, 20, 1e-6)
    assert np.abs(np.asarray(jnp.stack([jnp.stack(r) for r in want]))
                  - m).max() < 1e-6
    assert np.allclose(out[:4], 1 / (1 + np.exp(-np.asarray(raw[:4]))),
                       atol=1e-6)


def test_yarn_frequencies_and_scale_are_the_published_ones():
    """At the published sizes (d_r 64, theta 10,000, factor 64 over
    4,096): corr(32) = 10.4 and corr(1) = 22.4, so the 11 fastest
    frequencies are kept, those from index 23 divided by 64, a ramp
    between; the softmax scale is 0.07217 x (0.1 ln 64 + 1)^2."""
    sc = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
          "mscale": 1, "mscale_all_dim": 1,
          "original_max_position_embeddings": 4096}
    inv = mla_moe.yarn_inv_freq(64, 10000.0, sc)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert np.allclose(inv[:11], plain[:11], rtol=1e-6)
    assert np.allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13.0
    assert np.allclose(inv[11:23], plain[11:23] * (1 - ramp)
                       + plain[11:23] / 64 * ramp, rtol=1e-6)
    attn = mla_moe.MLAttention(64, 2, 128, 64, 128, 32, 10000, 1e-6,
                               q_lora_rank=8, rope_scaling=sc)
    assert attn.scale == pytest.approx(0.0721688 * 2.00474, rel=1e-5)
    assert mla_moe.yarn_mscale(64, 1) == pytest.approx(1.415888)
    assert np.allclose(inv, _reference().rotary(dict(
        qk_rope_head_dim=64, qk_nope_head_dim=128, rope_theta=10000,
        rope_scaling=sc))[0])


def test_what_the_config_does_not_declare_is_not_built():
    """Kimi's config declares none of the new keys: its model has the
    plain residual, one query matrix and plain frequencies."""
    plain = dict(DIMS, q_lora_rank=None, rope_scaling=None, hc_mult=1)
    model = MLAMoEModel(plain)
    names = {n for n, _ in model.named_parameters()}
    assert "blocks.0.attn.q_proj.weight" in names
    assert not any("hc" in n or "q_a_" in n or "q_b_" in n for n in names)
    assert model.serving_spec().residual is None
    with pytest.raises(ValueError, match="n_group"):
        MLAMoEModel(dict(DIMS, n_group=2))


def test_served_through_the_engine_token_for_token():
    """Ragged prompts through ``Engine`` behind ``EngineServer``: every
    served token is the reference's best token over what came before
    it, and ``/healthz`` names the residual."""
    model, leaves = seeded()
    eng = Engine(model, num_slots=4, max_seq_len=128, kv_block_size=8,
                 kv_blocks=72, prefill_chunk=16,
                 registry=monitor.StatRegistry())
    prompts = [tokens(n, seed=n)[0].tolist() for n in (70, 3, 33, 100)]
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
    assert health["residual"] == {"streams": 4, "sinkhorn_iters": 20}
    assert health["kv_row_bytes"] == 3 * 40 * 4
    ref = _reference()
    for p, out in zip(prompts, outs):
        assert len(out) == 7
        seq = np.asarray([p + out])
        lg = np.asarray(ref.logits(getter(leaves), DIMS, seq))[0]
        for i, tok in enumerate(out):
            row = lg[len(p) - 1 + i]
            assert row.max() - row[tok] < TOL


@pytest.mark.parametrize("shape, tiles", [
    # a decode step's 128 pair rows and a chunk's 1,024 through
    # [3,584 -> 2,048] and [1,024 -> 3,584] (chip run, PR 37:
    # ``_gmm_tiling``'s docstring)
    ((128, 3584, 2048), (128, 512, 2048)),
    ((128, 1024, 3584), (128, 1024, 1792)),
    ((1024, 3584, 2048), (128, 512, 2048)),
    ((1024, 1024, 3584), (128, 1024, 1792)),
])
def test_the_tiles_of_the_grouped_products(shape, tiles):
    from paddle_tpu.distributed import moe
    assert moe._gmm_tiling(*shape) == tiles
