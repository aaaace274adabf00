"""``trinity-large-preview-serve``: its file against the published
config, the counts of ``configs/afmoe_program.py`` against hand-worked
numbers, the seeded model it builds, its cell and traffic, its control
and its planted fault at the rehearsal's size, and the operations
``dev_share.attn_walk`` reads held to a compile for the described
v5e."""
import functools
import json
import os
import re
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from bench_paths import BENCH, ROOT, config, manifest, run_cell
from harness import common, counts, reducers, xplane

NAME = "trinity-large-preview-serve"
CELL = NAME + ".mixed-doc-sessions"
XING = "xing4-29b-a4b-serve.long-doc-sessions"
cfg = config(NAME)
dims = cfg["dims"]
prog = common.load_program(cfg)
PEAKS = counts.peaks_for("TPU v5 lite")
S, F = "sliding_attention", "full_attention"

# the catalog row's ``config``
# (/opt/skills/guides/model-configs/architectures.jsonl, line 7), verbatim
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": [S, S, S, F] * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "sliding_window": 4096, "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_mm": True, "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1,
           "layer_types": [S, S, S, S, F], "num_experts": 32,
           "vocab_size": 25024}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_are_untouched_but_for_the_reduced(key):
    want = REDUCED.get(key, PUBLISHED[key])
    assert cfg[key] == want and dims[key] == want


def test_what_is_reduced_is_listed_and_no_width_is():
    entry, = [c for c in manifest()["configs"] if c["name"] == NAME]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == set(REDUCED)
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/arcee-ai/Trinity-Large-Preview/" \
           "blob/main/config.json"
    assert set(dims) - set(PUBLISHED) == {"share"}
    assert dims["share"] == {"experts_first": 0, "experts_of": 256}
    for key in ("num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"):
        assert cfg["published"][key] == PUBLISHED[key]
    # a whole period after the one dense layer, an eighth of the
    # experts and of the vocabulary: the guide's floors
    assert dims["num_hidden_layers"] - dims["num_dense_layers"] >= 4
    assert dims["layer_types"][1:] == PUBLISHED["layer_types"][8:12]
    assert dims["num_experts"] >= 8
    assert dims["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for word in ("8 chips", "ONE block table", "experts 0-31"):
        assert word in cfg["deployment"]
    for key in ("output_gate", "qk_norm", "positions", "sandwich_norms",
                "embedding_scale", "router", "attention_bias",
                "rope_pairs", "max_seq_len", "weights"):
        assert cfg["assumed"][key]


# the issue's own count, bf16: d 3,072, 48 query / 8 K/V heads of 128
ATTN = 3 * 3072 * 6144 + 2 * 3072 * 1024            # 62,914,560
EXPERT = 3 * 3072 * 3072                            # 28,311,552
NORMS = 4 * 3072 + 2 * 128
DENSE = ATTN + 3 * 3072 * 12288 + NORMS             # 176,173,312
OUTSIDE = ATTN + 3072 * 256 + 256 + EXPERT + NORMS  # 92,025,344
FIXED = 5 * ATTN + 3 * 3072 * 12288 \
    + 4 * (EXPERT + 3072 * 256) + 3072 * 25024      # 621,084,672


@pytest.mark.parametrize("what, got, want", [
    ("attention", prog.attention_params(dims), 62_914_560),
    ("an expert", prog.expert_params(dims), 28_311_552),
    ("an expert's bytes", prog.expert_bytes(dims), 56_623_104),
    ("the dense layer", DENSE, 176_173_312),
    ("an expert layer here", OUTSIDE + 32 * EXPERT, 997_995_008),
    ("the whole", prog.total_params(dims), 4_321_903_872),
    ("the whole, added up", DENSE + 4 * (OUTSIDE + 32 * EXPERT)
     + 2 * 25024 * 3072 + 3072, 4_321_903_872),
    ("a step's fixed reads", prog.fixed_step_params(dims), FIXED),
    ("a row of one layer", prog.row_bytes(dims), 4096),
    ("rows seen inside the window", prog.rows_seen(dims, 1499),
     5 * 1500),
    ("rows seen past it", prog.rows_seen(dims, 11999),
     4 * 4096 + 12000),
    ("a pair's attention", prog.attention_flops_per_pair(dims),
     4 * 48 * 128),
    ("the router's width", prog.router_width(dims), 256),
])
def test_hand_worked(what, got, want):
    assert got == want, what


def test_the_published_model_is_the_name_s_400b_a13b():
    """The parametrisation is the published one: whole, 60 layers of
    which 6 dense, 256 experts, the whole vocabulary."""
    whole = dict(PUBLISHED)
    assert prog.total_params(whole) == pytest.approx(398.6e9, rel=1e-3)
    active = (60 * (ATTN + NORMS) + 6 * 3 * 3072 * 12288
              + 54 * (5 * EXPERT + 3072 * 256 + 256)
              + 2 * 3072 * 200192 + 3072)
    assert active == pytest.approx(13.4e9, rel=5e-3)
    # ONE layer's experts do not fit a chip
    assert 256 * prog.expert_bytes(whole) > 14e9


def test_least_seconds_follow_the_counters():
    """1.24 GB a step, 56.6 MB a held expert hit by the decode program
    (a chunk run is taken to hit all 4 x 32), 4,096 B a row seen; the
    grouped product two operations a parameter for the pairs computed
    HERE."""
    work = {"tokens_emitted": 320, "num_slots": 32, "prefill_tokens": 512,
            "live_positions": 10**9,        # not read: the rows are
            "counters": {"serving.moe_experts_hit": 2 * 128 + 170,
                         "serving.prefill_chunks": 2,
                         "serving.moe_routed_pairs": 500,
                         "serving.moe_pairs_elsewhere": 3500,
                         "serving.attn_rows_seen": 1_000_000,
                         "serving.attn_rows_seen_chunk": 300_000}}
    assert prog.decode_least_seconds(cfg, PEAKS, work) == pytest.approx(
        (10 * 2 * FIXED + 170 * 56_623_104 + 1_000_000 * 4096) / 819e9,
        rel=1e-12)
    assert 2 * FIXED == pytest.approx(1.242e9, rel=1e-3)
    t, bound = prog.serve_least_seconds(cfg, PEAKS, work)
    assert bound == "memory" and t == pytest.approx(
        (10 * 2 * FIXED + 426 * 56_623_104 + 1_300_000 * 4096) / 819e9,
        rel=1e-12)
    assert prog.gmm_least_seconds(cfg, PEAKS, work) == pytest.approx(
        426 * 56_623_104 / 819e9, rel=1e-12)
    work["counters"]["serving.moe_routed_pairs"] = 10**7
    assert prog.gmm_least_seconds(cfg, PEAKS, work) == pytest.approx(
        2 * EXPERT * 1e7 / 197e12, rel=1e-12)
    # a program without the counters (the parent) gives a number, not
    # an error
    assert prog.decode_least_seconds(cfg, PEAKS, dict(
        work, counters={})) == pytest.approx(10 * 2 * FIXED / 819e9)


def test_build_holds_the_seeded_leaves_and_nothing_else():
    tiny = common.merged(cfg, cfg["rehearse"])
    model = prog.build(tiny, 2**31 + 9)
    params = dict(model.named_parameters())
    specs = prog.leaf_specs(tiny["dims"])
    assert set(params) == {n for n, _, _ in specs}
    want = common.seeded_weights(tiny, 2**31 + 9)
    for name, shape, _ in specs:
        got = params[name]._data
        assert tuple(got.shape) == tuple(shape)
        assert str(got.dtype) == tiny["dtype"]
        assert np.array_equal(np.asarray(got), np.asarray(want[name]))
    assert not list(model.named_buffers())
    assert model.held == (2, 4) and model.experts_of == 8
    assert [b.attn.reach for b in model.blocks] == [8, 8, 8, 8, None]


def test_the_cell_s_traffic_is_the_issue_s():
    with open(common.BENCH_DIR + "/traffic/mixed-doc-sessions.json") as f:
        mix = json.load(f)
    assert mix["kind"] == "sessions" and mix["system_prompt_len"] == 512
    assert mix["history_len"] == {"dist": "lognormal", "median": 8192,
                                  "sigma": 0.8, "min": 1024, "max": 24576}
    assert mix["user_len"] == {"dist": "lognormal", "median": 64,
                               "sigma": 0.6, "min": 16, "max": 256}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.6, "min": 48, "max": 512}
    assert mix["think_s"] == {"dist": "exponential", "mean": 2.0}
    assert mix["max_context"] == 28672 and mix["grace_s"] == 5
    assert cfg["engine"] == {"num_slots": 32, "max_seq_len": 32768,
                             "kv_block_size": 16, "kv_blocks": 15360,
                             "prefill_chunk": 256}
    # the pool: 20,480 B a position, 5.03 GB
    assert 15360 * 16 * 5 * prog.row_bytes(dims) == 5_033_164_800
    man = manifest()
    cell, = [w for w in man["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "mixed-doc-sessions", 1)
    for word in ("5 of 60 layers", "1/8", "4 sliding"):
        assert word in cell["why"]
    assert len(cell["why"]) <= 200
    # on every list that has Xing's cell but dev_share.mhc's, and on
    # dev_share.attn_walk alone
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if XING in m.get("workloads", ()):
                assert (CELL in m["workloads"]) \
                    == (m["name"] != "dev_share.mhc"), m["name"]
    own, = [m for m in man["per_layer"]
            if m["name"] == "dev_share.attn_walk"]
    assert own["workloads"] == [CELL]
    assert sum(CELL in m.get("workloads", ())
               for m in man["per_layer"]) == 16


def test_dev_share_attn_walk_reads_the_loops_by_name():
    """The device loops of both programs over busy time; a trace
    without one reports nothing and raises nothing."""
    files = reducers.load_metric_files(common.BENCH_DIR + "/layer_metrics")
    src = {"device": {"busy_s": 4.0, "by_name": {"XLA Ops": {
        "while s32[]": 1.5, "gmm": 1.0,
        "fusion bf16[256,16,2048]": 0.6}}}}
    got = reducers.reduce_all(files, ["dev_share.attn_walk"], src)
    assert got["dev_share.attn_walk"]["value"] == pytest.approx(37.5)
    src["device"]["by_name"]["XLA Ops"] = {"gmm": 1.0}
    assert reducers.reduce_all(files, ["dev_share.attn_walk"], src) == {}


# -- the operations dev_share.attn_walk reads, held to the compiler ---------

@functools.lru_cache(maxsize=None)
def _loops_on_the_v5e(program):
    """The cell's own step program (its depth, slots, chunk, pool and
    table, at the published widths), compiled for the compile-only
    ``TPU v5 lite`` device with what a chip would run -> [(the name the
    trace's reducer gives a device loop, the scope it was written
    under)] of every ``while`` instruction."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.afmoe import AfmoeModel

    try:
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:      # no compiler for a described chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
    eng, i32 = cfg["engine"], jnp.int32
    layers, slots = dims["num_hidden_layers"], eng["num_slots"]
    with nn.LazyGuard():
        model = AfmoeModel(dims, **dims["share"])
    model.to(dtype="bfloat16")
    params = dict(model.named_parameters())
    names = sorted(params)
    pools = [sds(s) for s in model.serving_spec().kv.pool_shapes(
        (eng["kv_blocks"] + 1, eng["kv_block_size"]))] * layers
    blocks = eng["max_seq_len"] // eng["kv_block_size"]
    if program == "decode":
        def step(p_list, pools, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._fused_decode_tick_slots(
                    args[1], pools, args[0], *args[2:])
        f32, u32 = jnp.float32, jnp.uint32
        args = [sds((slots, blocks), i32), sds((slots, 1), i32)] + [
            sds((slots,), t) for t in (i32, f32, i32, f32, u32, u32, i32,
                                       i32, i32)]
    else:
        def step(p_list, pools, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._chunk_prefill_tick_paged(
                    args[0], pools, *args[1:])
        args = [sds((1, eng["prefill_chunk"]), i32), sds((blocks,), i32),
                sds((), i32), sds((), i32), sds((), i32)]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = jax.jit(step, donate_argnums=(1,)).lower(
                [sds(params[n].shape) for n in names], pools,
                *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    loops = []
    for ln in text.splitlines():
        if " while(" in ln:
            scope = re.search(r'op_name="([^"]*)"', ln).group(1)
            loops.append((xplane.op_key(ln.strip().replace("ROOT ", "")),
                          scope))
    return loops


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_dev_share_attn_walk_matches_the_walks_on_the_v5e(program):
    """Both step programs as the cell runs them (5 layers, the engine's
    own options): every device loop goes under the one name the
    metric's ``match`` finds, there is one a layer's walk (four under
    ``attn.sliding``, one under ``attn.full``: the trace's names do not
    tell the kinds apart, their loops carry the same types) and, in
    the chunk program, megablox's search for its groups' first tiles
    (one a routed layer, a few microseconds), and no other."""
    with open(os.path.join(BENCH, "layer_metrics",
                           "dev_share.attn_walk.json")) as f:
        match = json.load(f)["params"]["match"]
    loops = _loops_on_the_v5e(program)
    assert all(re.search(match, name) for name, _ in loops), loops
    walks = [s for _, s in loops if "/sdar.attend/" in s]
    assert sum("/attn.sliding/" in s for s in walks) == 4
    assert sum("/attn.full/" in s for s in walks) == 1
    rest = [s for _, s in loops if "/sdar.attend/" not in s]
    assert all("jit(gmm)/jit(searchsorted)" in s for s in rest), rest
    assert len(rest) == (4 if program == "chunk" else 0)


LONGER = json.dumps({
    "answer_len": {"dist": "uniform", "min": 24, "max": 32},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history_len": {"dist": "uniform", "min": 16, "max": 40}})
MORE = json.dumps({"check": {"tokens": 4000, "max_requests": 200}})


def compared(lines):
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in lines if ln.startswith("compared ")}


@pytest.mark.parametrize("seed", [1, 2])
def test_control_int8_weights_is_not_correct(seed):
    args = ("--rehearse", "--mix-override", LONGER, "--config-override",
            MORE)
    rc, lines, err = run_cell(CELL, *args, "--control", "int8", seed=seed)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] or not c["regret_mean"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_ignored_window_is_not_correct(seed):
    """``planted_fault_window.py`` (the upper reading of the cell's
    limits on the chip comes from it): a model whose sliding layers see
    every earlier row is caught on requests past the window (here 8
    positions: every request)."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "benchmarks",
                                      "planted_fault_window.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", "--rehearse", "--mix-override", LONGER,
         "--config-override", MORE],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any(ln.startswith("PLANTED FAULT") for ln in lines)
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] and not c["regret_mean"]
    assert c["finished_with_wrong_length"] and c["engine_step_failures"]
