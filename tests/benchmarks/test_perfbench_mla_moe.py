"""``kimi-vl-a3b-serve``: its file against the published config, the
counts of ``configs/mla_moe_program.py`` against hand-worked numbers, the
seeded model it builds, and its control at the rehearsal's size."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import ROOT, config, manifest, run_cell
from harness import common, counts

NAME = "kimi-vl-a3b-serve"
CELL = NAME + ".doc-sessions"
cfg = config(NAME)
dims = cfg["dims"]
prog = common.load_program(cfg)
PEAKS = counts.peaks_for("TPU v5 lite")

PUBLISHED = dict(
    hidden_size=2048, num_attention_heads=16, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
    n_routed_experts=64, moe_intermediate_size=1408,
    num_experts_per_tok=6, n_shared_experts=2,
    routed_scaling_factor=2.446, scoring_func="sigmoid",
    topk_method="noaux_tc", intermediate_size=11264, vocab_size=163840,
    rope_theta=800000, first_k_dense_replace=1, rms_norm_eps=1e-5,
    max_position_embeddings=131072, q_lora_rank=None)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_widths_are_untouched(key):
    assert cfg[key] == PUBLISHED[key] and dims[key] == PUBLISHED[key]


def test_only_the_depth_is_reduced_and_dims_are_the_file_s_own_keys():
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 9
    assert cfg["published"] == {"num_hidden_layers": 27}
    assert all(cfg[k] == v for k, v in dims.items())
    entry, = [c for c in manifest()["configs"] if c["name"] == NAME]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


@pytest.mark.parametrize("what, got, want, rel", [
    # 9 layers x (512 + 64) x 2 bytes
    ("bytes a cached position",
     lambda: prog.row_bytes_per_position(dims), 10_368, 0),
    # 3 x 2,048 x 1,408 x 2 bytes
    ("bytes an expert", lambda: prog.expert_bytes(dims), 17_301_504, 0),
    # 2,048 x 3,072 + 2,048 x 576 + 512 x 4,096 + 2,048 x 2,048
    ("attention parameters a layer",
     lambda: prog.attention_params(dims), 13_762_560, 0),
    # embedding and head 671.1 M, the dense layer 83.0 M, 8 expert
    # layers of 584.8 M, and 44,032 of norms and correction biases
    ("parameters held", lambda: prog.total_params(dims),
     5_432_847_360, 0),
    # attention 9 x 13.76 M, dense feed-forward 69.2 M, shared experts
    # 8 x 17.3 M, routers 8 x 0.131 M, head 335.5 M: 668.07 M x 2 bytes
    ("fixed bytes a decode step",
     lambda: 2 * prog.fixed_step_params(dims), 1.336e9, 0.0005),
    # the fixed part and 8 layers x 6 experts x 8.65 M
    ("parameters a token is multiplied by",
     lambda: prog.active_params(dims), 1.0833e9, 0.0001),
    # 2 x 16 heads x (128 + 64 + 128)
    ("attention operations a pair",
     lambda: prog.attention_flops_per_pair(dims), 10_240, 0),
])
def test_hand_worked(what, got, want, rel):
    assert got() == pytest.approx(want, rel=rel or 1e-12), what


def test_decode_least_seconds_follows_the_experts_hit():
    """13 live rows of 5,500 positions, 46 experts hit in each of 8
    layers: 1.336 GB x 13/32 of a step + 368 x 17.3 MB + 0.74 GB over
    819 GB/s; a chunk run in the interval is taken to have hit all 512
    and leaves the decode count."""
    work = {"tokens_emitted": 13, "num_slots": 32,
            "live_positions": 13 * 5500, "prefill_tokens": 0,
            "counters": {"serving.moe_experts_hit": 368}}
    want = (2 * prog.fixed_step_params(dims) * 13 / 32
            + 368 * 17_301_504 + 13 * 5500 * 10_368) / 819e9
    assert prog.decode_least_seconds(cfg, PEAKS, work) \
        == pytest.approx(want, rel=1e-12)
    assert 0.0092 < want < 0.0094
    more = dict(work, counters={"serving.moe_experts_hit": 368 + 512,
                                "serving.prefill_chunks": 1})
    assert prog.decode_least_seconds(cfg, PEAKS, more) \
        == pytest.approx(want, rel=1e-12)
    secs, bound = prog.serve_least_seconds(cfg, PEAKS, more)
    assert bound == "memory" and secs == pytest.approx(
        want + 512 * 17_301_504 / 819e9, rel=1e-12)
    # 8,192 fresh prompt tokens make the interval compute-bound
    secs, bound = prog.serve_least_seconds(
        cfg, PEAKS, dict(work, prefill_tokens=8192))
    assert bound == "compute" and secs == pytest.approx(
        (2 * prog.active_params(dims) * (8192 + 13)
         + 9 * 10_240 * 13 * 5500) / 197e12, rel=1e-12)


def test_the_kernel_s_roofline_share_reads_it_by_name():
    """368 experts hit and 624 pairs: 6.37 GB over 819 GB/s (the
    operations, 10.8 GFLOP, are far below); over 12 ms of instructions
    named gmm that is 64.8%.  A program without the kernel or the
    counters (the parent) reports nothing and raises nothing."""
    from harness import reducers
    files = reducers.load_metric_files(
        common.BENCH_DIR + "/layer_metrics")
    work = {"counters": {"serving.moe_experts_hit": 368.0,
                         "serving.moe_routed_pairs": 624.0}}
    least = 368 * 17_301_504 / 819e9
    assert prog.gmm_least_seconds(cfg, PEAKS, work) \
        == pytest.approx(least, rel=1e-12)
    src = {"device": {"busy_s": 0.02, "by_name": {"XLA Ops": {
        "gmm": 0.012, "fusion f32[32,16]": 0.001}}},
        "work": work, "ctx": {"cfg": cfg, "peaks": PEAKS}}
    got = reducers.reduce_all(files, ["roofline_share.gmm"], src)
    assert got["roofline_share.gmm"]["value"] \
        == pytest.approx(100 * least / 0.012)
    src["device"]["by_name"]["XLA Ops"].pop("gmm")
    assert reducers.reduce_all(files, ["roofline_share.gmm"], src) == {}
    # a chunk of 1,536 pairs on 64 experts is still bound by memory
    heavy = {"counters": {"serving.moe_experts_hit": 64.0,
                          "serving.moe_routed_pairs": 1536.0}}
    assert prog.gmm_least_seconds(cfg, PEAKS, heavy) \
        == pytest.approx(64 * 17_301_504 / 819e9, rel=1e-12)


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


def test_the_cell_s_traffic_is_the_issue_s():
    with open(common.BENCH_DIR + "/traffic/doc-sessions.json") as f:
        mix = json.load(f)
    assert mix["kind"] == "sessions" and mix["system_prompt_len"] == 512
    assert mix["history_len"] == {"dist": "uniform", "min": 2048,
                                  "max": 6144}
    assert (mix["user_len"]["median"], mix["answer_len"]["median"]) \
        == (64, 96)
    assert mix["max_context"] == cfg["engine"]["max_seq_len"] == 8192
    assert mix["think_s"] == {"dist": "exponential", "mean": 3.0}
    cell, = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "doc-sessions", 1)


LONGER = json.dumps({
    "answer_len": {"dist": "uniform", "min": 24, "max": 32},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history_len": {"dist": "uniform", "min": 16, "max": 24}})
# every finished request (~2,000 served tokens on an idle machine): at
# 600 about one control run in ten met no token that int8 had moved
MORE = json.dumps({"check": {"tokens": 4000, "max_requests": 200}})


def compared(lines):
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in lines if ln.startswith("compared ")}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_int8_weights_is_not_correct(seed):
    args = ("--rehearse", "--mix-override", LONGER, "--config-override",
            MORE)
    rc, lines, err = run_cell(CELL, *args, "--control", "int8", seed=seed)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] or not c["regret_mean"]
    if seed == 1:   # the same run without the control is correct
        rc, lines, err = run_cell(CELL, *args, seed=seed)
        assert rc == 0, err[-2000:]
        assert json.loads(lines[-1])["rehearsal_correct"] is True
        assert all(compared(lines).values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_wrong_block_table_is_not_correct(seed):
    """``planted_fault.py`` (the upper reading of ``regret_max`` on the
    chip comes from it): a slot that now and then reads another's
    blocks is caught by the largest regret."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "benchmarks",
                                      "planted_fault.py"),
         "--period", "8", "--workload", CELL, "--seed", str(seed),
         "--seconds", "2", "--trace", "0", "--rehearse",
         "--mix-override", LONGER, "--config-override", MORE],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any(ln.startswith("PLANTED FAULT") for ln in lines)
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"]
    assert c["finished_with_wrong_length"] and c["engine_step_failures"]
