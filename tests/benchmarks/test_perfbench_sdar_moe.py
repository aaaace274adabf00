"""``sdar-30b-a3b-serve``: its file against the catalog row's config, the
counts of ``configs/sdar_moe_program.py`` against hand-worked numbers, the
seeded model it builds, and its control and planted fault at the
rehearsal's size."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import ROOT, config, manifest, run_cell
from harness import common, counts

NAME = "sdar-30b-a3b-serve"
CELL = NAME + ".gen-sessions"
cfg = config(NAME)
dims = cfg["dims"]
prog = common.load_program(cfg)
PEAKS = counts.peaks_for("TPU v5 lite")

# the catalog row's ``config`` (model-configs/architectures.jsonl,
# SDAR-30B-A3B-Chat), verbatim
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=32768, max_window_layers=48,
    mlp_only_layers=[], model_type="sdar_moe", moe_intermediate_size=768,
    norm_topk_prob=True, num_attention_heads=32, num_experts=128,
    num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
    rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
    sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=151936)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_are_untouched_but_for_the_depth(key):
    want = 7 if key == "num_hidden_layers" else PUBLISHED[key]
    assert cfg[key] == want and dims[key] == want


def test_only_the_depth_is_reduced_and_dims_are_the_file_s_own_keys():
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert all(cfg[k] == v for k, v in dims.items())
    assert set(dims) == set(PUBLISHED) | {"generation", "seeded"}
    # a power of two: the scaled gains are exact in bf16 and float32
    assert dims["seeded"] == {"q_norm_scale": 8.0}
    assert "seeded.q_norm_scale" in cfg["assumed"]["weights"]
    assert dims["generation"] == dict(
        block_length=4, denoising_steps=4, mask_token_id=151669,
        remasking_strategy="low_confidence_static")
    assert set(dims["generation"]) <= set(cfg["assumed"])
    entry, = [c for c in manifest()["configs"] if c["name"] == NAME]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert cfg["engine"] == dict(cfg["engine"], num_slots=32,
                                 max_seq_len=4096, kv_block_size=16,
                                 prefill_chunk=256)
    assert set(cfg["engine"]) == {"num_slots", "max_seq_len",
                                  "kv_block_size", "kv_blocks",
                                  "prefill_chunk"}


@pytest.mark.parametrize("what, got, want, rel", [
    # 7 layers x K and V of 4 x 128 x 2 bytes
    ("bytes a cached position",
     lambda: prog.row_bytes_per_position(dims), 14_336, 0),
    # 3 x 2,048 x 768 x 2 bytes
    ("bytes an expert", lambda: prog.expert_bytes(dims), 9_437_184, 0),
    # 2 x 2,048 x 4,096 + 2 x 2,048 x 512
    ("attention parameters a layer",
     lambda: prog.attention_params(dims), 18_874_368, 0),
    # attention 18,874,368 + router 262,144 + 128 x 4,718,592 + norms
    # 2 x 2,048 + 2 x 128
    ("parameters a layer", lambda: prog.layer_params(dims),
     623_120_640, 0),
    # 7 layers, embedding and head 2 x 311,164,928, the final norm
    ("parameters held", lambda: prog.total_params(dims),
     4_984_176_384, 0),
    # 7 x 19,136,512 of attention and router, 311,164,928 of head
    ("fixed bytes a pass",
     lambda: 2 * prog.fixed_step_params(dims), 0.890e9, 0.0005),
    # the fixed part and 7 layers x 8 experts x 4,718,592
    ("parameters a row is multiplied by",
     lambda: prog.active_params(dims), 7 * 56_885_248 + 311_164_928, 0),
    # 4 x 32 heads x 128
    ("attention operations a pair",
     lambda: prog.attention_flops_per_pair(dims), 16_384, 0),
    ("necessary passes a token", lambda: prog.passes_per_token(dims),
     1.0, 0),
])
def test_hand_worked(what, got, want, rel):
    assert got() == pytest.approx(want, rel=rel or 1e-12), what


def test_the_counts_are_of_four_passes_a_block_whatever_the_program_ran():
    """25 live lanes for 100 steps of the 5-pass program: 2,000 denoise
    and 500 commit lane-passes, 2,000 tokens of ~1,500 live positions,
    every step hitting 127 experts in each of 7 layers.  Necessary: 80
    steps' fixed bytes (2,000 tokens x 4 / 4 / 32 ... the 32 slots'
    worth), four fifths of the expert hits, every token's positions
    once."""
    hits = 100 * 7 * 127
    work = {"tokens_emitted": 2000, "num_slots": 32,
            "live_positions": 2000 * 1500, "prefill_tokens": 0,
            "counters": {"serving.moe_experts_hit": hits,
                         "serving.denoise_passes": 2000,
                         "serving.commit_passes": 500}}
    want = (2000 / 32 * 2 * prog.fixed_step_params(dims)
            + 0.8 * hits * 9_437_184 + 2000 * 1500 * 14_336) / 819e9
    assert prog.decode_least_seconds(cfg, PEAKS, work) \
        == pytest.approx(want, rel=1e-12)
    # a program that folds the commit into the next block's first pass
    # runs 80 steps for the same tokens and counts the same work
    fused = dict(work, counters={"serving.moe_experts_hit": 0.8 * hits,
                                 "serving.denoise_passes": 2000,
                                 "serving.commit_passes": 0})
    assert prog.decode_least_seconds(cfg, PEAKS, fused) \
        == pytest.approx(want, rel=1e-12)
    # a chunk run in the interval is taken to have hit every expert and
    # leaves the decode count; the serving count keeps it whole
    more = dict(work, counters=dict(
        work["counters"], **{"serving.moe_experts_hit": hits + 7 * 128,
                             "serving.prefill_chunks": 1}))
    assert prog.decode_least_seconds(cfg, PEAKS, more) \
        == pytest.approx(want, rel=1e-12)
    secs, bound = prog.serve_least_seconds(cfg, PEAKS, more)
    assert bound == "memory" and secs == pytest.approx(
        want + 7 * 128 * 9_437_184 / 819e9, rel=1e-12)
    # 200,000 fresh prompt tokens make the interval compute-bound; each
    # emitted token stands for 4 rows
    secs, bound = prog.serve_least_seconds(
        cfg, PEAKS, dict(work, prefill_tokens=200_000))
    assert bound == "compute" and secs == pytest.approx(
        (2 * prog.active_params(dims) * (200_000 + 4 * 2000)
         + 7 * 16_384 * 4 * 2000 * 1500) / 197e12, rel=1e-12)


def test_the_kernel_s_least_time_counts_every_pass():
    work = {"counters": {"serving.moe_experts_hit": 889.0,
                         "serving.moe_routed_pairs": 7 * 800.0}}
    assert prog.gmm_least_seconds(cfg, PEAKS, work) \
        == pytest.approx(889 * 9_437_184 / 819e9, rel=1e-12)


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
        scale = 8.0 if name.endswith("attn.q_norm.weight") else 1.0
        assert prog.leaf_scale(tiny["dims"], name) == scale
        assert np.array_equal(np.asarray(got),
                              np.asarray(want[name]) * scale)
    assert not list(model.named_buffers())
    assert (model.block_length, model.denoising_steps,
            model.mask_token_id) == (4, 4, 8191)


def test_the_cell_s_traffic_is_the_issue_s():
    with open(common.BENCH_DIR + "/traffic/gen-sessions.json") as f:
        mix = json.load(f)
    assert mix["kind"] == "sessions" and mix["system_prompt_len"] == 256
    assert mix["history_len"] == {"dist": "uniform", "min": 256,
                                  "max": 1024}
    assert mix["user_len"] == {"dist": "lognormal", "median": 96,
                               "sigma": 0.6, "min": 16, "max": 512}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 128, "max": 1024}
    assert mix["max_context"] == cfg["engine"]["max_seq_len"] == 4096
    assert mix["think_s"] == {"dist": "exponential", "mean": 2.0}
    assert mix["grace_s"] == 5
    cell, = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "gen-sessions", 1)
    reported = [m["name"] for g in ("end_to_end", "per_layer")
                for m in manifest()[g]
                if "workloads" not in m or CELL in m["workloads"]]
    theirs = [m["name"] for g in ("end_to_end", "per_layer")
              for m in manifest()[g] if "workloads" not in m
              or "kimi-vl-a3b-serve.doc-sessions" in m["workloads"]]
    assert reported == theirs


LONGER = json.dumps({
    "answer_len": {"dist": "uniform", "min": 24, "max": 32},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history_len": {"dist": "uniform", "min": 16, "max": 24}})
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
def test_a_skipped_commit_pass_is_not_correct(seed):
    """``planted_fault_commit.py`` (the chip's second upper reading
    comes from it): blocks whose cached K/V are their last denoise
    pass's are caught by the comparison the harness makes."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "benchmarks",
                                      "planted_fault_commit.py"),
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


# -- why the seeded query gains are scaled (assumed.weights) -------------

SMALL = dict(dims, hidden_size=128, num_attention_heads=2,
             num_key_value_heads=1, moe_intermediate_size=32,
             num_hidden_layers=2, num_experts=16, num_experts_per_tok=2,
             vocab_size=2048,
             generation=dict(dims["generation"], mask_token_id=2047))


def _distinct_answer_tokens(scale, seed):
    """Eight lanes of 150-300 random prompt tokens (a shared 64 first),
    32 answer tokens each, through the engine on the model ``build``
    makes at ``SMALL``: how many different tokens the 256 answers
    hold."""
    from paddle_tpu import monitor
    from paddle_tpu.serving import Engine
    small = dict(cfg, dtype="float32",
                 dims=dict(SMALL, seeded={"q_norm_scale": scale}))
    model = prog.build(small, seed)
    model.eval()
    eng = Engine(model, registry=monitor.StatRegistry(), num_slots=8,
                 max_seq_len=512, kv_block_size=16, kv_blocks=272,
                 prefill_chunk=64)
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, 2047, 64).tolist()
    reqs = [eng.submit(shared + rng.integers(
        1, 2047, int(rng.integers(150, 300))).tolist(), max_new_tokens=32)
        for _ in range(8)]
    eng.run_until_idle()
    return len({t for r in reqs for t in r.generated})


@pytest.mark.parametrize("seed", [1, 2])
def test_the_scaled_query_gains_make_a_step_s_rows_distinct(seed):
    """With the harness's gains of 1 + normal a head's scores have a
    standard deviation of ~1: every row averages its whole context,
    every masked row (all start from the one mask embedding) computes
    nearly the same vector and a handful of tokens is the answer
    everywhere, so a step hits few experts, how few drawn with the
    seed (the review of PR 35).  Scaled by ``seeded.q_norm_scale`` the
    scores are peaked over these contexts (sqrt(2 ln 300) = 3.4) and
    the answers differ.  Measured here: 8-14 against 149-152 distinct
    tokens of 256."""
    assert _distinct_answer_tokens(1.0, seed) <= 40
    assert _distinct_answer_tokens(dims["seeded"]["q_norm_scale"],
                                   seed) >= 100
