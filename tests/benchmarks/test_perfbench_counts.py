"""The table of peaks, the counts of ``configs/gpt2_program.py`` against
hand-worked numbers, and the seeded values of its leaves against a
recorded checksum."""
import hashlib

import numpy as np
import pytest

from bench_paths import config
from harness import common, counts


def dims(name):
    return config(name)["dims"]


XL = "gpt3-1.3b-serve"
MED = "gpt2-medium-train"
prog = common.load_program(config(XL))


def test_both_configurations_name_the_one_program_file():
    assert config(XL)["program"] == config(MED)["program"] == "gpt2_program"
    assert common.load_program(config(MED)) is prog


@pytest.mark.parametrize("what, got, want, rel", [
    # 2 (K, V) x 24 layers x 2,048 x 2 bytes
    ("kv bytes a position",
     lambda: prog.kv_bytes_per_position(dims(XL)), 196_608, 0),
    # 12 x 2,048^2 a block: 3d^2 + d^2 + 4d^2 + 4d^2
    ("matrix parameters of a block",
     lambda: prog.block_matrix_params(dims(XL)), 50_331_648, 0),
    # 24 blocks (1.208 B) + the LM head 2,048 x 50,304 (0.103 B)
    ("parameters a token is multiplied by",
     lambda: prog.matmul_params(dims(XL)), 1_310_982_144, 0),
    # the same in bf16, plus biases and norms: 2.62 GB a decode step
    ("weight bytes a decode step",
     lambda: prog.step_weight_bytes(dims(XL)), 2.62e9, 0.002),
    # gpt2-medium: 24 x 12 x 1,024^2 + 1,024 x 50,304 = 0.354 B
    ("gpt2-medium multiplied parameters",
     lambda: prog.matmul_params(dims(MED)), 353_501_184, 0),
    # 6 x 0.3535 B + 12 x 24 x 1,024 x 1,024 = 2.42 GFLOP a token
    ("training FLOPs a token",
     lambda: prog.train_flops_per_token(dims(MED), 1024),
     6 * 353_501_184 + 301_989_888, 0),
    # x 8,192 tokens: 19.9 TFLOP a step
    ("training FLOPs a step",
     lambda: prog.train_step_flops(dims(MED), 8, 1024), 19.9e12, 0.005),
])
def test_hand_worked(what, got, want, rel):
    assert got() == pytest.approx(want, rel=rel or 1e-12), what


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        counts.peaks_for("cpu")
    assert counts.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("tokens, positions, prefill, bound", [
    # 32 tokens, one decode step, short contexts: the weights bound it
    (32, 32 * 300, 0, "memory"),
    # a window that is nearly all prefill is bound by FLOPs
    (32, 32 * 300, 200_000, "compute"),
])
def test_serve_least_seconds(tokens, positions, prefill, bound):
    d = dims(XL)
    peaks = counts.peaks_for("TPU v5 lite")
    work = {"tokens_emitted": tokens, "live_positions": positions,
            "prefill_tokens": prefill, "num_slots": 32, "counters": {}}
    t, which = prog.serve_least_seconds(config(XL), peaks, work)
    assert which == bound
    t_mem = (tokens / 32 * prog.step_weight_bytes(d)
             + positions * 196_608) / 819e9
    t_flop = 2 * 1_310_982_144 * (prefill + tokens) / 197e12
    assert t == pytest.approx(max(t_mem, t_flop))
    # the decode program's share of the count is its memory side
    assert prog.decode_least_seconds(config(XL), peaks, work) == \
        pytest.approx(t_mem)
    # one step of 32 tokens: 2.62 GB at 819 GB/s is 3.2 ms
    if not prefill:
        assert 3.2e-3 < t < 3.3e-3 + positions * 196_608 / 819e9


def test_train_least_seconds_is_step_flops_over_peak():
    # 10 steps of 19.9 TFLOP at 197 TFLOP/s: 1.01 s
    t = prog.train_least_seconds(
        config(MED), counts.peaks_for("TPU v5 lite"),
        {"steps": 10, "batch": 8, "seq_len": 1024})
    assert t == pytest.approx(10 * 19.85e12 / 197e12, rel=0.01)


@pytest.mark.parametrize("seed, name, shape, digest", [
    # sha256 of the bfloat16 bytes, recorded from PR 26's weights.py
    # (commit 59b10cb) on the CPU, with the program imported as in every
    # run (it picks JAX's generator), before leaf_specs moved to the
    # program file: a leaf's values follow from the seed and its place
    # (2, 137 and 288 of 293) alone
    (7, "blocks.0.ln1.weight", (2048,), "2a7eb1d5a690ae27"),
    (7, "blocks.11.attn.qkv_proj.bias", (6144,), "a47c04e4ca3cb05e"),
    (7, "blocks.23.mlp.fc2.weight", (8192, 2048), "3d95c037fae2286b"),
    (2**31 + 5, "blocks.0.ln1.weight", (2048,), "7b5ce82fc2f213e5"),
    (2**31 + 5, "blocks.23.mlp.fc2.weight", (8192, 2048),
     "7968c8914b0ee275"),
])
def test_seeded_leaves_keep_their_values(seed, name, shape, digest):
    import paddle_tpu  # noqa: F401  (sets jax_default_prng_impl)
    specs = prog.leaf_specs(dims(XL))
    assert len(specs) == 293
    assert {n: (i, s) for i, (n, s, _) in enumerate(specs)}[name] == (
        {"blocks.0.ln1.weight": 2, "blocks.11.attn.qkv_proj.bias": 137,
         "blocks.23.mlp.fc2.weight": 288}[name], shape)
    w = common.seeded_weights(config(XL), seed, names=frozenset([name]))
    a = np.asarray(w[name])
    assert a.shape == shape and a.dtype.name == "bfloat16"
    assert hashlib.sha256(a.tobytes()).hexdigest()[:16] == digest
