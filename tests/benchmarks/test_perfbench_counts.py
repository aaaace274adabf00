"""counts.py against hand-worked numbers."""
import json
import os

import pytest

from bench_paths import BENCH
from harness import counts


def dims(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["dims"]


XL = "gpt3-1.3b-serve"
MED = "gpt2-medium-train"


@pytest.mark.parametrize("what, got, want, rel", [
    # 2 (K, V) x 24 layers x 2,048 x 2 bytes
    ("kv bytes a position",
     lambda: counts.kv_bytes_per_position(dims(XL)), 196_608, 0),
    # 12 x 2,048^2 a block: 3d^2 + d^2 + 4d^2 + 4d^2
    ("matrix parameters of a block",
     lambda: counts.block_matrix_params(dims(XL)), 50_331_648, 0),
    # 24 blocks (1.208 B) + the LM head 2,048 x 50,304 (0.103 B)
    ("parameters a token is multiplied by",
     lambda: counts.matmul_params(dims(XL)), 1_310_982_144, 0),
    # the same in bf16, plus biases and norms: 2.62 GB a decode step
    ("weight bytes a decode step",
     lambda: counts.step_weight_bytes(dims(XL)), 2.62e9, 0.002),
    # gpt2-medium: 24 x 12 x 1,024^2 + 1,024 x 50,304 = 0.354 B
    ("gpt2-medium multiplied parameters",
     lambda: counts.matmul_params(dims(MED)), 353_501_184, 0),
    # 6 x 0.3535 B + 12 x 24 x 1,024 x 1,024 = 2.42 GFLOP a token
    ("training FLOPs a token",
     lambda: counts.train_flops_per_token(dims(MED), 1024),
     6 * 353_501_184 + 301_989_888, 0),
    # x 8,192 tokens: 19.9 TFLOP a step
    ("training FLOPs a step",
     lambda: counts.train_step_flops(dims(MED), 8, 1024), 19.9e12, 0.005),
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
    t, which = counts.serve_least_seconds(
        d, peaks, tokens_emitted=tokens, live_positions=positions,
        prefill_tokens=prefill, num_slots=32)
    assert which == bound
    t_mem = (tokens / 32 * counts.step_weight_bytes(d)
             + positions * 196_608) / 819e9
    t_flop = 2 * 1_310_982_144 * (prefill + tokens) / 197e12
    assert t == pytest.approx(max(t_mem, t_flop))
    # one step of 32 tokens: 2.62 GB at 819 GB/s is 3.2 ms
    if not prefill:
        assert 3.2e-3 < t < 3.3e-3 + positions * 196_608 / 819e9
