"""``correct`` has to be able to come out false.

The controls (the nearest precision below the configuration's) at the
rehearsal's size, on three seeds each; and a whole run with the timed
path broken underneath: a token altered where the stream hands it out,
a training step that returns its state unchanged.  The broken-path runs
skip the harness's look for a chip and drive the rest of a run in this
process.
"""
import argparse
import json

import pytest

from bench_paths import manifest, run_cell
from harness import common

LONG = json.dumps({"prompt_len": {"dist": "fixed", "value": 8},
                   "output_len": {"dist": "fixed", "value": 48, "max": 48},
                   "rate_per_s": 6})
MORE = json.dumps({"check": {"tokens": 560, "max_requests": 12}})


def compared(lines):
    out = {}
    for ln in lines:
        if ln.startswith("compared "):
            name = ln.split()[1].rstrip(":")
            out[name] = ln.endswith(" ok")
    return out


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_int8_is_not_correct(seed):
    args = ("--rehearse", "--mix-override", LONG, "--config-override", MORE)
    rc, lines, err = run_cell("gpt3-1.3b-serve.chat", *args, "--control",
                              "int8", seed=seed)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] or not c["regret_mean"]
    if seed == 1:   # the same run without the control is correct
        rc, lines, err = run_cell("gpt3-1.3b-serve.chat", *args, seed=seed)
        assert rc == 0, err[-2000:]
        assert json.loads(lines[-1])["rehearsal_correct"] is True
        assert all(compared(lines).values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_bf16_is_not_correct(seed):
    rc, lines, err = run_cell("gpt2-medium-train.steps", "--rehearse",
                              "--control", "bf16", seed=seed)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["grad_norm_gap"] and not c["loss_gap_max"]


def _args(mix, **over):
    ns = argparse.Namespace(
        seed=5, seconds=2.0, trace=0, rehearse=True, control=None,
        sweep=None, dump_sources=None,
        mix_override=mix.get("rehearse", {}))
    vars(ns).update(over)
    return ns


def _cell(name):
    cell, cfg, mix_path = common.find_cell(manifest(), name, rehearse=True)
    with open(mix_path) as f:
        mix = json.load(f)
    return cell, cfg, mix_path, mix


def test_altered_token_is_not_correct(monkeypatch):
    import time
    from paddle_tpu.serving.stream import TokenStream
    from harness import serve
    real = TokenStream.feed

    def feed(self, tok, index):
        return real(self, (tok + 1) % 8192 if index == 2 else tok, index)
    monkeypatch.setattr(TokenStream, "feed", feed)
    cell, cfg, mix_path, mix = _cell("gpt3-1.3b-serve.chat")
    res = serve.run(cell, cfg, mix_path, _args(mix), time.monotonic())
    assert res["correct"] is False
    assert res["failed"] == 0 and res["attempted"] > 0


def test_step_that_keeps_its_state_is_not_correct(monkeypatch, capsys):
    import time
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.train_step import TrainStep
    from harness import train
    real = TrainStep.step

    def step(self, inputs, labels=()):
        kept = jax.tree_util.tree_map(jnp.copy,
                                      (self.params, self.opt_state))
        loss = real(self, inputs, labels)
        self.params, self.opt_state = kept
        return loss
    monkeypatch.setattr(TrainStep, "step", step)
    cell, cfg, mix_path, mix = _cell("gpt2-medium-train.steps")
    res = train.run(cell, cfg, mix_path, _args(mix), time.monotonic())
    assert res["correct"] is False
    c = compared(capsys.readouterr().out.splitlines())
    assert not c["grad_norm_gap"] and not c["update_norm_gap"]


def test_sound_runs_in_process_are_correct():
    import time
    from harness import serve, train
    cell, cfg, mix_path, mix = _cell("gpt3-1.3b-serve.sessions")
    assert serve.run(cell, cfg, mix_path, _args(mix),
                     time.monotonic())["correct"] is True
    cell, cfg, mix_path, mix = _cell("gpt2-medium-train.steps")
    assert train.run(cell, cfg, mix_path, _args(mix),
                     time.monotonic())["correct"] is True
