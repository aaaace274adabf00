"""``--rehearse`` end to end for every cell, as the driver would call the
command; and the refusals: no accelerator, no program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import ROOT, manifest, run_cell

CELLS = [w["name"] for w in manifest()["workloads"]]


def check_line(lines, workload, group):
    man = manifest()
    assert "platform: cpu" in lines
    assert not any('"correct": true' in ln for ln in lines)
    res = json.loads(lines[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert res["correct"] is False and res["rehearsal"] is True
    assert res["rehearsal_correct"] is True, lines[-12:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    wanted = {m["name"] for m in man[group]
              if workload in m.get("workloads", [workload])}
    got = set(res["metrics"])
    assert got <= wanted
    units = {m["name"]: m["unit"] for m in man[group]}
    for name, m in res["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float)
    return res, wanted


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_end_to_end(workload):
    rc, lines, err = run_cell(workload, "--rehearse", seed=2**31 + 5)
    assert rc == 0, err[-2000:]
    res, wanted = check_line(lines, workload, "end_to_end")
    assert set(res["metrics"]) == wanted      # every end-to-end metric
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert any(ln.startswith("compared ") and " limit " in ln
               for ln in lines)
    # each number compared, with its limit: the result's last key and
    # the last lines on standard error
    compared = res["compared"]
    assert list(res)[-1] == "compared" and len(compared) >= 4
    assert all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
    tail = err.strip().splitlines()[-len(compared):]
    assert [ln.split()[1].rstrip(":") for ln in tail] == list(compared)
    assert all(ln.startswith("compared ") and " limit " in ln
               for ln in tail)


def test_no_accelerator_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr and "cpu" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_benchmark_alone_without_the_program_fails(tmp_path):
    man = manifest()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in man["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "paddle_tpu" in proc.stderr
