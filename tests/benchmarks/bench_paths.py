"""Shared by the benchmark's CPU tests: where things are, and how to run
the one command."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name):
    """The configuration file ``benchmarks/configs/<name>.json``."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def run_cell(workload, *extra, root=ROOT, seed=7, trace=0, timeout=600):
    """Run the benchmark's command as the driver would (plus ``extra``)
    from ``root``; returns (exit code, stdout lines, stderr)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # a worker of the test run may hold forced-device flags the child
    # has no use for
    env.pop("XLA_FLAGS", None)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cmd = json.load(f)["command"]
    proc = subprocess.run(
        cmd + ["--workload", workload, "--seed", str(seed), "--seconds",
               "2", "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
