#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
files of both are found by those names (``benchmarks/configs/``,
``benchmarks/traffic/``), and every per-layer metric by listing
``benchmarks/layer_metrics/``.  The configuration's file names two more
that sit beside it: its plain ``reference`` and its ``program`` file
(``configs/<name>.py``), which builds the program's model, lists the
leaves the benchmark seeds and counts the work an ideal chip must do.
So an architecture is a set of new files; the harness names no model.
The mix's ``kind`` picks the runner: ``open_loop`` and ``sessions``
serve, ``train_steps`` trains.

The run needs an accelerator with as many chips as the cell asks for;
with none it exits non-zero, names the platform and prints no result.
``--rehearse`` is the explicit CPU form (the configuration's tiny
``rehearse`` sizes, a two-second window): it prints ``platform: cpu`` and
its result line never says ``"correct": true``.

Beside the driver's four arguments, for defining and proving cells:
``--sweep 1.5,2,2.5`` runs an open-loop mix at several rates under one
set-up and prints one line per rate (no result line);
``--control <name>`` switches on a configuration's control (a lower
precision), whose result has to come out as not correct;
``--mix-override '<json>'`` and ``--config-override '<json>'`` lay values
over the mix and over the configuration;
``--dump-sources <file>`` writes what a traced run's reducers read
(spans, counters, the client's series, the reduced device trace).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared``: each number compared with its value and its limit, which
are also the last lines on standard error.
"""
from __future__ import annotations

import time

T_PROC0 = time.monotonic()   # set-up is counted from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
REHEARSAL_SECONDS = 2.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--mix-override", default="{}")
    ap.add_argument("--config-override", default="{}")
    ap.add_argument("--dump-sources", default=None)
    args = ap.parse_args(argv)
    args.sweep = ([float(x) for x in args.sweep.split(",")]
                  if args.sweep else None)
    args.mix_override = json.loads(args.mix_override)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.seconds = REHEARSAL_SECONDS

    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(1, ROOT)
    from harness import common
    manifest = common.load_manifest()
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    cell, cfg, mix_path = common.find_cell(manifest, args.workload,
                                           rehearse=args.rehearse)
    cfg = common.merged(cfg, json.loads(args.config_override))
    with open(mix_path) as f:
        mix = json.load(f)
    if args.rehearse:
        args.mix_override = common.merged(mix.get("rehearse", {}),
                                          args.mix_override)

    import jax
    dev = common.device_info()
    common.say(f"platform: {dev['platform']}")
    common.say(f"device_kind: {dev['kind']}")
    common.say(f"device_count: {dev['count']}")
    if args.rehearse:
        common.say("REHEARSAL on the CPU at tiny sizes: control flow "
                   "only, nothing about a chip")
    elif dev["platform"] == "cpu":
        sys.exit(f"benchmarks/run.py: no accelerator (JAX found platform "
                 f"'cpu', {dev['kind']}); this run does not downgrade. "
                 "--rehearse is the CPU rehearsal.")
    elif dev["count"] < cell["chips"]:
        sys.exit(f"benchmarks/run.py: cell {cell['name']} needs "
                 f"{cell['chips']} chips, JAX found {dev['count']}")
    common.say(f"compile_cache_dir: {common.enable_cache()}")
    common.say(f"versions: jax {jax.__version__}")

    if mix["kind"] == "train_steps":
        from harness import train as runner
    else:
        from harness import serve as runner
    result = runner.run(cell, cfg, mix_path, args, T_PROC0)
    if result is None:      # a sweep: its lines are printed already
        return
    if result.get("breakdown") is None:
        result.pop("breakdown", None)
    if args.rehearse:
        result["rehearsal_correct"] = result["correct"]
        result["correct"] = False
        result["rehearsal"] = True
    # each number compared beside its limit: the result's last key and
    # the last lines on standard error
    result["compared"] = result.pop("compared")
    common.say(f"total_wall_s: {time.monotonic() - T_PROC0:.1f}")
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    common.say(json.dumps(result))


if __name__ == "__main__":
    main()
