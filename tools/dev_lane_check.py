"""Hold the engine's ``dev.*`` lane against a device trace.

The engine's device watcher times every dispatch on the host's clock
(``dev.decode`` / ``dev.prefill`` spans, ``serving.dev_busy_ms``).  The
truth for those is the profiler's device plane: the ``XLA Modules``
line has one event per executed program, named after its
``_compile_probe`` kind (``jit_gpt_fused_decode(...)``).  Given the
sources a traced benchmark run dumped (``benchmarks/run.py --trace 1
--dump-sources src.json``: the engine's spans, the counters' change
over the profiled interval, the reduced device trace) and the
``.xplane.pb`` the same run wrote, this prints one JSON object:

``programs``  per program: the median ``dev.*`` duration, the median
              module duration, the median and 90th percentile of
              |difference| over the pairs matched by nearest end, and of
              ``offset_ms`` = dev completion minus module end (watcher
              wake-up plus the profiler's host-to-device plane offset)
``busy``      change of ``serving.dev_busy_ms`` over the profiled
              interval against the trace's ``busy_s`` (the counter
              moves when a program completes, by its whole duration, so
              this pair differs by up to one program at either edge),
              and the ``dev.*`` spans clipped to the interval from the
              first module's start to the last module's end against the
              modules' own summed time there
``modules``   every module name seen on the device, with its count

The two clocks are aligned on the ``tick`` spans, which both sides hold
(``trace_annotations=True`` puts the engine's spans on the profiler's
host plane).

Usage:
    python tools/dev_lane_check.py src.json path/to/x.xplane.pb
"""
from __future__ import annotations

import bisect
import json
import re
import statistics
import sys

MODULES_LINE = "XLA Modules"


def percentile(values, q):
    v = sorted(values)
    return v[max(0, min(len(v) - 1, -(-q * len(v) // 100) - 1))]


def read_xplane(path):
    """({module name: [(start_us, end_us)]}, [tick start_us]) from the
    first device plane and the host planes."""
    from jax.profiler import ProfileData
    modules, ticks = {}, []
    seen_device = False
    for plane in ProfileData.from_file(path).planes:
        if re.match(r"/device:[A-Za-z]+:\d+$", plane.name):
            if seen_device:
                continue
            seen_device = True
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    modules.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-3,
                         (ev.start_ns + ev.duration_ns) * 1e-3))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ticks += [ev.start_ns * 1e-3 for ev in line.events
                          if ev.name == "tick"]
    return modules, sorted(ticks)


def clock_offset(xplane_ticks, trace_ticks):
    """Profiler clock minus ``perf_counter`` clock, in us: the shift of
    the tick sequences under which they agree best."""
    best = None
    for k in range(len(trace_ticks) - len(xplane_ticks) + 1):
        d = [x - t for x, t in zip(xplane_ticks, trace_ticks[k:])]
        spread = max(d) - min(d)
        if best is None or spread < best[0]:
            best = (spread, statistics.median(d))
    if best is None:
        raise SystemExit("fewer tick spans in the engine's trace than "
                         "in the device trace: nothing to align on")
    return best[1], best[0]


def check(src, xplane_path):
    modules, xticks = read_xplane(xplane_path)
    spans = src["spans"]
    tticks = sorted(e["ts"] for e in spans
                    if e["ph"] == "X" and e["name"] == "tick")
    off, off_spread = clock_offset(xticks, tticks)
    out = {"clock_offset_spread_us": off_spread, "programs": {},
           "modules": {k: len(v) for k, v in sorted(modules.items())}}
    dev = [e for e in spans if e["ph"] == "X" and e.get("cat") == "device"]
    lo = min(s for v in modules.values() for s, _ in v) - off
    hi = max(e for v in modules.values() for _, e in v) - off
    for program in sorted({e["args"]["program"] for e in dev}):
        name = next((m for m in modules if "gpt_" + program + "(" in m
                     or m.endswith("gpt_" + program)), None)
        mine = [e for e in dev if e["args"]["program"] == program
                and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
        if name is None or not mine:
            continue
        ends = sorted((e - off, e - s) for s, e in modules[name])
        keys = [x[0] for x in ends]
        diff, offset, mod_d = [], [], []
        for e in mine:
            done = e["ts"] + e["dur"]
            i = bisect.bisect_left(keys, done)
            j = min((j for j in (i - 1, i) if 0 <= j < len(ends)),
                    key=lambda j: abs(keys[j] - done))
            diff.append(abs(e["dur"] - ends[j][1]) * 1e-3)
            offset.append((done - keys[j]) * 1e-3)
            mod_d.append(ends[j][1] * 1e-3)
        out["programs"][program] = {
            "span": mine[0]["name"], "module": name, "pairs": len(mine),
            "dev_p50_ms": statistics.median(e["dur"] for e in mine) * 1e-3,
            "module_p50_ms": statistics.median(mod_d),
            "abs_diff_p50_ms": statistics.median(diff),
            "abs_diff_p90_ms": percentile(diff, 90),
            "offset_p50_ms": statistics.median(offset),
            "offset_p90_ms": percentile(offset, 90)}
    pd = src["counters"].get("profile_delta") or {}
    clipped = sum(max(0.0, min(e["ts"] + e["dur"], hi) - max(e["ts"], lo))
                  for e in dev)
    out["busy"] = {
        "dev_busy_ms_delta": pd.get("serving.dev_busy_ms"),
        "trace_busy_ms": src["device"]["busy_s"] * 1e3,
        "profiled_ms": src["device"]["window_s"] * 1e3,
        "dev_spans_clipped_ms": clipped * 1e-3,
        "modules_ms": sum(e - s for v in modules.values()
                          for s, e in v) * 1e-3,
        "first_to_last_module_ms": (hi - lo) * 1e-3}
    return out


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        raise SystemExit(__doc__)
    with open(argv[0]) as f:
        src = json.load(f)
    print(json.dumps(check(src, argv[1]), indent=1))


if __name__ == "__main__":
    main()
