"""From spans, counters, the client's records and the device trace to
per-layer metrics.

A per-layer metric is a data file under ``benchmarks/layer_metrics/``
that names one of the reducer kinds below and its parameters.  The kinds
are generic over span, instant, counter and argument names, so a metric
over a new span is a new file, not new code.  A reducer that finds
nothing to read returns ``None`` and the harness leaves the metric out.

The sources a reducer sees (``src``):

``spans``     the program's own trace over the measured window, Catapult
              events (``ph`` "X" spans and "i" instants, ``ts``/``dur`` in
              microseconds) from ``Engine.chrome_trace()`` plus the
              harness's own spans (``train.step`` ...)
``counters``  ``delta`` (after minus before, over the window), ``peak``
              (highest of the once-a-second samples), ``profile_delta``
              (over the profiled interval) of the program's registry
``client``    what the load generator measured (lateness, first tokens)
``device``    the reduced device trace (``xplane.reduce``) or None; its
              ``by_name`` holds the summed seconds of every distinct
              name of the ``XLA Ops`` and ``XLA Modules`` lines
``work``      what the profiled interval asked of the chip (serving:
              ``tokens_emitted``, ``live_positions``, ``prefill_tokens``,
              ``num_slots``, ``counters`` = the registry's increase;
              training: ``steps``, ``batch``, ``seq_len``); the counts
              of the configuration's program file take it whole
``ctx``       the configuration (``cfg``), the device's ``peaks``,
              ``window_s``, ``memory_peak_bytes`` ...
"""
from __future__ import annotations

import json
import os
import re


def percentile(values, q):
    """Nearest-rank percentile of ``values`` (q in 0..100)."""
    if not values:
        return None
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 100)) - 1))
    return float(v[k])


def _spans(src, name, where=None):
    out = []
    for ev in src.get("spans") or ():
        if ev.get("ph") != "X" or ev.get("name") != name:
            continue
        args = ev.get("args") or {}
        if where and not all(_test(args.get(k), cond)
                             for k, cond in where.items()):
            continue
        out.append(ev)
    return out


def _test(value, cond):
    if value is None:
        return False
    if "min" in cond and value < cond["min"]:
        return False
    if "max" in cond and value > cond["max"]:
        return False
    if "eq" in cond and value != cond["eq"]:
        return False
    return True


def span_percentile(src, span, q, where=None, scale=1e-3):
    """Percentile of a span's duration (default unit ms)."""
    d = [ev["dur"] * scale for ev in _spans(src, span, where)]
    return percentile(d, q)


def span_arg_mean(src, span, arg, where=None):
    """Mean of one argument over a span's events."""
    v = [(ev.get("args") or {}).get(arg) for ev in _spans(src, span, where)]
    v = [x for x in v if x is not None]
    return sum(v) / len(v) if v else None


def span_rate(src, counter, span, where=None):
    """A counter's increase over the summed duration of a span: work per
    second of the time spent on it."""
    delta = (src["counters"].get("delta") or {}).get(counter)
    total = sum(ev["dur"] for ev in _spans(src, span, where)) * 1e-6
    if delta is None or total <= 0:
        return None
    return delta / total


def lifecycle_gap(src, start, end, key, q, scale=1e-3):
    """Percentile of the time from instant ``start`` to instant ``end``,
    paired by the argument ``key`` (default unit ms)."""
    t0, gaps = {}, []
    for ev in sorted((e for e in src.get("spans") or ()
                      if e.get("ph") == "i"), key=lambda e: e["ts"]):
        k = (ev.get("args") or {}).get(key)
        if k is None:
            continue
        if ev["name"] == start:
            t0.setdefault(k, ev["ts"])
        elif ev["name"] == end and k in t0:
            gaps.append((ev["ts"] - t0.pop(k)) * scale)
    return percentile(gaps, q)


def counter_delta(src, counter):
    return (src["counters"].get("delta") or {}).get(counter)


def counter_delta_ratio(src, num, den, scale=100.0):
    """sum(delta of ``num``) / sum(delta of ``den``), default in %."""
    d = src["counters"].get("delta") or {}
    if any(n not in d for n in list(num) + list(den)):
        return None
    bottom = sum(d[n] for n in den)
    if bottom <= 0:
        return None
    return scale * sum(d[n] for n in num) / bottom


def gauge_peak(src, gauge, over=None, scale=100.0):
    """Highest sample of a gauge, as a share of gauge ``over`` when
    given (default in %)."""
    peak = (src["counters"].get("peak") or {}).get(gauge)
    if peak is None:
        return None
    if over is None:
        return float(peak)
    total = (src["counters"].get("last") or {}).get(over)
    return scale * peak / total if total else None


def client_percentile(src, series, q):
    """Percentile of one of the load generator's own series."""
    return percentile((src.get("client") or {}).get(series) or [], q)


def memory_peak(src, scale=1e-9):
    b = (src.get("ctx") or {}).get("memory_peak_bytes")
    return None if b is None else b * scale


def xplane_idle(src):
    """1 - (union of device-operation intervals) / traced window, in %."""
    dev = src.get("device")
    if not dev or not dev.get("window_s"):
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def _least_seconds(src, least):
    """What the function ``least`` of the configuration's program file
    gives for the profiled interval's work, in seconds."""
    from . import common
    work = src.get("work")
    if not work:
        return None
    ctx = src["ctx"]
    t = getattr(common.load_program(ctx["cfg"]), least)(
        ctx["cfg"], ctx["peaks"], work)
    return t[0] if isinstance(t, tuple) else t      # (seconds, bound)


def roofline(src, work):
    """Least time the chip could take for the profiled interval's work
    (the program file's ``<work>_least_seconds``) over the device's busy
    time in that interval, in %."""
    dev = src.get("device")
    if not dev or dev.get("busy_s", 0) <= 0:
        return None
    least = _least_seconds(src, work + "_least_seconds")
    return None if not least else 100.0 * least / dev["busy_s"]


def _trace_time(src, line, match):
    """Summed device seconds of the names of ``line`` (``XLA Ops`` or
    ``XLA Modules``) that the expression ``match`` finds; None where
    there is no such name."""
    names = ((src.get("device") or {}).get("by_name") or {}).get(line)
    hit = [t for name, t in (names or {}).items() if re.search(match, name)]
    return sum(hit) if hit else None


def trace_time_share(src, line, match):
    """The device time of one program (``line`` "XLA Modules") or of one
    operation or kernel ("XLA Ops") over the device's busy time, in %."""
    t = _trace_time(src, line, match)
    if t is None or src["device"].get("busy_s", 0) <= 0:
        return None
    return 100.0 * t / src["device"]["busy_s"]


def trace_roofline(src, line, match, least):
    """Least time the chip could take for the profiled interval's work,
    as the function ``least`` of the program file counts it, over the
    device time of the program or kernel that does that work, in %."""
    t = _trace_time(src, line, match)
    if not t:
        return None
    ideal = _least_seconds(src, least)
    return None if not ideal else 100.0 * ideal / t


def rate_over_peak(src, rate_key, flops_per_unit_key):
    """An end-to-end rate times the operations a unit needs, over the
    chip's peak, in % (model-FLOPs utilisation)."""
    ctx = src["ctx"]
    rate = ctx.get(rate_key)
    if rate is None:
        return None
    return 100.0 * rate * ctx[flops_per_unit_key] \
        / (ctx["peaks"]["bf16_flops"] * ctx.get("chips", 1))


KINDS = {f.__name__: f for f in (
    span_percentile, span_arg_mean, span_rate, lifecycle_gap,
    counter_delta, counter_delta_ratio, gauge_peak, client_percentile,
    memory_peak, xplane_idle, roofline, rate_over_peak, trace_time_share,
    trace_roofline)}


def load_metric_files(directory):
    """Every per-layer metric's file, by listing the directory."""
    out = {}
    for fn in sorted(os.listdir(directory)):
        if fn.endswith(".json"):
            with open(os.path.join(directory, fn)) as f:
                m = json.load(f)
            if m["name"] + ".json" != fn:
                raise ValueError(f"{fn} holds metric {m['name']!r}")
            out[m["name"]] = m
    return out


def reduce_all(metric_files, wanted, src):
    """{name: {"value", "unit"}} for the ``wanted`` metrics that found
    something to read."""
    out = {}
    for name in wanted:
        m = metric_files[name]
        try:
            fn = KINDS[m["reducer"]]
        except KeyError:
            raise KeyError(f"metric {name}: no reducer kind "
                           f"{m['reducer']!r}") from None
        value = fn(src, **(m.get("params") or {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out
