"""The device trace (``*.xplane.pb``) reduced to what needs no program
names: when an operation ran on the device, which operations took the
time, and what the host was doing in the idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is one
whose name starts with ``/device:``; its operations are the events of
the line named ``XLA Ops`` (every event of the plane where no such line
exists); ``/device:CUSTOM:...`` planes are not chips.  Host spans are
the ``jax.profiler.TraceAnnotation`` events on the ``/host:CPU`` plane
whose names are in ``span_names``.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"


def find_trace(directory):
    files = glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def merge(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _device_lines(plane):
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    return ops or lines


def short_op(name):
    """An operation's name as the trace prints it, cut to what tells
    operations apart: ``%convert.266 = f32[65536,16,128]{...} convert(...)``
    becomes ``convert f32[65536,16,128]``, so the same operation of every
    layer adds up under one name."""
    m = re.match(r"%?([A-Za-z_\-]+)[.\d]* = \(?([a-z0-9]+\[[\d,]*\])?", name)
    if not m:
        return name[:80]
    return (m.group(1) + " " + (m.group(2) or "")).strip()


def _label(gap, host):
    """The narrowest host span that covers the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2.0
    best = None
    for s, e, name in host:
        if s <= mid < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "(no program span)"


def reduce(path, span_names=(), window_s=None, top=10):
    """{"busy_s", "window_s", "device_ops", "idle_gaps", "devices"}.

    ``busy_s`` is the union of the device's operation intervals,
    averaged over the device planes that ran anything; ``window_s`` is
    the traced window as the caller timed it (else the span from the
    first device event to the last).  ``device_ops`` are the ``top``
    operations by summed time, ``idle_gaps`` the idle time summed by the
    host span that covers each gap."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    names = set(span_names)
    host, per_device, op_time = [], [], {}
    for plane in data.planes:
        if re.match(r"/device:[A-Za-z]+:\d+$", plane.name):
            iv = []
            for line in _device_lines(plane):
                for ev in line.events:
                    d = ev.duration_ns
                    if d <= 0:
                        continue
                    iv.append((ev.start_ns, ev.start_ns + d))
                    op = short_op(ev.name)
                    op_time[op] = op_time.get(op, 0.0) + d
            if iv:
                per_device.append(merge(iv))
        elif plane.name.startswith("/host:") and names:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names and ev.duration_ns > 0:
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     ev.name))
    if not per_device:
        return {"busy_s": 0.0, "window_s": window_s or 0.0,
                "device_ops": [], "idle_gaps": [], "devices": 0}
    busy = sum(sum(e - s for s, e in m) for m in per_device) \
        / len(per_device) * 1e-9
    if window_s is None:
        window_s = (max(m[-1][1] for m in per_device)
                    - min(m[0][0] for m in per_device)) * 1e-9
    gaps = {}
    first = per_device[0]
    for (_, e0), (s1, _) in zip(first, first[1:]):
        label = _label((e0, s1), host)
        gaps[label] = gaps.get(label, 0.0) + (s1 - e0) * 1e-9
    n = len(per_device)
    ops = sorted(((k, v * 1e-9 / n) for k, v in op_time.items()),
                 key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "window_s": float(window_s),
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle],
            "devices": n}
