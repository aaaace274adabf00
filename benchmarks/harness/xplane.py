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
import heapq
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


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


def op_key(name):
    """The name under which an operation's time is summed for the
    readers by name: a Pallas call is an instruction named after its
    kernel (``pallas_call(..., name="k")`` traces as ``%k.1 = ...
    custom-call(...), custom_call_target="tpu_custom_call"``) and goes
    under the kernel's own name, ``k``, whatever its shapes; every other
    operation under ``short_op``."""
    if 'custom_call_target="tpu_custom_call"' in name:
        m = re.match(r"%?([\w\-]+?)(?:\.\d+)* = ", name)
        if m:
            return m.group(1)
    return short_op(name)


def label_gaps(gaps, host):
    """For each ``(start, end)`` of ``gaps`` (sorted, disjoint) the name
    of the narrowest host span ``(start, end, name)`` that covers its
    middle (the first of ``host`` among equally narrow ones), by one
    sweep over both in time order."""
    order = sorted(range(len(host)), key=lambda i: host[i][0])
    open_, nxt, out = [], 0, []      # open_: heap of (width, index, end)
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2.0
        while nxt < len(order) and host[order[nxt]][0] <= mid:
            i = order[nxt]
            nxt += 1
            heapq.heappush(open_, (host[i][1] - host[i][0], i, host[i][1]))
        # a span that has ended stays ended for every later gap; one
        # that hides under a narrower open span is dropped when it
        # surfaces
        while open_ and open_[0][2] <= mid:
            heapq.heappop(open_)
        out.append(host[open_[0][1]][2] if open_ else "(no program span)")
    return out


def _by_name(raw, key, n):
    out = {}
    for name, ns in raw.items():
        k = key(name)
        out[k] = out.get(k, 0.0) + ns
    return {k: v * 1e-9 / n for k, v in out.items()}


def capture_window(host_window_s, extent_s):
    """{"window_s", "host_window_s", "extent_s"}: the window that
    ``busy_s`` is compared with is the larger of what the caller timed
    (None where it timed nothing) and the device events' extent."""
    host = None if host_window_s is None else float(host_window_s)
    return {"window_s": max(host or 0.0, extent_s), "host_window_s": host,
            "extent_s": extent_s}


def reduce(path, span_names=(), window_s=None, top=10):
    """{"busy_s", "window_s", "host_window_s", "extent_s", "device_ops",
    "idle_gaps", "devices", "by_name"}.

    ``busy_s`` is the union of the device's operation intervals,
    averaged over the device planes that ran anything.  ``extent_s`` is
    the span from the first device event's start to the last one's end
    over those planes (0 where none ran anything), ``host_window_s`` the
    caller's ``window_s`` as it timed it (None where it gave none), and
    ``window_s`` the larger of the two.  A caller reads its clock after
    ``start_trace`` returns and before it calls ``stop_trace``, so both
    its interval and the events' extent lie inside the capture and the
    larger is the better lower bound of it; the two are on different
    clocks, so neither is clipped to the other, and ``busy_s <= extent_s
    <= window_s`` whatever the host timed.  Where the device idles at
    the capture's edges the host's interval is the larger and that idle
    time still counts.  ``device_ops`` are the ``top``
    operations by summed time, ``idle_gaps`` the idle time summed by the
    host span that covers each gap.  ``by_name`` holds the summed
    seconds (averaged over the devices, as ``device_ops``) of every
    distinct name of two lines: ``"XLA Ops"`` under ``op_key`` and
    ``"XLA Modules"`` under the event's own name, which is the jitted
    program's (``jit_gpt_fused_decode(<fingerprint>)``).

    A name is a long string that thousands of events share, so each
    event pays a dictionary step and the expressions run once a distinct
    name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    names = set(span_names)
    host, per_device = [], []
    raw = {OPS_LINE: {}, MODULES_LINE: {}}   # event name -> summed ns
    for plane in data.planes:
        if re.match(r"/device:[A-Za-z]+:\d+$", plane.name):
            iv, ops = [], raw[OPS_LINE]
            for line in _device_lines(plane):
                for ev in line.events:
                    d = ev.duration_ns
                    if d <= 0:
                        continue
                    s, name = ev.start_ns, ev.name
                    iv.append((s, s + d))
                    ops[name] = ops.get(name, 0.0) + d
            if iv:
                per_device.append(merge(iv))
            mods = raw[MODULES_LINE]
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        name = ev.name
                        mods[name] = mods.get(name, 0.0) + ev.duration_ns
        elif plane.name.startswith("/host:") and names:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names and ev.duration_ns > 0:
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     ev.name))
    if not per_device:
        return {"busy_s": 0.0, **capture_window(window_s, 0.0),
                "device_ops": [], "idle_gaps": [], "devices": 0,
                "by_name": {}}
    busy = sum(sum(e - s for s, e in m) for m in per_device) \
        / len(per_device) * 1e-9
    extent = (max(m[-1][1] for m in per_device)
              - min(m[0][0] for m in per_device)) * 1e-9
    first = per_device[0]
    between = [(e0, s1) for (_, e0), (s1, _) in zip(first, first[1:])]
    gaps = {}
    for (e0, s1), label in zip(between, label_gaps(between, host)):
        gaps[label] = gaps.get(label, 0.0) + (s1 - e0) * 1e-9
    n = len(per_device)
    ops = sorted(_by_name(raw[OPS_LINE], short_op, n).items(),
                 key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, **capture_window(window_s, extent),
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle],
            "devices": n,
            "by_name": {
                OPS_LINE: _by_name(raw[OPS_LINE], op_key, n),
                MODULES_LINE: _by_name(raw[MODULES_LINE], str, n)}}
