"""Terminal trace inspector: per-phase time table from a chrome trace.

chrome://tracing is the full viewer, but most "where did tick N's 11 ms
go" questions only need aggregates — this prints, per span name, the
count / total / mean / p50 / p99 duration over every complete-event in
a trace file (a ``/debug/trace`` download, a flight-recorder dump, or
a ``stop_profiler(profile_path=...)`` export), so traces are
inspectable over ssh with nothing but Python.

``--wall`` adds the host by thread: the CPU time of the spans that
carry ``cpu_ms`` (the tick, its phases, the HTTP edge) beside their
wall time, and the CPU a wall-second of the tick's thread, the edge's,
the device watcher's and the rest of the process.  The file may also
be a benchmark run's ``--dump-sources`` dump, whose counters fill all
four rows.

Usage:
    python tools/trace_view.py trace.json [--cat serving] [--sort total]
"""
from __future__ import annotations

import argparse
import json
import sys


def _percentile(sorted_vals, q):
    """Nearest-rank-with-interpolation percentile over a SORTED list
    (numpy 'linear' semantics — no numpy dependency here: the tool
    must run anywhere a trace file lands)."""
    if not sorted_vals:
        return float("nan")
    if len(sorted_vals) == 1:
        return sorted_vals[0]
    rank = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = rank - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def summarize(events, cat=None):
    """Aggregate complete-events (``ph == "X"``) by name.  Returns rows
    of dicts: name, count, total_ms, mean_ms, p50_ms, p99_ms — sorted
    by total descending — and, for a name whose spans carry ``cpu_ms``
    (opened with ``cpu=True``; the engine's ``tick``), ``cpu_ms``: the
    thread CPU time summed, and ``wait_ms``: the rest of their summed
    wall time, in which the thread stood still (the ``tick`` span says
    its own: what is left of ``host_ms``, its waits for the device
    taken out).  Sums, because a coarse CPU clock (10 ms steps on some
    kernels) makes one span's ``cpu_ms`` 0 or a whole step.  Both are
    None where no span of the name has the arg."""
    groups, cpu, wait = {}, {}, {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        if cat is not None and ev.get("cat") != cat:
            continue
        name = ev["name"]
        dur = float(ev.get("dur", 0.0)) / 1e3  # us -> ms
        groups.setdefault(name, []).append(dur)
        args = ev.get("args") or {}
        if "cpu_ms" in args:
            cpu[name] = cpu.get(name, 0.0) + args["cpu_ms"]
            wait[name] = wait.get(name, 0.0) + args.get(
                "wait_ms", dur - args["cpu_ms"])
    rows = []
    for name, durs in groups.items():
        durs.sort()
        rows.append({
            "name": name, "count": len(durs),
            "total_ms": sum(durs),
            "mean_ms": sum(durs) / len(durs),
            "p50_ms": _percentile(durs, 50),
            "p99_ms": _percentile(durs, 99),
            "cpu_ms": cpu.get(name),
            "wait_ms": max(wait[name], 0.0) if name in wait else None,
        })
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def format_table(rows, cpu=False):
    """The per-span table; ``cpu`` adds the ``cpu(ms)`` and
    ``wait(ms)`` columns (``-`` for spans that read no CPU clock)."""
    head = (f"{'span':<28} {'count':>7} {'total(ms)':>11} "
            f"{'mean(ms)':>10} {'p50(ms)':>10} {'p99(ms)':>10}")
    if cpu:
        head += f" {'cpu(ms)':>11} {'wait(ms)':>11}"
    lines = [head]
    for r in rows:
        line = (
            f"{r['name']:<28} {r['count']:>7} {r['total_ms']:>11.3f} "
            f"{r['mean_ms']:>10.3f} {r['p50_ms']:>10.3f} "
            f"{r['p99_ms']:>10.3f}")
        if cpu:
            line += "".join(
                f" {'-':>11}" if r.get(k) is None else f" {r[k]:>11.3f}"
                for k in ("cpu_ms", "wait_ms"))
        lines.append(line)
    return "\n".join(lines)


def cpu_by_group(events, counters=None, window_s=None):
    """CPU time a wall-second by thread group: the tick's thread
    (inside ``host_ms``), the HTTP edge's handler threads
    (``http.ingest`` + ``http.stream``), the device watcher's, and the
    rest of the process (the runtime's own threads, which hold no
    interpreter lock, plus whatever the first three spend outside
    their spans).  The first three share ONE interpreter: their sum
    near 1,000 ms a second says it is saturated; a process total that
    falls while the tick's ``wait_ms`` rises says the machine is.

    From ``counters`` (a window's deltas of ``serving.tick_cpu_ms``,
    ``serving.http_cpu_ms``, ``serving.dev_watch_cpu_ms`` and
    ``process.cpu_ms``: a ``--dump-sources`` dump has them) over
    ``window_s``; else from the spans' ``cpu_ms`` over the time the
    ``tick`` spans cover, where the last two rows cannot be known.
    Returns ``{"wall_s", "rows": [(group, ms a second or None)]}``, or
    None where nothing carries a CPU time."""
    if counters and window_s and "serving.tick_cpu_ms" in counters:
        tick = counters["serving.tick_cpu_ms"]
        edge = counters.get("serving.http_cpu_ms", 0.0)
        watch = counters.get("serving.dev_watch_cpu_ms", 0.0)
        proc = counters.get("process.cpu_ms")
        rest = None if proc is None else proc - tick - edge - watch
        wall_s = float(window_s)
    else:
        tick = edge = 0.0
        t_lo, t_hi, seen = float("inf"), float("-inf"), False
        for ev in events:
            if ev.get("ph") != "X":
                continue
            name = ev.get("name")
            if name == "tick":
                t_lo = min(t_lo, float(ev["ts"]))
                t_hi = max(t_hi, float(ev["ts"]) + float(ev.get("dur", 0)))
            c = (ev.get("args") or {}).get("cpu_ms")
            if c is None:
                continue
            if name == "tick":
                tick, seen = tick + c, True
            elif name in ("http.ingest", "http.stream"):
                edge, seen = edge + c, True
        if not seen or t_hi <= t_lo:
            return None
        watch = rest = None
        wall_s = (t_hi - t_lo) / 1e6
    return {"wall_s": wall_s, "rows": [
        (group, None if ms is None else ms / wall_s)
        for group, ms in (("tick", tick), ("edge", edge),
                          ("watcher", watch), ("rest of process", rest))]}


def format_cpu_groups(g):
    lines = [f"CPU by thread group, ms a wall-second over "
             f"{g['wall_s']:.3f} s (tick + edge + watcher share one "
             "interpreter):"]
    for group, ms in g["rows"]:
        lines.append(f"  {group:<26} " + (
            f"{'-':>11}   (needs the counters of a dump)" if ms is None
            else f"{ms:>11.3f}"))
    return "\n".join(lines)


def wall_summary(events):
    """Per-tick wall time vs summed phase time.  The span table above
    sums every complete-event independently, which silently
    DOUBLE-COUNTS concurrent spans — with the async engine loop, host
    phases (``host.overlap``) run while the device computes, so the
    per-phase totals legitimately exceed wall time.  This summary
    makes that divergence explicit: ``wall_ms`` is the summed duration
    of the ``tick`` spans, ``phase_ms`` the summed duration of every
    other complete-event, ``overlap_ms``/``d2h_wait_ms`` the async
    loop's own attribution spans.  phase/wall > 1 means concurrency
    (work hidden behind device compute), not an accounting bug."""
    wall = phase = overlap = d2h_wait = 0.0
    ragged_stream = 0.0
    kv_blocks_walked = 0
    allgather = shard_sync = 0.0
    mig_export = mig_wire = mig_import = 0.0
    sup_restart = drain_mig = dequant = 0.0
    lora_swap = stream_emit = 0.0
    off_demote = off_promote = 0.0
    n_ticks = n_ragged_stream = n_allgather = 0
    n_migrations = 0
    n_restarts = n_drain_migs = n_dequants = 0
    n_lora_swaps = n_stream_emits = 0
    n_off_demotes = n_off_promotes = 0
    state_patch = state_push = 0.0
    n_patches = n_first_picks = n_pushes = 0
    ring_drains = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat") == "device":
            continue  # the device lane is not a host phase: see
            #           device_summary
        dur = float(ev.get("dur", 0.0)) / 1e3  # us -> ms
        name = ev.get("name")
        if name == "tick":
            n_ticks += 1
            wall += dur
        else:
            phase += dur
            if name == "host.overlap":
                overlap += dur
            elif name == "decode.d2h_wait":
                d2h_wait += dur
            elif name == "migrate.export":
                # KV block migration legs, broken out per side:
                # export = device->host gather on the source,
                # wire = payload encode/decode in transit,
                # import = host->device scatter + trie adoption on
                # the destination — together, the stream's total
                # off-accelerator time during a migration
                mig_export += dur
                n_migrations += 1
            elif name == "migrate.wire":
                mig_wire += dur
            elif name == "migrate.import":
                mig_import += dur
            elif name == "decode.ragged_stream":
                # Pallas ragged-paged-attention dispatches
                # (Engine(attn_impl="ragged")) — broken out so a trace
                # shows at a glance whether the kernel or the
                # per-shape XLA programs (decode.dispatch) served it;
                # the span's kv_blocks_walked arg sums each lane's
                # causal horizon, so block-walk cost is attributable
                # per tick
                ragged_stream += dur
                n_ragged_stream += 1
                kv_blocks_walked += int(
                    ev.get("args", {}).get("kv_blocks_walked", 0))
            elif name == "decode.allgather":
                # mesh-sharded engines (Engine(mesh=...)): waiting on
                # the cross-shard psum/all-gather collectives before
                # the tiny replicated d2h — THE tensor-parallel tax,
                # visible per trace instead of smeared into d2h_wait
                allgather += dur
                n_allgather += 1
            elif name == "shard.sync":
                # replicating dirtied cursors/tables to every shard
                shard_sync += dur
            elif name == "supervisor.restart":
                # self-healing fleet legs: restart = the supervisor
                # respawning a dead/wedged replica (boot wait
                # excluded — it only covers the spawn), drain.migrate
                # = a SIGTERM'd replica shipping one live stream to a
                # peer over the migration wire
                sup_restart += dur
                n_restarts += 1
            elif name == "drain.migrate":
                drain_mig += dur
                n_drain_migs += 1
            elif name == "lora.swap":
                # multi-adapter serving: hot-load/unload of a LoRA
                # lane (ring drain + bank .at[lane].set) — the cost
                # of changing the adapter inventory WITHOUT a
                # recompile, visible per swap instead of smeared
                # into the tick gaps
                lora_swap += dur
                n_lora_swaps += 1
            elif name == "stream.emit":
                # token streaming: per-token fan-out from the tick
                # loop to attached SSE sinks — the engine-side cost
                # of live delivery (zero when nobody streams)
                stream_emit += dur
                n_stream_emits += 1
            elif name == "offload.demote":
                # host-RAM KV tier (Engine(kv_host_mb=...)): demote =
                # materializing an evicted block's async gather into
                # the host store at a tick boundary, promote = the
                # admission-gate restore (host payload scattered into
                # fresh device blocks instead of recomputed) — the
                # d2h/h2d price of the second tier, per transfer
                off_demote += dur
                n_off_demotes += 1
            elif name == "offload.promote":
                off_promote += dur
                n_off_promotes += 1
            elif name == "state.patch":
                # how the device-resident step state follows the
                # host: per-slot patch programs queued behind the
                # decodes in flight (admission, chunk progress,
                # eviction) and the first-token picks queued with
                # them, beside the whole uploads (state.push: one a
                # healthy run) and what still consumed the ring to
                # empty, by its reason
                state_patch += dur
                if "first_token" in ev.get("args", {}):
                    n_first_picks += 1
                else:
                    n_patches += 1
            elif name == "state.push":
                state_push += dur
                n_pushes += 1
            elif name == "ring.drain":
                why = ev.get("args", {}).get("why", "?")
                ring_drains[why] = ring_drains.get(why, 0) + 1
            elif name == "decode.dequant":
                # int8-KV engines (Engine(kv_dtype="int8")): the
                # host-side attribution span of a QUANTIZED dispatch
                # — gather-side dequant rides inside the compiled
                # program, so this is the per-tick cost of serving
                # codes+scales instead of fp blocks, nested inside
                # decode.dispatch/decode.ragged_stream (double-counted in
                # phase_ms like every nested span)
                dequant += dur
                n_dequants += 1
    return {
        "ticks": n_ticks, "wall_ms": wall, "phase_ms": phase,
        "per_tick_wall_ms": wall / n_ticks if n_ticks else float("nan"),
        "per_tick_phase_ms": (phase / n_ticks if n_ticks
                              else float("nan")),
        "overlap_ms": overlap, "d2h_wait_ms": d2h_wait,
        "ragged_stream_ms": ragged_stream,
        "ragged_stream_dispatches": n_ragged_stream,
        "kv_blocks_walked": kv_blocks_walked,
        "allgather_ms": allgather, "allgather_waits": n_allgather,
        "shard_sync_ms": shard_sync,
        "migrations": n_migrations,
        "migrate_export_ms": mig_export,
        "migrate_wire_ms": mig_wire,
        "migrate_import_ms": mig_import,
        "supervisor_restarts": n_restarts,
        "supervisor_restart_ms": sup_restart,
        "drain_migrations": n_drain_migs,
        "drain_migrate_ms": drain_mig,
        "dequant_ms": dequant,
        "dequant_dispatches": n_dequants,
        "lora_swap_ms": lora_swap,
        "lora_swaps": n_lora_swaps,
        "stream_emit_ms": stream_emit,
        "stream_emits": n_stream_emits,
        "offload_demote_ms": off_demote,
        "offload_demotes": n_off_demotes,
        "offload_promote_ms": off_promote,
        "offload_promotes": n_off_promotes,
        "state_patch_ms": state_patch,
        "state_patches": n_patches,
        "first_token_picks": n_first_picks,
        "state_push_ms": state_push,
        "state_pushes": n_pushes,
        "ring_drains": ring_drains,
    }


def device_summary(events):
    """The device lane against the host's: the engine's ``dev.*``
    spans (``cat == "device"``) are its dispatches as the device
    completed them, on the clock of every host span, so a hole between
    two of them is the device idle — and the narrowest host span open
    over the hole's middle says what the host was doing meanwhile
    ("why was the device idle").  Returns None without a device lane,
    else busy/idle totals (ms), per-span-name busy time, and the idle
    time summed by host span, largest first."""
    dev, host = [], []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        iv = (float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0)),
              ev.get("name"))
        (dev if ev.get("cat") == "device" else host).append(iv)
    if not dev:
        return None
    dev.sort()
    host.sort()
    busy = {}
    for s, e, name in dev:
        n, ms = busy.get(name, (0, 0.0))
        busy[name] = (n + 1, ms + (e - s) / 1e3)
    idle, open_spans, nxt = {}, [], 0
    for (_, e0, _), (s1, _, _) in zip(dev, dev[1:]):
        if s1 <= e0:
            continue
        mid = (e0 + s1) / 2.0
        while nxt < len(host) and host[nxt][0] <= mid:
            open_spans.append(host[nxt])
            nxt += 1
        open_spans = [h for h in open_spans if h[1] > mid]
        label = (min(open_spans, key=lambda h: h[1] - h[0])[2]
                 if open_spans else "(no host span)")
        idle[label] = idle.get(label, 0.0) + (s1 - e0) / 1e3
    return {
        "span_ms": (dev[-1][1] - dev[0][0]) / 1e3,
        "busy_ms": sum(ms for _, ms in busy.values()),
        "idle_ms": sum(idle.values()),
        "busy": dict(sorted(busy.items())),
        "idle_by_host_span": sorted(idle.items(),
                                    key=lambda kv: -kv[1]),
    }


def format_device(d, top=8):
    parts = ", ".join(f"{name} {ms:.3f} ms over {n}"
                      for name, (n, ms) in d["busy"].items())
    share = 100.0 * d["idle_ms"] / d["span_ms"] if d["span_ms"] else 0.0
    lines = [
        f"device lane: busy {d['busy_ms']:.3f} ms ({parts})",
        f"device idle {d['idle_ms']:.3f} ms of {d['span_ms']:.3f} ms "
        f"({share:.1f}%), by the host span open meanwhile:"]
    lines += [f"  {name:<26} {ms:>11.3f} ms"
              for name, ms in d["idle_by_host_span"][:top]]
    return "\n".join(lines)


def format_wall(w):
    lines = [
        f"ticks: {w['ticks']}   wall {w['wall_ms']:.3f} ms   "
        f"summed phases {w['phase_ms']:.3f} ms",
        f"per tick: wall {w['per_tick_wall_ms']:.3f} ms vs phases "
        f"{w['per_tick_phase_ms']:.3f} ms",
        f"host.overlap {w['overlap_ms']:.3f} ms   "
        f"decode.d2h_wait {w['d2h_wait_ms']:.3f} ms",
    ]
    if w.get("state_patches") or w.get("state_pushes") \
            or w.get("first_token_picks") or w.get("ring_drains"):
        drains = ", ".join(f"{why} {n}" for why, n in
                           sorted(w.get("ring_drains", {}).items()))
        lines.append(
            f"state.patch {w.get('state_patch_ms', 0.0):.3f} ms over "
            f"{w.get('state_patches', 0)} slot patch(es) and "
            f"{w.get('first_token_picks', 0)} first-token pick(s)   "
            f"state.push {w.get('state_push_ms', 0.0):.3f} ms over "
            f"{w.get('state_pushes', 0)} whole upload(s)   "
            f"ring.drain by why: {drains or 'none'}")
    if w.get("ragged_stream_dispatches"):
        per = (w["kv_blocks_walked"] / w["ragged_stream_dispatches"]
               if w["ragged_stream_dispatches"] else 0.0)
        lines.append(
            f"decode.ragged_stream {w['ragged_stream_ms']:.3f} ms "
            f"over {w['ragged_stream_dispatches']} streaming "
            "online-softmax dispatches (attn_impl='ragged')   "
            f"kv blocks walked {w['kv_blocks_walked']} "
            f"({per:.1f}/tick)")
    if w.get("allgather_waits") or w.get("shard_sync_ms"):
        lines.append(
            f"decode.allgather {w['allgather_ms']:.3f} ms over "
            f"{w['allgather_waits']} sharded ticks   shard.sync "
            f"{w['shard_sync_ms']:.3f} ms (mesh-sharded engine: "
            "cross-shard collective wait + cursor replication)")
    if w.get("migrations") or w.get("migrate_import_ms") \
            or w.get("migrate_wire_ms"):
        lines.append(
            f"migrate.export {w['migrate_export_ms']:.3f} ms over "
            f"{w['migrations']} migration(s)   migrate.wire "
            f"{w['migrate_wire_ms']:.3f} ms   migrate.import "
            f"{w['migrate_import_ms']:.3f} ms (KV block migration: "
            "source gather / payload transit / destination adopt)")
    if w.get("dequant_dispatches"):
        lines.append(
            f"decode.dequant {w['dequant_ms']:.3f} ms over "
            f"{w['dequant_dispatches']} quantized dispatches "
            "(kv_dtype='int8': in-program dequant of int8 "
            "codes+scales at gather)")
    if w.get("lora_swaps"):
        lines.append(
            f"lora.swap {w['lora_swap_ms']:.3f} ms over "
            f"{w['lora_swaps']} adapter swap(s) (hot-load/unload "
            "into a bank lane: ring drain + .at[lane].set, zero "
            "recompiles)")
    if w.get("stream_emits"):
        lines.append(
            f"stream.emit {w['stream_emit_ms']:.3f} ms over "
            f"{w['stream_emits']} streamed token(s) (per-token "
            "fan-out to attached SSE sinks)")
    if w.get("offload_demotes") or w.get("offload_promotes"):
        lines.append(
            f"offload.demote {w['offload_demote_ms']:.3f} ms over "
            f"{w['offload_demotes']} block demote(s)   "
            f"offload.promote {w['offload_promote_ms']:.3f} ms over "
            f"{w['offload_promotes']} restore(s) (host-RAM KV tier: "
            "evicted-block spill / admission restore)")
    if w.get("supervisor_restarts") or w.get("drain_migrations"):
        lines.append(
            f"supervisor.restart {w['supervisor_restart_ms']:.3f} ms "
            f"over {w['supervisor_restarts']} respawn(s)   "
            f"drain.migrate {w['drain_migrate_ms']:.3f} ms over "
            f"{w['drain_migrations']} stream(s) (self-healing fleet: "
            "replica respawn + SIGTERM drain handoff)")
    lines += [
        "(phases exceeding wall = spans ran concurrently — e.g. the "
        "async engine loop's",
        " host work hidden behind device compute; the table above "
        "double-counts them)",
    ]
    return "\n".join(lines)


def lifecycle_summary(events):
    """Count instant events (``ph == "i"``) by name — the request
    lifecycle: req.queued / admitted / prefix_adopted / first_token /
    finished / evicted, plus the overload-protection instants
    ``req.preempted`` / ``req.resumed`` / ``req.shed`` (with a
    per-reason breakdown) and ``fault.injected`` / ``engine.watchdog``
    from the chaos harness.  Returns rows of (name, count) sorted by
    count descending; shed/evicted reasons render as
    ``name[reason]``.  (timeline.py's ``lifecycle_counts`` is the
    dict-shaped twin — both tools stay single-file standalone by
    design, so a key-format change must be mirrored there.)"""
    counts = {}
    for ev in events:
        if ev.get("ph") != "i":
            continue
        name = ev.get("name", "?")
        reason = (ev.get("args") or {}).get("reason")
        key = f"{name}[{reason}]" if reason else name
        counts[key] = counts.get(key, 0) + 1
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))


def format_lifecycle(rows):
    lines = [f"{'instant':<28} {'count':>7}"]
    for name, count in rows:
        lines.append(f"{name:<28} {count:>7}")
    return "\n".join(lines)


def load_trace(path):
    """``(events, counters, window_s)`` of a trace file: Catapult
    object form or bare list (no counters), or a benchmark run's
    ``--dump-sources`` dump (its ``spans``, the window's counter
    deltas and its length)."""
    with open(path) as f:
        data = json.load(f)
    counters = window_s = None
    if isinstance(data, dict) and "spans" in data:
        events = data["spans"]
        counters = (data.get("counters") or {}).get("delta")
        window_s = (data.get("ctx") or {}).get("window_s")
    else:
        events = data["traceEvents"] if isinstance(data, dict) else data
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a chrome trace")
    return events, counters, window_s


def main(argv=None):
    p = argparse.ArgumentParser(
        description="per-span-name time table for a chrome trace file")
    p.add_argument("trace", help="trace JSON (object or bare list form)")
    p.add_argument("--cat", default=None,
                   help="only spans of this category (e.g. serving, "
                        "tick, compile, host)")
    p.add_argument("--sort", default="total",
                   choices=("total", "count", "mean", "p50", "p99"),
                   help="sort column (descending; default total)")
    p.add_argument("--wall", action="store_true",
                   help="append a per-tick wall-time vs summed-phase "
                        "summary (concurrent spans — async engine "
                        "overlap — make the two diverge; the table "
                        "alone double-counts them) and, where the "
                        "trace has the engine's device lane (dev.* "
                        "spans), the device's busy and idle time with "
                        "the idle summed by the host span open "
                        "meanwhile; also cpu(ms) and wait(ms) columns "
                        "for the spans that carry cpu_ms, and the CPU "
                        "a wall-second of the tick's, the edge's and "
                        "the watcher's threads and the rest of the "
                        "process")
    p.add_argument("--lifecycle", action="store_true",
                   help="append an instant-event count table (request "
                        "lifecycle incl. req.preempted / req.resumed "
                        "/ req.shed[reason], fault.injected, "
                        "engine.watchdog)")
    args = p.parse_args(argv)
    events, counters, window_s = load_trace(args.trace)
    rows = summarize(events, cat=args.cat)
    key = {"total": "total_ms", "count": "count", "mean": "mean_ms",
           "p50": "p50_ms", "p99": "p99_ms"}[args.sort]
    rows.sort(key=lambda r: -r[key])
    if not rows and not args.lifecycle:
        print("no complete-events matched", file=sys.stderr)
        return 1
    if rows:
        print(format_table(rows, cpu=args.wall))
    if args.wall:
        print()
        print(format_wall(wall_summary(events)))
        dev = device_summary(events)
        if dev is not None:
            print(format_device(dev))
        groups = cpu_by_group(events, counters, window_s)
        if groups is not None:
            print(format_cpu_groups(groups))
    if args.lifecycle:
        life = lifecycle_summary(events)
        print()
        if life:
            print(format_lifecycle(life))
        else:
            print("no instant events", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
