"""Span tracer + chrome-trace exporter (monitor/tracing.py) and the
timeline tools: Catapult JSON validity (round-trips through ``json``,
monotonic ``ts``, well-formed ``ph`` fields), ring-buffer bounding
under sustained load, thread safety of concurrent spans against
concurrent snapshots, the RecordEvent decorator/context-manager API,
and the tools/trace_view.py + tools/timeline.py CLIs.  Pure stdlib —
no jax, no model; engine integration lives in tests/test_serving.py."""
import importlib.util
import json
import os
import threading

import pytest

from paddle_tpu.monitor.tracing import (
    NullTracer, RecordEvent, Tracer, to_chrome_trace)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_and_instant_events_valid_catapult():
    """Spans/instants render as Catapult JSON that json round-trips,
    with monotonic ts, matched ph fields (X carries dur, i carries
    scope), and args preserved."""
    tr = Tracer(capacity=128)
    with tr.span("tick", cat="tick", tick=1) as sp:
        tr.instant("req.queued", cat="request", req=7)
        with tr.span("decode.dispatch", batch=3):
            pass
        sp.args["emitted"] = 3
    trace = tr.chrome_trace(process_name="test")
    text = json.dumps(trace)
    back = json.loads(text)
    evs = back["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert phs <= {"X", "i", "M"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert all("dur" in e and e["dur"] >= 0 for e in xs)
    assert all(e["s"] == "t" for e in evs if e["ph"] == "i")
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)
    tick = next(e for e in xs if e["name"] == "tick")
    assert tick["args"] == {"tick": 1, "emitted": 3}
    # nesting: the dispatch span lies inside the tick span
    disp = next(e for e in xs if e["name"] == "decode.dispatch")
    assert tick["ts"] <= disp["ts"]
    assert disp["ts"] + disp["dur"] <= tick["ts"] + tick["dur"] + 1e-6
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["name"] == "req.queued" and inst["args"]["req"] == 7


def test_ring_buffer_bounded_under_sustained_load():
    """The per-thread ring holds at most ``capacity`` events: sustained
    load drops the OLDEST — the flight-recorder property."""
    tr = Tracer(capacity=64)
    for i in range(1000):
        with tr.span("s", i=i):
            pass
    evs = tr.events()
    assert len(evs) == 64
    # the retained window is the most recent one
    assert [e.args["i"] for e in evs] == list(range(936, 1000))
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_thread_safety_concurrent_spans_and_snapshots():
    """4 writer threads spin spans while the main thread snapshots and
    exports continuously: no exception, every thread's ring visible,
    events bounded per thread."""
    tr = Tracer(capacity=256)
    stop = threading.Event()
    errors = []

    def spin(k):
        try:
            while not stop.is_set():
                with tr.span(f"w{k}"):
                    tr.instant(f"i{k}")
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=spin, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(100):
            evs = tr.events()
            json.dumps(tr.chrome_trace())
            assert all(evs[i].ts <= evs[i + 1].ts
                       for i in range(len(evs) - 1))
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    tids = {e.tid for e in tr.events()}
    assert len(tids) == 4
    per_thread = {}
    for e in tr.events():
        per_thread[e.tid] = per_thread.get(e.tid, 0) + 1
    assert all(n <= 256 for n in per_thread.values())
    names = tr.thread_names()
    assert set(names) == tids


def test_record_event_decorator_and_disable():
    """RecordEvent doubles as a decorator; a disabled tracer collects
    nothing and its span() short-circuits to the shared no-op."""
    tr = Tracer(capacity=32)

    @RecordEvent("work", tr, cat="host", n=1)
    def work(x):
        return x * 2

    assert work(21) == 42
    assert work(2) == 4
    evs = tr.events()
    assert [e.name for e in evs] == ["work", "work"]
    assert evs[0].args == {"n": 1}
    tr.enabled = False
    sp = tr.span("muted")
    with sp:
        pass
    tr.instant("muted.i")
    assert len(tr.events()) == 2  # nothing new landed
    tr.enabled = True
    with tr.span("back"):
        pass
    assert [e.name for e in tr.events()][-1] == "back"
    tr.clear()
    assert tr.events() == []


def test_null_tracer_and_emit():
    """NullTracer supports the full surface as no-ops; Tracer.emit
    back-dates an externally timed event (the compile hook's path)."""
    nt = NullTracer()
    with nt.span("x") as sp:
        sp.args["k"] = 1
    nt.instant("y")
    nt.emit("z", 0.0, 1.0)
    assert nt.events() == []
    assert nt.chrome_trace()["traceEvents"] == []
    tr = Tracer()
    tr.emit("compile:decode", 10.0, 2.5, cat="compile",
            args={"wall_ms": 2500})
    (ev,) = tr.events()
    assert ev.ts == 10.0 * 1e6 and ev.dur == 2.5 * 1e6
    assert ev.cat == "compile"


def test_to_chrome_trace_bare_event_list():
    """Without thread/process names the export has exactly one JSON
    object per event (the profiler compat contract)."""
    tr = Tracer()
    with tr.span("a"):
        pass
    trace = to_chrome_trace(tr.events())
    assert len(trace["traceEvents"]) == 1
    assert trace["traceEvents"][0]["name"] == "a"
    assert trace["displayTimeUnit"] == "ms"


def test_trace_view_summary_percentiles(tmp_path):
    """tools/trace_view.py aggregates complete-events per name with
    count/total/p50/p99 (interpolated), category filter included."""
    tv = _load_tool("trace_view")
    events = ([{"name": "tick", "ph": "X", "ts": i * 100.0,
                "dur": (i + 1) * 1000.0, "cat": "tick"}
               for i in range(100)] +
              [{"name": "admit", "ph": "X", "ts": 0.0, "dur": 500.0,
                "cat": "serving"},
               {"name": "req.queued", "ph": "i", "ts": 0.0,
                "cat": "request"}])
    rows = tv.summarize(events)
    assert [r["name"] for r in rows] == ["tick", "admit"]  # by total
    tick = rows[0]
    assert tick["count"] == 100
    # durs are 1..100 ms; numpy-linear percentiles over them
    assert tick["p50_ms"] == pytest.approx(50.5)
    assert tick["p99_ms"] == pytest.approx(99.01)
    assert rows[1]["count"] == 1 and rows[1]["p50_ms"] == 0.5
    assert tv.summarize(events, cat="tick")[0]["name"] == "tick"
    assert len(tv.summarize(events, cat="tick")) == 1
    # CLI end to end over a file
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path)]) == 0
    assert tv.main([str(path), "--cat", "nope"]) == 1
    table = tv.format_table(rows)
    assert "tick" in table and "p99(ms)" in table


def test_timeline_merge_assigns_pids(tmp_path):
    """tools/timeline.py merges N traces into one timeline with
    distinct pids, preserves flight-recorder metadata, and accepts
    both object-form and bare-list files."""
    tl = _load_tool("timeline")
    t1 = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 999, "tid": 0,
         "args": {"name": "engine"}},
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 5.0,
         "pid": 999, "tid": 1, "cat": "tick"}],
        "metadata": {"flight-recorder": {"error": "boom"}}}
    t2 = [{"name": "step", "ph": "X", "ts": 1.0, "dur": 2.0,
           "pid": 999, "tid": 1, "cat": "host"}]
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    p1.write_text(json.dumps(t1))
    p2.write_text(json.dumps(t2))
    out = tmp_path / "merged.json"
    assert tl.main([str(p1), str(p2), "--out", str(out)]) == 0
    merged = json.loads(out.read_text())
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    assert merged["metadata"]["flight-recorder"]["error"] == "boom"
    # the bare-list source got a synthesized process_name row
    metas = [e for e in merged["traceEvents"]
             if e["ph"] == "M" and e["pid"] == 1]
    assert metas and metas[0]["args"]["name"].endswith("b.json")


def test_timeline_rejects_non_trace(tmp_path):
    tl = _load_tool("timeline")
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError, match="traceEvents"):
        tl.load_trace(str(bad))


def test_dead_thread_lanes_pruned_and_idents_not_recycled():
    """Lanes are per thread LIFETIME: a new thread never inherits a
    dead thread's lane/name (even if the OS recycles the ident), dead
    lanes are retained for post-mortems until max_threads, then pruned
    oldest-first — live lanes never evicted."""
    tr = Tracer(capacity=16, max_threads=4)
    with tr.span("main.keepalive"):
        pass  # the live main-thread lane that must survive pruning

    def one_span(k):
        t = threading.Thread(target=lambda: tr.instant(f"w{k}"),
                             name=f"worker-{k}")
        t.start()
        t.join()

    for k in range(10):
        one_span(k)
    names = tr.thread_names()
    assert len(names) <= 4                      # bounded
    assert "MainThread" in names.values()       # live lane retained
    # every lane id is unique per thread lifetime: 11 threads emitted,
    # so the newest lane id outgrew the bound — no reuse happened
    assert max(names) > 4
    # the retained worker lanes are the most recent ones
    worker_names = sorted(v for v in names.values()
                          if v.startswith("worker-"))
    assert worker_names == [f"worker-{k}" for k in (7, 8, 9)]
    # and the main lane still collects
    with tr.span("main.again"):
        pass
    assert any(e.name == "main.again" for e in tr.events())


def test_trace_view_wall_summary(tmp_path, capsys):
    """--wall reports per-tick wall time vs summed phase time: with
    the async engine loop, host.overlap spans run concurrently with
    device compute, so phase totals legitimately exceed wall — the
    summary surfaces the divergence the plain table double-counts."""
    tv = _load_tool("trace_view")
    # 2 ticks of 10 ms wall; phases sum to 14 ms per tick because
    # 5 ms of host.overlap + 2 ms of d2h wait ran concurrently
    events = []
    for i in range(2):
        t0 = i * 20000.0
        events += [
            {"name": "tick", "ph": "X", "ts": t0, "dur": 10000.0,
             "cat": "tick"},
            {"name": "decode.dispatch", "ph": "X", "ts": t0,
             "dur": 7000.0, "cat": "serving"},
            {"name": "host.overlap", "ph": "X", "ts": t0 + 1000.0,
             "dur": 5000.0, "cat": "serving"},
            {"name": "decode.d2h_wait", "ph": "X", "ts": t0 + 7000.0,
             "dur": 2000.0, "cat": "serving"},
        ]
    w = tv.wall_summary(events)
    assert w["ticks"] == 2
    assert w["wall_ms"] == pytest.approx(20.0)
    assert w["phase_ms"] == pytest.approx(28.0)
    assert w["per_tick_wall_ms"] == pytest.approx(10.0)
    assert w["per_tick_phase_ms"] == pytest.approx(14.0)
    assert w["overlap_ms"] == pytest.approx(10.0)
    assert w["d2h_wait_ms"] == pytest.approx(4.0)
    # CLI: --wall appends the summary after the table
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    assert "wall 20.000 ms" in out
    assert "host.overlap 10.000 ms" in out
    assert "concurrently" in out
    # no ragged dispatches in this trace: the kernel line stays out
    assert "decode.ragged" not in out


def test_trace_view_surfaces_ragged_stream_dispatches(tmp_path,
                                                      capsys):
    """--wall breaks out ``decode.ragged_stream`` spans (the Pallas
    dispatches of ``Engine(attn_impl="ragged")``) so a trace shows at
    a glance whether the kernel or the per-shape XLA programs
    (``decode.dispatch``) served the tick, and sums the spans'
    ``kv_blocks_walked`` arg — per-tick block-walk cost, attributable
    from a trace alone."""
    tv = _load_tool("trace_view")
    events = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "decode.ragged_stream", "ph": "X", "ts": 500.0,
         "dur": 6000.0, "cat": "serving",
         "args": {"chunks": 1, "w": 8, "kv_blocks_walked": 12}},
        {"name": "tick", "ph": "X", "ts": 20000.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "decode.ragged_stream", "ph": "X", "ts": 20500.0,
         "dur": 5000.0, "cat": "serving",
         "args": {"kv_blocks_walked": 14}},
    ]
    w = tv.wall_summary(events)
    assert w["ragged_stream_dispatches"] == 2
    assert w["ragged_stream_ms"] == pytest.approx(11.0)
    assert w["kv_blocks_walked"] == 26
    path = tmp_path / "ragged_stream.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    assert "decode.ragged_stream 11.000 ms over 2 streaming" in out
    assert "kv blocks walked 26 (13.0/tick)" in out


def test_trace_view_surfaces_offload_transfers(tmp_path, capsys):
    """--wall breaks out ``offload.demote`` / ``offload.promote``
    spans (the host-RAM KV tier of ``Engine(kv_host_mb=...)``) so a
    trace shows at a glance what the second tier's d2h spills and h2d
    restores cost next to decode itself."""
    tv = _load_tool("trace_view")
    events = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "offload.demote", "ph": "X", "ts": 500.0,
         "dur": 1500.0, "cat": "serving",
         "args": {"key": "ab12", "stored": True}},
        {"name": "offload.demote", "ph": "X", "ts": 2500.0,
         "dur": 500.0, "cat": "serving"},
        {"name": "tick", "ph": "X", "ts": 20000.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "offload.promote", "ph": "X", "ts": 20500.0,
         "dur": 3000.0, "cat": "serving", "args": {"blocks": 3}},
    ]
    w = tv.wall_summary(events)
    assert w["offload_demotes"] == 2
    assert w["offload_demote_ms"] == pytest.approx(2.0)
    assert w["offload_promotes"] == 1
    assert w["offload_promote_ms"] == pytest.approx(3.0)
    path = tmp_path / "offload.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    assert "offload.demote 2.000 ms over 2 block demote(s)" in out
    assert "offload.promote 3.000 ms over 1 restore(s)" in out
    assert "host-RAM KV tier" in out
    # a trace with no offload traffic keeps the line out entirely
    quiet = [e for e in events if not e["name"].startswith("offload.")]
    assert not (tv.wall_summary(quiet)["offload_demotes"]
                or tv.wall_summary(quiet)["offload_promotes"])
    path.write_text(json.dumps({"traceEvents": quiet}))
    assert tv.main([str(path), "--wall"]) == 0
    assert "offload." not in capsys.readouterr().out


def test_trace_view_lifecycle_instants(tmp_path, capsys):
    """tools/trace_view.py --lifecycle counts instant events by name
    with a [reason] breakdown — the req.preempted / req.resumed /
    req.shed overload lifecycle renders alongside the span table."""
    tv = _load_tool("trace_view")
    events = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 100.0,
         "cat": "tick"},
        {"name": "req.queued", "ph": "i", "ts": 1.0, "cat": "request",
         "args": {"req": 1}},
        {"name": "req.preempted", "ph": "i", "ts": 2.0,
         "cat": "request", "args": {"req": 1, "slot": 0}},
        {"name": "req.resumed", "ph": "i", "ts": 3.0,
         "cat": "request", "args": {"req": 1}},
        {"name": "req.shed", "ph": "i", "ts": 4.0, "cat": "request",
         "args": {"req": 2, "reason": "deadline"}},
        {"name": "req.shed", "ph": "i", "ts": 5.0, "cat": "request",
         "args": {"req": 3, "reason": "queue_full"}},
        {"name": "req.shed", "ph": "i", "ts": 6.0, "cat": "request",
         "args": {"req": 4, "reason": "deadline"}},
        {"name": "fault.injected", "ph": "i", "ts": 7.0,
         "cat": "fault", "args": {"site": "dispatch"}},
    ]
    rows = dict(tv.lifecycle_summary(events))
    assert rows["req.preempted"] == 1
    assert rows["req.resumed"] == 1
    assert rows["req.shed[deadline]"] == 2
    assert rows["req.shed[queue_full]"] == 1
    assert rows["fault.injected"] == 1
    assert "tick" not in rows            # complete-events excluded
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--lifecycle"]) == 0
    out = capsys.readouterr().out
    assert "req.preempted" in out and "req.shed[deadline]" in out


def test_timeline_lifecycle_counts(tmp_path, capsys):
    """tools/timeline.py --lifecycle prints per-source instant counts
    (stderr) while the merged trace stays intact on stdout."""
    tl = _load_tool("timeline")
    t1 = {"traceEvents": [
        {"name": "req.preempted", "ph": "i", "ts": 1.0,
         "cat": "request", "args": {"req": 9}},
        {"name": "req.shed", "ph": "i", "ts": 2.0, "cat": "request",
         "args": {"req": 10, "reason": "rate_limited"}},
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 3.0,
         "cat": "tick"}]}
    assert tl.lifecycle_counts(t1) == {"req.preempted": 1,
                                       "req.shed[rate_limited]": 1}
    p1 = tmp_path / "a.json"
    p1.write_text(json.dumps(t1))
    out_path = tmp_path / "m.json"
    assert tl.main([str(p1), "--lifecycle",
                    "--out", str(out_path)]) == 0
    err = capsys.readouterr().err
    assert "req.preempted=1" in err
    merged = json.loads(out_path.read_text())
    assert len(merged["traceEvents"]) == 4  # 3 events + process_name


def test_shared_lanes_outlive_their_threads():
    """Request-lifecycle and HTTP events go to ONE shared ``requests``
    lane per tracer whichever thread emits them: after 200 sequential
    short-lived handler threads at the default ``max_threads`` the
    first connection's ``req.queued`` is still there, and those
    threads burned no lane of their own."""
    tr = Tracer()
    assert tr.max_threads == 64

    def handler(k):
        with tr.span("http.ingest", cat="http") as sp:
            tr.instant("req.queued", cat="request", req=k)
            sp.args["req"] = k
        tr.instant("http.first_frame", cat="http", req=k)

    for k in range(200):
        t = threading.Thread(target=handler, args=(k,))
        t.start()
        t.join()
    evs = tr.events()
    queued = [e.args["req"] for e in evs if e.name == "req.queued"]
    assert queued == list(range(200))
    assert sum(e.name == "http.ingest" for e in evs) == 200
    assert sum(e.name == "http.first_frame" for e in evs) == 200
    names = tr.thread_names()
    assert list(names.values()) == ["requests"]     # one lane, shared
    assert len({e.tid for e in evs}) == 1
    # the lane is bounded like any other ring
    small = Tracer(capacity=8)
    for k in range(50):
        small.instant("req.queued", cat="request", req=k)
    assert [e.args["req"] for e in small.events()] == list(range(42, 50))


def test_shared_lane_by_category_and_never_pruned():
    """``cat`` picks the lane: ``request``/``http`` -> ``requests``,
    ``device`` -> ``device``, anything else the emitting thread's own;
    pruning dead threads' lanes never touches a shared one, and
    ``max_threads`` stays a settable attribute."""
    tr = Tracer(max_threads=2)
    tr.instant("req.queued", cat="request", req=1)
    tr.emit("dev.decode", 1.0, 0.5, cat="device", args={"batch": 2})
    tr.instant("own")
    by_name = {e.name: e.tid for e in tr.events()}
    names = tr.thread_names()
    assert names[by_name["req.queued"]] == "requests"
    assert names[by_name["dev.decode"]] == "device"
    assert names[by_name["own"]] == "MainThread"
    for k in range(6):
        t = threading.Thread(target=lambda: tr.instant("w"),
                             name=f"w{k}")
        t.start()
        t.join()
    names = tr.thread_names()
    assert {"requests", "device", "MainThread"} <= set(names.values())
    assert sum(v.startswith("w") for v in names.values()) <= 1
    tr.max_threads = 1 << 20        # what the benchmark's harness does
    assert tr.max_threads == 1 << 20
    # export: the shared lanes are labelled like thread lanes
    meta = {e["args"]["name"] for e in tr.chrome_trace()["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"requests", "device"} <= meta


def test_trace_view_wall_reads_the_device_lane(tmp_path, capsys):
    """--wall leaves the ``dev.*`` spans out of the host's phase sum
    and prints the device lane: busy by span name, and the idle holes
    summed by the narrowest host span open over each hole's middle."""
    tv = _load_tool("trace_view")
    events = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "state.push", "ph": "X", "ts": 4100.0, "dur": 800.0,
         "cat": "serving"},
        {"name": "tick", "ph": "X", "ts": 10000.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "dev.decode", "ph": "X", "ts": 0.0, "dur": 4000.0,
         "cat": "device"},
        {"name": "dev.prefill", "ph": "X", "ts": 5000.0, "dur": 1000.0,
         "cat": "device"},
        {"name": "dev.decode", "ph": "X", "ts": 6000.0, "dur": 6000.0,
         "cat": "device"},
        {"name": "dev.decode", "ph": "X", "ts": 14000.0, "dur": 4000.0,
         "cat": "device"},
    ]
    w = tv.wall_summary(events)
    assert w["phase_ms"] == pytest.approx(0.8)   # no dev.* in it
    d = tv.device_summary(events)
    assert d["busy_ms"] == pytest.approx(15.0)
    assert d["idle_ms"] == pytest.approx(3.0)
    assert d["span_ms"] == pytest.approx(18.0)
    assert d["busy"]["dev.decode"] == (3, pytest.approx(14.0))
    assert d["busy"]["dev.prefill"] == (1, pytest.approx(1.0))
    assert d["idle_by_host_span"] == [
        ("tick", pytest.approx(2.0)), ("state.push", pytest.approx(1.0))]
    assert tv.device_summary(events[:3]) is None
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    assert "device lane: busy 15.000 ms" in out
    assert "device idle 3.000 ms of 18.000 ms (16.7%)" in out
    assert "state.push" in out.split("device idle")[1]


def test_trace_view_wall_knows_the_state_patch(tmp_path, capsys):
    """--wall names how the device-resident step state followed the
    host: the per-slot patch programs and the first-token picks queued
    with them (``state.patch``), the whole uploads (``state.push``) and
    what still consumed the ring to empty, by ``why``."""
    tv = _load_tool("trace_view")
    events = [
        {"name": "tick", "ph": "X", "ts": 0.0, "dur": 10000.0,
         "cat": "tick"},
        {"name": "state.push", "ph": "X", "ts": 100.0, "dur": 900.0,
         "cat": "serving", "args": {"bytes": 4096}},
        {"name": "state.patch", "ph": "X", "ts": 2000.0, "dur": 400.0,
         "cat": "serving", "args": {"slots": 2, "bytes": 512}},
        {"name": "state.patch", "ph": "X", "ts": 2400.0, "dur": 100.0,
         "cat": "serving", "args": {"first_token": 1}},
        {"name": "state.patch", "ph": "X", "ts": 5000.0, "dur": 300.0,
         "cat": "serving", "args": {"slots": 1, "bytes": 256}},
        {"name": "ring.drain", "ph": "X", "ts": 7000.0, "dur": 500.0,
         "cat": "serving", "args": {"why": "tail", "ticks": 1}},
        {"name": "ring.drain", "ph": "X", "ts": 8000.0, "dur": 500.0,
         "cat": "serving", "args": {"why": "tail", "ticks": 1}},
        {"name": "ring.drain", "ph": "X", "ts": 9000.0, "dur": 500.0,
         "cat": "serving", "args": {"why": "spec", "ticks": 1}},
    ]
    w = tv.wall_summary(events)
    assert w["state_patches"] == 2 and w["first_token_picks"] == 1
    assert w["state_patch_ms"] == pytest.approx(0.8)
    assert w["state_pushes"] == 1
    assert w["state_push_ms"] == pytest.approx(0.9)
    assert w["ring_drains"] == {"tail": 2, "spec": 1}
    path = tmp_path / "t.json"
    path.write_text(json.dumps({"traceEvents": events}))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    assert ("state.patch 0.800 ms over 2 slot patch(es) and 1 "
            "first-token pick(s)") in out
    assert "state.push 0.900 ms over 1 whole upload(s)" in out
    assert "ring.drain by why: spec 1, tail 2" in out
    # a trace with none of them prints no such line
    assert "state.patch" not in tv.format_wall(
        tv.wall_summary(events[:1]))


def test_dev_lane_check_aligns_clocks_and_pairs_programs(monkeypatch):
    """tools/dev_lane_check.py: the profiler's clock is aligned to the
    engine's on the tick spans both hold, each ``dev.*`` span is paired
    with the module event of its program nearest in end time, and the
    spans clipped to the traced interval are summed against the
    modules'."""
    chk = _load_tool("dev_lane_check")
    off = 5_000_000.0                       # profiler clock runs ahead
    ticks = [1000.0 * k + 7.0 * ((k * k) % 11) for k in range(40)]
    decode = [(1000.0 * k + 100.0, 1000.0 * k + 900.0)
              for k in range(10, 20)]
    chunk = [(1000.0 * k + 900.0, 1000.0 * k + 950.0)
             for k in range(10, 20, 2)]
    modules = {
        "jit_gpt_fused_decode(1)": [(s + off, e + off) for s, e in decode],
        "jit_gpt_paged_chunk_prefill(2)": [(s + off, e + off)
                                           for s, e in chunk]}
    monkeypatch.setattr(chk, "read_xplane", lambda path: (
        modules, [t + off for t in ticks[10:20]]))
    spans = [{"name": "tick", "ph": "X", "ts": t, "dur": 990.0,
              "cat": "tick"} for t in ticks]
    for name, program, ivs, late in (
            ("dev.decode", "fused_decode", decode, 30.0),
            ("dev.prefill", "paged_chunk_prefill", chunk, 20.0)):
        spans += [{"name": name, "ph": "X", "cat": "device",
                   "ts": s + late, "dur": e - s + 2.0,
                   "args": {"program": program}} for s, e in ivs[:-1]]
    src = {"spans": spans,
           "counters": {"profile_delta": {"serving.dev_busy_ms": 8.0}},
           "device": {"busy_s": 0.00825, "window_s": 0.01}}
    got = chk.check(src, "unused.xplane.pb")
    assert got["clock_offset_spread_us"] == pytest.approx(0.0, abs=1e-6)
    d = got["programs"]["fused_decode"]
    assert (d["span"], d["pairs"]) == ("dev.decode", 9)
    assert d["module"] == "jit_gpt_fused_decode(1)"
    assert d["dev_p50_ms"] == pytest.approx(0.802)
    assert d["module_p50_ms"] == pytest.approx(0.800)
    assert d["abs_diff_p50_ms"] == pytest.approx(0.002)
    assert d["offset_p50_ms"] == pytest.approx(0.032)
    c = got["programs"]["paged_chunk_prefill"]
    assert (c["pairs"], c["offset_p90_ms"]) == (4, pytest.approx(0.022))
    assert got["modules"] == {"jit_gpt_fused_decode(1)": 10,
                              "jit_gpt_paged_chunk_prefill(2)": 5}
    b = got["busy"]
    assert b["modules_ms"] == pytest.approx(10 * 0.8 + 5 * 0.05)
    assert b["dev_spans_clipped_ms"] == pytest.approx(
        9 * 0.802 + 4 * 0.052)
    assert b["dev_busy_ms_delta"] == 8.0
