"""The host by thread: a CPU clock beside the wall clock.

``Tracer.span(cpu=True)`` (monitor/tracing.py) reads the calling
thread's CPU clock at both ends of a span; the engine splits every
tick's ``host_ms`` into ``cpu_ms`` (Python its thread ran) and
``wait_ms`` (it stood runnable or blocked, but not on the device) and
keeps the sums as counters with tracing on or off; the HTTP edge
accounts for its handler threads (``http.ingest``, ONE ``http.stream``
a streamed response); ``tools/trace_view.py --wall`` prints both."""
import importlib.util
import json
import os
import socket
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.monitor import tracing
from paddle_tpu.monitor.tracing import NullTracer, RecordEvent, Tracer
from paddle_tpu.serving import Engine, EngineServer

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

# the phases of a tick that read the CPU clock, and the waits for the
# device, which do not (the engine times those itself)
CPU_PHASES = {"admit", "chunk.plan", "prefill.chunk", "prefill.d2h",
              "state.push", "state.patch", "first_token", "ring.drain",
              "dispatch", "decode.dispatch",
              "decode.ragged_stream", "consume", "decode.emit"}
DEVICE_WAITS = {"decode.d2h_wait", "decode.d2h", "prefill.d2h",
                "decode.allgather"}


def _trace_view():
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(TOOLS, "trace_view.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

def _busy(seconds):
    end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < end:
        n += 1
    return n


def test_cpu_span_reads_what_the_thread_computed():
    """Around a busy loop ``cpu_ms`` is the span's duration (within a
    fifth; a loaded machine may deschedule the loop, so the best of a
    few tries counts); around a sleep it is next to nothing."""
    tr = Tracer()
    best = 1.0
    for _ in range(5):
        with tr.span("busy", cpu=True) as sp:
            _busy(0.05)
        ev = tr.events()[-1]
        assert ev.args["cpu_ms"] == round(sp.cpu_elapsed * 1e3, 3)
        assert sp.cpu_elapsed <= sp.elapsed + 1e-4
        best = min(best, abs(sp.elapsed - sp.cpu_elapsed) / sp.elapsed)
        if best <= 0.2:
            break
    assert best <= 0.2
    with tr.span("sleep", cpu=True, why="idle") as sp:
        time.sleep(0.05)
    ev = tr.events()[-1]
    assert ev.name == "sleep" and ev.dur >= 50e3
    assert ev.args["cpu_ms"] < 5.0 and ev.args["why"] == "idle"
    assert sp.cpu_elapsed < 5e-3


def test_span_without_cpu_reads_no_cpu_clock(monkeypatch):
    """A span that does not ask pays nothing new: no ``cpu_ms`` arg
    and not one call of the thread's CPU clock, as a context manager
    and as a decorator; one that asks makes exactly two."""
    calls = []
    real = time.thread_time

    def counted():
        calls.append(1)
        return real()
    monkeypatch.setattr(tracing.time, "thread_time", counted)
    tr = Tracer()
    with tr.span("plain", batch=2) as sp:
        pass

    @tr.span("deco")
    def f():
        return 7
    assert f() == 7
    with RecordEvent("bare", tr):
        pass
    tr.instant("mark")
    assert calls == []
    assert sp.cpu_elapsed == 0.0
    assert all("cpu_ms" not in (ev.args or {}) for ev in tr.events())
    with tr.span("asked", cpu=True):
        pass
    assert len(calls) == 2

    @tr.span("deco.cpu", cpu=True)
    def g():
        return 8
    assert g() == 8 and g() == 8
    assert len(calls) == 6
    asked = [ev for ev in tr.events() if ev.name == "deco.cpu"]
    assert len(asked) == 2 and all("cpu_ms" in ev.args for ev in asked)


def test_disabled_tracers_stay_the_shared_noop(monkeypatch):
    """``NullTracer.span(cpu=True)`` and a disabled tracer's return the
    one shared no-op span and read no clock."""
    monkeypatch.setattr(
        tracing.time, "thread_time",
        lambda: pytest.fail("a disabled span read the CPU clock"))
    null = NullTracer()
    off = Tracer(enabled=False)
    a = null.span("x", cpu=True, k=1)
    b = off.span("y", cpu=True)
    assert a is b is tracing._NULL_SPAN
    with a as sp:
        sp.args["n"] = 1
    assert sp.elapsed == 0.0 and sp.cpu_elapsed == 0.0
    assert null.events() == [] and off.events() == []


# ---------------------------------------------------------------------------
# the engine's tick
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def cheap_cpu_clock(monkeypatch):
    """The engine reads the phases' CPU clock where a read is cheap;
    hold the probe's answer so a loaded machine cannot flip it."""
    monkeypatch.setattr(tracing, "thread_clock_read_us",
                        lambda reads=16: 0.3)


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("registry", monitor.StatRegistry())
    return Engine(model, **kw)


def _prompts(n, lens=(5, 7, 3, 9, 4, 6)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


TICK_COUNTERS = ("serving.tick_host_ms", "serving.tick_cpu_ms",
                 "serving.tick_wait_ms")


def _run_ticks(eng, at_least=50):
    """Staggered requests until ``at_least`` ticks ran; the three tick
    counters after every tick."""
    series = []
    prompts = _prompts(64)
    k = 0
    while len(series) < at_least:
        if k < len(prompts) and len(series) % 3 == 0:
            eng.submit(prompts[k], max_new_tokens=5 + k % 4)
            k += 1
        eng.step()
        series.append(tuple(eng.registry.get(n).value
                            for n in TICK_COUNTERS))
    eng.run_until_idle()
    series.append(tuple(eng.registry.get(n).value
                        for n in TICK_COUNTERS))
    return series


@pytest.mark.parametrize("kw", [
    dict(kv_block_size=8, prefill_chunk=8),
    dict(async_depth=1, kv_block_size=8),
    dict(kv_block_size=8, prefill_chunk=8, attn_impl="ragged"),
], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_tick_splits_host_time_into_cpu_and_wait(tiny_gpt, kw):
    """Over 50 ticks and more: ``dur = blocked + host_ms`` (blocked the
    waits for the device under the tick), ``host_ms = cpu_ms +
    wait_ms`` within 0.05 ms, the phases' ``cpu_ms`` (the waits' own
    CPU left out, as the tick leaves it out) sum to no more than the
    tick's, and the three counters are monotone and equal to the
    spans' sums."""
    eng = _engine(tiny_gpt, **kw)
    series = _run_ticks(eng)
    eng.stop()
    for a, b in zip(series, series[1:]):
        assert all(y >= x for x, y in zip(a, b))
    spans = [e for e in eng.chrome_trace()["traceEvents"]
             if e.get("ph") == "X"]
    ticks = [e for e in spans if e["name"] == "tick"]
    assert len(ticks) >= 50
    lane = ticks[0]["tid"]
    inner = sorted((e for e in spans
                    if e["tid"] == lane and e["name"] != "tick"),
                   key=lambda e: (e["ts"], -e["dur"]))
    sums = dict.fromkeys(("host_ms", "cpu_ms", "wait_ms"), 0.0)
    names = set()
    loose = 0
    for t in ticks:
        a = t["args"]
        t0, t1 = t["ts"], t["ts"] + t["dur"]
        kids = [e for e in inner if t0 <= e["ts"] < t1]
        waits = [e for e in kids if e["name"] in DEVICE_WAITS]
        blocked_ms = sum(e["dur"] for e in waits) * 1e-3
        # the engine's reads lie just inside each wait's span and the
        # tick's, a few clock reads a span; a thread descheduled
        # between two of them widens one tick's gap, never many
        gap = t["dur"] * 1e-3 - blocked_ms - a["host_ms"]
        assert gap >= -0.02 * (1 + len(waits))
        loose += gap > 0.02 * (1 + len(waits))
        assert a["cpu_ms"] >= 0 and a["wait_ms"] >= 0
        assert a["host_ms"] == pytest.approx(
            a["cpu_ms"] + a["wait_ms"], abs=0.05)
        assert a["wait_ms"] <= a["host_ms"] + 1e-9
        assert a.get("blocked_cpu_ms", 1.0) > 0      # kept only if not 0
        assert a.get("blocked_cpu_ms", 0.0) <= blocked_ms + 0.01
        end, direct = t0, 0.0
        for e in kids:
            names.add(e["name"])
            if e["ts"] >= end:          # not nested in the one before
                end = e["ts"] + e["dur"]
                direct += (e.get("args") or {}).get("cpu_ms", 0.0) \
                    if e["name"] not in DEVICE_WAITS else 0.0
            if e["name"] in CPU_PHASES:
                assert 0 <= e["args"]["cpu_ms"] <= e["dur"] * 1e-3 + 0.05
            else:
                assert "cpu_ms" not in (e.get("args") or {}), e["name"]
        # nested waits' CPU is inside their parents' cpu_ms but not
        # in the tick's: give it back before comparing
        assert direct <= a["cpu_ms"] + a.get("blocked_cpu_ms", 0.0) \
            + 0.02 * (1 + len(kids))
        for k in sums:
            sums[k] += a[k]
    assert loose <= len(ticks) // 10
    # one whole upload (the first tick's), then per-slot patches
    assert {"admit", "dispatch", "consume", "decode.emit",
            "state.push", "state.patch"} <= names
    assert sum(e["name"] == "state.push" for e in inner) == 1
    assert "stream.emit" not in CPU_PHASES
    for name, k in zip(TICK_COUNTERS, sums):
        assert eng.registry.get(name).value == pytest.approx(
            sums[k], abs=1e-6), name
    assert series[-1][0] == pytest.approx(
        series[-1][1] + series[-1][2], abs=0.05 * len(ticks))
    assert eng.registry.get("process.cpu_ms").value >= series[-1][1]


def test_tick_counters_kept_with_tracing_off(tiny_gpt):
    """``tracing=False``: no span, no watcher, and the tick still
    reads its thread's CPU clock: the three counters grow, add up, and
    leave the waits for the device out (the engine times those itself,
    not through a span)."""
    eng = _engine(tiny_gpt, tracing=False, kv_block_size=8,
                  prefill_chunk=8)
    series = _run_ticks(eng, at_least=20)
    eng.stop()
    assert eng.chrome_trace()["traceEvents"] == []
    for a, b in zip(series, series[1:]):
        assert all(y >= x for x, y in zip(a, b))
    host, cpu, wait = series[-1]
    assert cpu > 0 and host >= cpu - 0.05 * len(series)
    assert host == pytest.approx(cpu + wait, abs=0.05 * len(series))
    assert eng.registry.get("serving.dev_watch_cpu_ms").value == 0
    assert eng.registry.get("process.cpu_ms").value > 0
    assert eng._blocked_s > 0       # the last tick's download was timed


def test_a_coarse_cpu_clock_keeps_the_identities_and_the_sums(
        tiny_gpt, monkeypatch):
    """Where the kernel accounts CPU time by its timer the thread
    clock moves in steps of 10 ms (the benchmark's machine): a step
    lands whole on one tick, often more than its ``host_ms``.  The
    rest is carried to the next ticks: ``cpu_ms <= host_ms`` and
    ``host_ms = cpu_ms + wait_ms`` hold in every tick, and the counter
    keeps what the clock charged (clamping alone would lose most of
    it: a tick is a tenth of a step here)."""
    real = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: int(real() * 100) / 100.0)
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)
    r0 = real()
    series = _run_ticks(eng, at_least=150)
    spent_ms = (real() - r0) * 1e3
    eng.stop()
    ticks = [e["args"] for e in eng.chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "tick"]
    for a in ticks:
        assert 0 <= a["cpu_ms"] <= a["host_ms"]
        assert a["wait_ms"] == pytest.approx(
            a["host_ms"] - a["cpu_ms"], abs=2e-3)
    host, cpu, wait = series[-1]
    assert host == pytest.approx(cpu + wait, abs=2e-3 * len(ticks))
    assert cpu == pytest.approx(sum(a["cpu_ms"] for a in ticks))
    assert spent_ms >= 50              # some steps of the clock passed
    kept = cpu + eng._cpu_carry_ms + sum(
        a.get("blocked_cpu_ms", 0.0) for a in ticks)
    assert 0.6 * spent_ms - 10 <= kept <= spent_ms + 10
    assert {a["cpu_ms"] for a in ticks} - {0.0}     # and some landed


def test_a_dear_cpu_clock_is_kept_off_the_phases(tiny_gpt, monkeypatch):
    """Where one read of the thread's CPU clock is a trap into a
    sandbox's kernel (5.5 us on the benchmark's machine, and far more
    in a busy process), the default ring keeps the tick's own split
    and the counters and the phases read no CPU clock; with
    annotations on (the detailed mode) they read it again."""
    monkeypatch.undo()
    assert 0 < tracing.thread_clock_read_us() < 1e4      # the real probe
    monkeypatch.setattr(tracing, "thread_clock_read_us",
                        lambda reads=16: 5.5)
    for kw, phases in ((dict(), False),
                       (dict(trace_annotations=True), True)):
        eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8, **kw)
        assert eng._phase_cpu is phases
        series = _run_ticks(eng, at_least=12)
        eng.stop()
        spans = [e for e in eng.chrome_trace()["traceEvents"]
                 if e.get("ph") == "X"]
        ticks = [e for e in spans if e["name"] == "tick"]
        assert all({"cpu_ms", "wait_ms", "host_ms"} <= set(e["args"])
                   for e in ticks)
        seen = {e["name"] for e in spans
                if "cpu_ms" in (e.get("args") or {})}
        assert (seen - {"tick"} != set()) is phases
        if phases:
            assert seen - {"tick"} <= CPU_PHASES
        assert series[-1][1] > 0
    off = _engine(tiny_gpt, tracing=False)
    assert off._phase_cpu is False
    off.stop()


def test_watcher_counts_its_own_cpu(tiny_gpt):
    eng = _engine(tiny_gpt, kv_block_size=8)
    _run_ticks(eng, at_least=10)
    eng.stop()          # joins the watcher: its last share is in
    watch = eng.registry.get("serving.dev_watch_cpu_ms").value
    assert 0 < watch < eng.registry.get("process.cpu_ms").value


# ---------------------------------------------------------------------------
# the HTTP edge
# ---------------------------------------------------------------------------

def _frames(raw):
    """The SSE frames of a streamed body: (all, token, heartbeat)."""
    frames = [f for f in raw.split(b"\n\n") if f]
    return (len(frames),
            sum(f.startswith(b"event: token") for f in frames),
            sum(f.startswith(b":") for f in frames))


def _http_spans(eng, name):
    return {e["args"]["req"]: e
            for e in eng.chrome_trace()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == name}


def test_one_http_stream_span_a_streamed_request(tiny_gpt):
    """Exactly one ``http.stream`` a streamed request and none for a
    buffered one: ``frames`` = tokens + 1 (+ heartbeats), ``bytes``
    what the client read, ``done_bytes`` the terminal frame's, and the
    counters equal to the spans' sums."""
    eng = _engine(tiny_gpt, kv_block_size=8)
    reg = eng.registry
    got = {}
    with EngineServer(eng, port=0) as srv:
        def post(k, stream, n):
            body = {"prompt": [1 + k % 7, 2, 3, 4], "max_new_tokens": n,
                    "stream": stream}
            with urllib.request.urlopen(urllib.request.Request(
                    f"{srv.address}/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})) as r:
                got[k] = r.read()
        post(0, False, 2)            # warm the programs, buffered
        ths = [threading.Thread(target=post, args=(k, True, 3 + k))
               for k in range(1, 6)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        post(6, True, 40)            # one long enough to settle mid-way
        assert reg.get("serving.http_frames").value >= 32
    streams = _http_spans(eng, "http.stream")
    ingests = _http_spans(eng, "http.ingest")
    assert len(streams) == 6 and len(ingests) == 7
    assert set(streams) < set(ingests)
    bodies = sorted((v for k, v in got.items() if k), key=len)
    spans = sorted(streams.values(), key=lambda e: e["args"]["bytes"])
    for raw, e in zip(bodies, spans):
        a = e["args"]
        n_frames, n_tok, n_hb = _frames(raw)
        assert a["frames"] == n_frames == n_tok + 1 + n_hb
        assert a["bytes"] == len(raw)
        last = raw.rstrip(b"\n").rsplit(b"\n\n", 1)[-1] + b"\n\n"
        assert last.startswith(b"event: done")
        assert a["done_bytes"] == len(last)
        assert 0 <= a["write_ms"] <= e["dur"] * 1e-3
        assert 0 <= a["cpu_ms"] <= e["dur"] * 1e-3 + 0.01
    assert [_frames(b)[1] for b in bodies] == [4, 5, 6, 7, 8, 40]
    assert reg.get("serving.http_frames").value == sum(
        e["args"]["frames"] for e in spans)
    assert reg.get("serving.http_bytes_out").value == sum(
        e["args"]["bytes"] for e in spans)
    assert all("cpu_ms" in e["args"] for e in ingests.values())
    # one reading feeds the span's arg and the counter
    cpu_spans = sum(e["args"]["cpu_ms"] for e in spans) + sum(
        e["args"]["cpu_ms"] for e in ingests.values())
    assert reg.get("serving.http_cpu_ms").value == pytest.approx(
        cpu_spans, abs=1e-3 * 13)
    lanes = {e["args"]["name"]: e["tid"]
             for e in eng.chrome_trace()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {e["tid"] for e in spans} == {lanes["requests"]}


def test_a_client_that_hangs_up_still_closes_its_span(tiny_gpt):
    """The client reads two frames and resets the connection: the
    handler's next writes fail, the span closes with what was written
    and no terminal frame, and the counters hold the same."""
    eng = _engine(tiny_gpt, kv_block_size=8)
    with EngineServer(eng, port=0) as srv:
        body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 40,
                           "stream": True}).encode()
        s = socket.create_connection((srv.host, srv.port))
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        buf = b""
        while buf.count(b"event: token") < 2:
            buf += s.recv(4096)
        # RST, not FIN: the very next write on the server side fails
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     b"\x01\x00\x00\x00\x00\x00\x00\x00")
        s.close()
        deadline = time.monotonic() + 30
        while not _http_spans(eng, "http.stream") \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    (e,) = _http_spans(eng, "http.stream").values()
    a = e["args"]
    assert 2 <= a["frames"] < 41 and a["done_bytes"] == 0
    assert a["bytes"] > 0 and "cpu_ms" in a and "write_ms" in a
    assert eng.registry.get("serving.http_frames").value == a["frames"]
    assert eng.registry.get("serving.http_bytes_out").value == a["bytes"]


# ---------------------------------------------------------------------------
# the reader an operator has
# ---------------------------------------------------------------------------

def test_trace_view_wall_prints_cpu_and_wait(tiny_gpt, tmp_path, capsys):
    """``--wall`` on a trace the test records itself: ``cpu(ms)`` and
    ``wait(ms)`` columns (``-`` for spans that read no CPU clock) and
    the four-row CPU by thread group table, from the spans of a trace
    (tick and edge) and from the counters of a dump (all four)."""
    tv = _trace_view()
    eng = _engine(tiny_gpt, kv_block_size=8, prefill_chunk=8)
    before = {n: m.value for n, m in eng.registry.items()
              if isinstance(getattr(m, "value", None), (int, float))}
    t_0 = time.monotonic()
    with EngineServer(eng, port=0) as srv:
        for k in range(3):
            with urllib.request.urlopen(urllib.request.Request(
                    f"{srv.address}/generate", data=json.dumps(
                        {"prompt": [1 + k, 2, 3], "max_new_tokens": 6,
                         "stream": True}).encode(),
                    headers={"Content-Type": "application/json"})) as r:
                r.read()
    window_s = time.monotonic() - t_0
    trace = eng.chrome_trace()
    after = {n: m.value for n, m in eng.registry.items() if n in before}
    rows = {r["name"]: r for r in tv.summarize(trace["traceEvents"])}
    ticks = [e for e in trace["traceEvents"] if e["name"] == "tick"]
    assert rows["tick"]["cpu_ms"] == pytest.approx(
        sum(e["args"]["cpu_ms"] for e in ticks))
    assert rows["tick"]["wait_ms"] == pytest.approx(
        sum(e["args"]["wait_ms"] for e in ticks))
    assert rows["http.stream"]["wait_ms"] == pytest.approx(
        rows["http.stream"]["total_ms"] - rows["http.stream"]["cpu_ms"])
    assert rows["stream.emit"]["cpu_ms"] is None
    assert rows["dev.decode"]["wait_ms"] is None
    g = tv.cpu_by_group(trace["traceEvents"])
    by = dict(g["rows"])
    assert by["tick"] == pytest.approx(
        rows["tick"]["cpu_ms"] / g["wall_s"])
    assert by["edge"] == pytest.approx(
        (rows["http.stream"]["cpu_ms"] + rows["http.ingest"]["cpu_ms"])
        / g["wall_s"])
    assert by["watcher"] is None and by["rest of process"] is None
    assert tv.cpu_by_group([{"name": "tick", "ph": "X", "ts": 0.0,
                             "dur": 5.0}]) is None
    path = tmp_path / "t.json"
    path.write_text(json.dumps(trace))
    assert tv.main([str(path), "--wall"]) == 0
    out = capsys.readouterr().out
    head = out.splitlines()[0]
    assert head.split()[-2:] == ["cpu(ms)", "wait(ms)"]
    line = next(ln for ln in out.splitlines()
                if ln.startswith("stream.emit"))
    assert line.split()[-2:] == ["-", "-"]
    assert "CPU by thread group" in out
    assert "needs the counters of a dump" in out
    # without --wall the table is the one it was
    assert tv.main([str(path)]) == 0
    assert "cpu(ms)" not in capsys.readouterr().out
    # a --dump-sources dump: spans, counter deltas, the window
    delta = {k: after[k] - before[k] for k in after}
    dump = tmp_path / "dump.json"
    dump.write_text(json.dumps({
        "spans": [e for e in trace["traceEvents"] if e["ph"] != "M"],
        "counters": {"delta": delta}, "ctx": {"window_s": window_s}}))
    events, counters, win = tv.load_trace(str(dump))
    g = tv.cpu_by_group(events, counters, win)
    by = dict(g["rows"])
    assert g["wall_s"] == pytest.approx(window_s)
    assert by["tick"] == pytest.approx(
        delta["serving.tick_cpu_ms"] / window_s)
    assert by["edge"] == pytest.approx(
        delta["serving.http_cpu_ms"] / window_s)
    assert by["watcher"] == pytest.approx(
        delta["serving.dev_watch_cpu_ms"] / window_s)
    assert sum(by.values()) == pytest.approx(
        delta["process.cpu_ms"] / window_s)
    assert tv.main([str(dump), "--wall"]) == 0
    out = capsys.readouterr().out
    assert "rest of process" in out
    assert "needs the counters" not in out
