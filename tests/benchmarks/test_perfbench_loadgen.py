"""The load generator: its plan from the seed, and its event loop
against a small SSE server of the test's own."""
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from bench_paths import BENCH
from harness import loadgen


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def plan_of(name, seed, seconds=20.0, vocab=50304):
    m = mix(name)
    return loadgen.KINDS[m["kind"]](m, seed, seconds, vocab)


def shape(plan):
    return [(r["due"], len(r["prompt"]), r["max_new"])
            for _, r in sorted(plan.initial(), key=lambda x: x[0])]


@pytest.mark.parametrize("name", ["chat", "sessions"])
def test_same_seed_same_plan(name):
    a, b = plan_of(name, 2**31 + 11), plan_of(name, 2**31 + 11)
    assert shape(a) == shape(b)
    assert [r["prompt"] for _, r in a.initial()] == \
        [r["prompt"] for _, r in b.initial()]
    other = plan_of(name, 12)
    assert [r["prompt"] for _, r in a.initial()] != \
        [r["prompt"] for _, r in other.initial()]
    # the schedule and the sizes are the mix's own, whatever the seed
    assert shape(a) == shape(other)


@pytest.mark.parametrize("seconds", [20.0, 48.0])
def test_open_loop_seed_gives_the_ids_and_nothing_else(seconds):
    """One schedule under every seed: the same gaps and lengths in the
    same order; only the token ids differ."""
    a = loadgen.OpenLoop(mix("chat"), 1, seconds, 50304)
    b = loadgen.OpenLoop(mix("chat"), 2**31 + 5, seconds, 50304)
    assert [(r["phase"], r["due"], len(r["prompt"]), r["max_new"])
            for r in a.requests] == \
        [(r["phase"], r["due"], len(r["prompt"]), r["max_new"])
         for r in b.requests]
    assert [r["prompt"] for r in a.requests] != \
        [r["prompt"] for r in b.requests]
    lens = [len(r["prompt"]) for r in a.requests if r["phase"] == "window"]
    assert lens != sorted(lens) and len(set(lens)) > len(lens) // 2


def test_open_loop_schedule_is_anchored_to_the_window():
    m = mix("chat")
    p = plan_of("chat", 3, seconds=20.0)
    warm = [r for r in p.requests if r["phase"] == "warm"]
    win = [r for r in p.requests if r["phase"] == "window"]
    assert len(warm) == round(m["rate_per_s"] * m["warm_s"])
    assert len(win) == round(m["rate_per_s"] * 20.0)
    assert all(0 <= r["due"] < m["warm_s"] for r in warm)
    assert all(m["warm_s"] <= r["due"] < m["warm_s"] + 20.0 for r in win)
    lo, hi = m["prompt_len"]["min"], m["prompt_len"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in p.requests)
    assert all(m["output_len"]["min"] <= r["max_new"]
               <= m["output_len"]["max"] for r in p.requests)
    assert all(len(r["prompt"]) + r["max_new"] <= 2048 for r in p.requests)


def test_sessions_share_the_system_prompt_and_resend_history():
    m = mix("sessions")
    p = plan_of("sessions", 4)
    primes = [r for _, r in p.initial() if r.get("prime")]
    first = [r for _, r in p.initial() if not r.get("prime")]
    assert len(first) == len(primes) == m["population"]
    n = m["system_prompt_len"]
    assert all(r["prompt"][:n] == first[0]["prompt"][:n] for r in first)
    # warm-up pays each session's own history once: one token asked
    # for at time 0, and the first real turn starts from that history
    for pr, r in zip(primes, first):
        assert pr["due"] == 0.0 and pr["max_new"] == 1
        assert r["prompt"][:len(pr["prompt"])] == pr["prompt"]
        assert m["prime_s"] <= r["due"] <= m["prime_s"] + m["stagger_s"]
    assert p.on_finish(dict(primes[0], tokens=[1]), 0.5) is None
    rec = dict(first[0], tokens=[5] * first[0]["max_new"])
    due, nxt = p.on_finish(rec, 12.5)
    assert due > 12.5                        # reply's end + think time
    history = rec["prompt"] + rec["tokens"]
    if nxt["session"] == rec["session"]:
        assert nxt["prompt"][:len(history)] == history
        assert nxt["turn"] == rec["turn"] + 1
    assert len(nxt["prompt"]) + nxt["max_new"] <= m["max_context"]
    # a turn that would be due after the window is never made
    assert p.on_finish(rec, m["warm_s"] + 20.0) is None


def test_session_past_the_context_limit_is_replaced():
    m = dict(mix("sessions"), max_context=640,
             history_len={"dist": "uniform", "min": 0, "max": 128})
    p = loadgen.Sessions(m, 5, 20.0, 1000)
    with pytest.raises(ValueError, match="over max_context"):
        loadgen.Sessions(dict(m, max_context=400), 5, 20.0, 1000)
    r = [r for _, r in p.initial() if not r.get("prime")][0]
    rec = dict(r, tokens=[1] * r["max_new"])
    seen = {r["session"]}
    for _ in range(12):
        nxt = p.on_finish(rec, 1.0)
        assert nxt is not None
        rec = dict(nxt[1], tokens=[1] * nxt[1]["max_new"])
        assert len(rec["prompt"]) + rec["max_new"] <= 640
        seen.add(rec["session"])
    assert len(seen) > 1


class _SSE(BaseHTTPRequestHandler):
    delay = 0.0

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(
            int(self.headers["Content-Length"])))
        if body["prompt"][0] == 99:          # a refused request
            self.send_response(503)
            self.end_headers()
            return
        time.sleep(self.delay)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i in range(body["max_new_tokens"]):
            self.wfile.write(
                f"event: token\ndata: {json.dumps({'token': 7 + i, 'index': i})}\n\n"
                .encode())
            self.wfile.flush()
            time.sleep(0.005)
        self.wfile.write(b": hb\n\nevent: done\ndata: {}\n\n")


class _Plan:
    warm, seconds, grace = 0.2, 0.6, 1.0

    def __init__(self, reqs):
        self.reqs = reqs

    def initial(self):
        return [(r["due"], r) for r in self.reqs]

    def on_finish(self, rec, t):
        return None


@pytest.fixture
def sse_server():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _SSE)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()
    th.join(5)
    assert not th.is_alive()


def test_event_loop_times_frames_and_reports_lateness(sse_server):
    reqs = [{"phase": "window", "due": 0.25 + 0.05 * i,
             "prompt": [1, 2, 3], "max_new": 4} for i in range(5)]
    reqs.append({"phase": "window", "due": 0.3, "prompt": [99],
                 "max_new": 2})
    reqs.append({"phase": "window", "due": 5.0, "prompt": [1],
                 "max_new": 2})           # due after the window: not sent
    start = time.monotonic() + 0.05
    recs = loadgen.run(sse_server, _Plan(reqs), start)
    assert len(recs) == 6
    ok = [r for r in recs if r["status"] == 200]
    assert len(ok) == 5
    for r in ok:
        assert r["tokens"] == [7, 8, 9, 10] and r["error"] is None
        assert r["done"] is not None and len(r["frames"]) == 4
        assert r["frames"] == sorted(r["frames"])
        assert 0 <= r["sent"] - r["due"] < 0.2     # lateness is recorded
        assert r["frames"][0] >= r["sent"]
    bad = [r for r in recs if r["status"] != 200]
    assert len(bad) == 1 and bad[0]["error"] == "HTTP 503"


def test_client_metrics_count_from_the_due_time():
    from harness import serve
    log = {"warm_s": 1.0, "seconds": 2.0, "requests": [
        # due in the window; first frame 0.5 s after it was DUE
        {"due": 1.0, "sent": 1.2, "frames": [1.5, 1.6, 1.8], "tokens": [],
         "status": 200, "error": None, "prompt": [0] * 10},
        # due in the warm-up: its frames in the window still count
        {"due": 0.5, "sent": 0.5, "frames": [0.9, 1.1, 3.5], "tokens": [],
         "status": 200, "error": None, "prompt": [0] * 10},
        # refused: failed, no latency
        {"due": 2.0, "sent": 2.0, "frames": [], "tokens": [],
         "status": 503, "error": "HTTP 503", "prompt": [0]},
        # no first token: failed
        {"due": 2.5, "sent": 2.5, "frames": [], "tokens": [],
         "status": 200, "error": None, "prompt": [0]},
    ]}
    cm = serve.client_metrics(log)
    assert cm["attempted"] == 3 and cm["failed"] == 2
    assert cm["ttft_ms"] == [pytest.approx(500.0)]
    assert sorted(cm["itl_ms"]) == [pytest.approx(100.0),
                                    pytest.approx(200.0)]
    assert cm["out_tok_s"] == pytest.approx(4 / 2.0)
    assert cm["late_ms"][0] == pytest.approx(200.0)
    tokens, positions = serve.profile_work(log, 1.0, 2.0)
    assert tokens == 4 and positions == (10 + 10 + 11 + 12) + (10 + 1) - 10


@pytest.mark.parametrize("spec, cv_over", [
    ({"dist": "gamma", "mean": 2.0, "cv": 3.0}, 1.5),
    ({"dist": "exponential", "mean": 2.0}, 0.6),
])
def test_fixed_set_is_the_mix_s_own(spec, cv_over):
    a = loadgen.fixed_set(spec, 50, as_int=False, total=100.0)
    b = loadgen.fixed_set(spec, 50, as_int=False, total=100.0)
    assert list(a) == list(b)            # no seed reaches it
    assert a.sum() == pytest.approx(100.0)
    # a gamma with CV 3 is far burstier than an exponential
    assert np.std(a) / np.mean(a) > cv_over
    # another count or another number in the spec is another stream
    assert list(loadgen.fixed_set(spec, 49, as_int=False))[:5] != \
        list(loadgen.fixed_set(spec, 50, as_int=False))[:5]
    other = dict(spec, mean=2.5)
    assert list(loadgen.fixed_set(other, 50, as_int=False, total=100.0)) \
        != list(a)


def test_nth_session_is_the_same_under_every_seed():
    p, q = plan_of("sessions", 8), plan_of("sessions", 9)
    fp = [r for _, r in p.initial() if not r.get("prime")]
    fq = [r for _, r in q.initial() if not r.get("prime")]
    assert [(r["due"], len(r["prompt"]), r["max_new"]) for r in fp] == \
        [(r["due"], len(r["prompt"]), r["max_new"]) for r in fq]
    assert fp[3]["prompt"] != fq[3]["prompt"]
    a = p.on_finish(dict(fp[3], tokens=[1] * fp[3]["max_new"]), 9.0)
    b = q.on_finish(dict(fq[3], tokens=[1] * fq[3]["max_new"]), 9.0)
    assert a[0] == b[0] and len(a[1]["prompt"]) == len(b[1]["prompt"])
