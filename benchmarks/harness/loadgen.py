#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX.

It reads one traffic mix (a data file under ``benchmarks/traffic/``),
builds the whole plan from ``--seed``, keeps the schedule on
``time.monotonic()`` (one clock for every process of the machine), talks
HTTP and SSE to the server's loopback socket from ONE thread (a selector
loop; no thread per request), and prints one JSON object: per request
when it was due, when it was sent, when each ``token`` frame was read,
and the ids.

Two kinds of serving mix are understood, both driven by the file's
numbers alone:

``open_loop``   independent users: arrivals on a schedule whether or not
                earlier requests have finished.
``sessions``    a closed population of conversations: a user waits for
                the reply, thinks, and sends the whole history again.

Lengths, gaps and think times are drawn once from the mix's own fixed
stream, in that stream's order: every seed's run holds the same work on
the same schedule.  The seed gives the token ids (and, in the harness,
the weights) and nothing else.  A seed that reorders the schedule
changes which request meets which, and with some tens of requests in a
window that alone moved the tail of time to first token by a third
between seeds.  A second schedule is a second mix file (any change to a
distribution's numbers draws another stream).
"""
from __future__ import annotations

import argparse
import heapq
import json
import selectors
import socket
import sys
import time
import zlib
from urllib.parse import urlparse

import numpy as np

# the mix's lengths and gaps come from this fixed stream, whatever --seed
FIXED_STREAM = 20260927


def draw(spec, n, rng):
    """``n`` values of the distribution ``spec`` from ``rng``."""
    kind = spec["dist"]
    if kind == "lognormal":
        v = np.exp(np.log(spec["median"])
                   + spec["sigma"] * rng.standard_normal(n))
    elif kind == "exponential":
        v = rng.exponential(spec["mean"], n)
    elif kind == "gamma":   # mean and coefficient of variation
        shape = 1.0 / spec["cv"] ** 2
        v = rng.gamma(shape, spec["mean"] / shape, n)
    elif kind == "uniform":
        v = rng.uniform(spec["min"], spec["max"], n)
    elif kind == "fixed":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in spec or "max" in spec:
        v = np.clip(v, spec.get("min", -np.inf), spec.get("max", np.inf))
    return v


def fixed_set(spec, n, as_int=True, total=None):
    """The mix's own ``n`` values of ``spec``, the same in the same
    order for every seed, scaled to sum to ``total`` when given."""
    v = draw(spec, n, np.random.default_rng(
        [FIXED_STREAM, n,
         zlib.crc32(json.dumps(spec, sort_keys=True).encode())]))
    if total is not None and n:
        v = v * (total / v.sum())
    if as_int:
        v = np.rint(v).astype(np.int64)
    return v


class OpenLoop:
    """Arrivals on a schedule.  The warm-up and the window each hold a
    fixed number of requests (rate x length) whose gaps sum to that
    length."""

    def __init__(self, mix, seed, seconds, vocab):
        rng = np.random.default_rng([int(seed), 1])
        self.rate = float(mix["rate_per_s"])
        self.warm, self.grace = float(mix["warm_s"]), float(mix["grace_s"])
        self.seconds = float(seconds)
        self.requests = []
        gaps_spec = dict(mix.get("arrival_gaps",
                                 {"dist": "exponential", "mean": 1.0}))
        for phase, t0, length in (("warm", 0.0, self.warm),
                                  ("window", self.warm, self.seconds)):
            n = int(round(self.rate * length))
            gaps = fixed_set(gaps_spec, n, as_int=False, total=length)
            due = t0 + np.cumsum(gaps) - gaps / 2.0
            plens = fixed_set(mix["prompt_len"], n)
            olens = fixed_set(mix["output_len"], n)
            for i in range(n):
                self.requests.append({
                    "phase": phase, "due": float(due[i]),
                    "prompt": rng.integers(1, vocab, int(plens[i])).tolist(),
                    "max_new": int(olens[i])})

    def initial(self):
        return [(r["due"], r) for r in self.requests]

    def on_finish(self, rec, t_rel):
        return None


class Sessions:
    """A closed population of conversations that share one system
    prompt.  A turn is due when the reply before it has ended and the
    user has thought; the prompt is the whole history.

    Each conversation's lengths and think times come from the mix's own
    fixed stream, keyed by the order in which conversations are opened:
    the n-th conversation has the same history, messages and pauses
    under every seed, and the seed gives the token ids."""

    def __init__(self, mix, seed, seconds, vocab):
        self.mix, self.vocab = mix, vocab
        self.rng = rng = np.random.default_rng([int(seed), 2])
        self.warm, self.grace = float(mix["warm_s"]), float(mix["grace_s"])
        self.seconds = float(seconds)
        n = int(mix["population"])
        self.system = rng.integers(
            1, vocab, int(mix["system_prompt_len"])).tolist()
        self.max_context = int(mix["max_context"])
        longest = int(mix["system_prompt_len"]) + sum(
            int(mix[k]["max"]) for k in ("history_len", "user_len",
                                         "answer_len"))
        if longest > self.max_context:
            raise ValueError(
                f"a new session's first turn can reach {longest} tokens, "
                f"over max_context {self.max_context}")
        self.n_sessions = 0
        self.first = []
        # every session's own history is a miss that warm-up pays: it is
        # sent once at time 0 asking for a single token, which fills the
        # prefix cache; the first real turns follow, staggered
        prime = float(mix.get("prime_s", 0.0))
        stagger = float(mix.get("stagger_s", self.warm * 0.6))
        starts = prime + stagger * (np.arange(n) + 0.5) / n
        for s in range(n):
            sess = self._new_session()
            if prime > 0:
                self.first.append((0.0, {
                    "phase": "warm", "due": 0.0, "prime": True,
                    "prompt": list(sess["history"]), "max_new": 1,
                    "session": sess["session"], "turn": 0, "_sess": sess}))
            self.first.append((float(starts[s]), self._new_turn(
                sess, float(starts[s]))))

    def _ids(self, n):
        return self.rng.integers(1, self.vocab, int(n)).tolist()

    def _new_session(self):
        self.n_sessions += 1
        own = np.random.default_rng([FIXED_STREAM, 7, self.n_sessions])
        hist = int(np.rint(draw(self.mix["history_len"], 1, own)[0]))
        return {"session": self.n_sessions, "own": own, "turn": 0,
                "history": self.system + self._ids(hist)}

    @staticmethod
    def _draw1(sess, spec, as_int=True):
        v = draw(spec, 1, sess["own"])[0]
        return int(np.rint(v)) if as_int else float(v)

    def _new_turn(self, sess, due):
        user = self._draw1(sess, self.mix["user_len"])
        answer = self._draw1(sess, self.mix["answer_len"])
        if len(sess["history"]) + user + answer > self.max_context:
            sess = self._new_session()
            user = self._draw1(sess, self.mix["user_len"])
            answer = self._draw1(sess, self.mix["answer_len"])
        sess["turn"] += 1
        prompt = sess["history"] + self._ids(user)
        return {"phase": "warm" if due < self.warm else "window",
                "due": due, "prompt": prompt, "max_new": answer,
                "session": sess["session"], "turn": sess["turn"],
                "_sess": sess}

    def initial(self):
        return list(self.first)

    def on_finish(self, rec, t_rel):
        """The reply ended at ``t_rel``: the next turn is due after the
        user's think time, with the reply in the history."""
        if rec.get("prime"):
            return None
        sess = rec["_sess"]
        sess["history"] = rec["prompt"] + rec["tokens"]
        due = t_rel + self._draw1(sess, self.mix["think_s"], as_int=False)
        if due >= self.warm + self.seconds:
            return None
        return (due, self._new_turn(sess, due))


KINDS = {"open_loop": OpenLoop, "sessions": Sessions}


def _merged(base, over):
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merged(out[k], v)
        else:
            out[k] = v
    return out


class _Conn:
    __slots__ = ("sock", "buf", "rec", "headers_done")

    def __init__(self, sock, rec):
        self.sock, self.rec = sock, rec
        self.buf = b""
        self.headers_done = False


def run(url, plan, start_at):
    """Drive ``plan`` against ``url``; times in the records are seconds
    since ``start_at`` (monotonic)."""
    u = urlparse(url)
    addr = (u.hostname, u.port)
    sel = selectors.DefaultSelector()
    heap, seq = [], 0
    for due, spec in plan.initial():
        heapq.heappush(heap, (due, seq, spec))
        seq += 1
    window_end = plan.warm + plan.seconds
    hard_end = window_end + plan.grace
    records, inflight = [], 0

    def launch(spec, now):
        nonlocal inflight
        rec = dict(spec)
        rec.update(id=len(records), sent=None, frames=[], tokens=[],
                   status=None, error=None, done=None)
        records.append(rec)
        body = json.dumps({"prompt": spec["prompt"],
                           "max_new_tokens": spec["max_new"],
                           "stream": True}).encode()
        head = (f"POST /generate HTTP/1.1\r\nHost: {u.hostname}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n"
                "Connection: close\r\n\r\n").encode()
        try:
            sock = socket.create_connection(addr, timeout=5.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.sendall(head + body)
            sock.setblocking(False)
        except OSError as e:
            rec["error"] = f"connect/send: {e!r}"
            return
        rec["sent"] = time.monotonic() - start_at
        sel.register(sock, selectors.EVENT_READ, _Conn(sock, rec))
        inflight += 1

    def finish(conn, t_rel):
        nonlocal inflight, seq
        sel.unregister(conn.sock)
        conn.sock.close()
        inflight -= 1
        rec = conn.rec
        if rec["done"] is not None and rec["error"] is None:
            nxt = plan.on_finish(rec, rec["done"])
            if nxt is not None:
                heapq.heappush(heap, (nxt[0], seq, nxt[1]))
                seq += 1
        elif rec["error"] is None:
            rec["error"] = "connection closed before the done frame"

    def on_data(conn, data, t_rel):
        rec = conn.rec
        conn.buf += data
        if not conn.headers_done:
            end = conn.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head, conn.buf = conn.buf[:end], conn.buf[end + 4:]
            lines = head.decode("latin-1").split("\r\n")
            rec["status"] = int(lines[0].split()[1])
            if any(ln.lower().startswith("transfer-encoding") and
                   "chunked" in ln.lower() for ln in lines[1:]):
                rec["error"] = "chunked transfer encoding is not parsed"
            conn.headers_done = True
            if rec["status"] != 200:
                rec["error"] = f"HTTP {rec['status']}"
        if rec["status"] != 200:
            return
        while True:
            end = conn.buf.find(b"\n\n")
            if end < 0:
                return
            frame, conn.buf = conn.buf[:end], conn.buf[end + 2:]
            event, payload = None, []
            for ln in frame.decode().split("\n"):
                ln = ln.rstrip("\r")
                if ln.startswith(":") or not ln:
                    continue
                if ln.startswith("event:"):
                    event = ln[6:].strip()
                elif ln.startswith("data:"):
                    payload.append(ln[5:].lstrip())
            if event == "token":
                rec["frames"].append(t_rel)
                rec["tokens"].append(int(json.loads(payload[0])["token"]))
            elif event == "done":
                rec["done"] = t_rel
            elif event == "error":
                rec["error"] = "error frame: " + "".join(payload)[:300]

    while True:
        now = time.monotonic() - start_at
        if now >= hard_end or (not heap and not inflight):
            break
        while heap and heap[0][0] <= now:
            due, _, spec = heapq.heappop(heap)
            if due < window_end:
                launch(spec, now)
        wait = 0.25
        if heap:
            wait = min(wait, max(heap[0][0] - now, 0.0))
        wait = min(wait, hard_end - now)
        for key, _ in sel.select(wait):
            conn = key.data
            try:
                data = conn.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                conn.rec["error"] = conn.rec["error"] or f"recv: {e!r}"
                data = b""
            t_rel = time.monotonic() - start_at
            if data:
                on_data(conn, data, t_rel)
            else:
                finish(conn, t_rel)
    for key in list(sel.get_map().values()):
        key.data.sock.close()
    sel.close()
    for rec in records:
        rec.pop("_sess", None)
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--mix", required=True, help="the traffic mix's file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--start-at", type=float, required=True,
                    help="time.monotonic() at which the warm-up begins")
    ap.add_argument("--override", default="{}",
                    help="JSON laid over the mix (a sweep's rate, a "
                         "rehearsal's sizes)")
    args = ap.parse_args(argv)
    with open(args.mix) as f:
        mix = json.load(f)
    mix = _merged(mix, json.loads(args.override))
    plan = KINDS[mix["kind"]](mix, args.seed, args.seconds, args.vocab)
    while time.monotonic() < args.start_at:
        time.sleep(min(0.05, max(args.start_at - time.monotonic(), 0)))
    records = run(args.url, plan, args.start_at)
    json.dump({"start_at": args.start_at, "warm_s": plan.warm,
               "seconds": plan.seconds, "grace_s": plan.grace,
               "requests": records}, sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
