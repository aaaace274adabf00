"""A serving cell: the program's ``Engine`` behind ``EngineServer`` in this
process (which holds the chip), the load generator as a child process
that never imports JAX, and everything measured from the client's side
of the loopback socket."""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np

from . import common, reducers

HERE = os.path.dirname(os.path.abspath(__file__))


def _post(url, prompt, max_new):
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps({"prompt": prompt,
                         "max_new_tokens": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1500) as resp:
        return json.loads(resp.read())


def warm_programs(url, cfg, seed):
    """Compile (or read from the cache) the programs the traffic uses,
    before any traffic: a prompt of more than one chunk, then the same
    prompt again so that a cached prefix is adopted once."""
    rng = np.random.default_rng([int(seed), 3])
    n = int(cfg["engine"].get("prefill_chunk") or 16) * 2 + 5
    n = min(n, cfg["engine"]["max_seq_len"] - 8)
    prompt = rng.integers(1, cfg["dims"]["vocab_size"], n).tolist()
    for _ in range(2):
        out = _post(url, prompt, 4)
        if len(out["generated"]) != 4:
            raise RuntimeError(f"warm-up request returned {out}")


def run_loadgen(url, mix_path, override, seed, seconds, vocab, start_at,
                monitor):
    """Start the child, call ``monitor()`` about once a second while it
    runs, and return what it printed."""
    with tempfile.TemporaryFile("w+") as out:
        cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
               "--url", url, "--mix", mix_path, "--seed", str(seed),
               "--seconds", str(seconds), "--vocab", str(vocab),
               "--start-at", repr(start_at),
               "--override", json.dumps(override)]
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        proc = subprocess.Popen(cmd, stdout=out, env=env)
        try:
            while proc.poll() is None:
                monitor()
                time.sleep(0.2)
        finally:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if proc.returncode != 0:
            raise RuntimeError(f"load generator exited {proc.returncode}")
        out.seek(0)
        return json.load(out)


def client_metrics(log):
    """End-to-end metrics and counts from the load generator's records;
    every time is seconds since the warm-up began."""
    w0 = log["warm_s"]
    w1 = w0 + log["seconds"]
    due = [r for r in log["requests"] if w0 <= r["due"] < w1]
    ok = [r for r in due if r["error"] is None and r["status"] == 200
          and r["frames"]]
    ttft = [(r["frames"][0] - r["due"]) * 1e3 for r in ok]
    gaps, frames_in = [], 0
    for r in log["requests"]:
        f = [t for t in r["frames"] if w0 <= t < w1]
        frames_in += len(f)
        gaps += [(b - a) * 1e3 for a, b in zip(f, f[1:])]
    return {
        "attempted": len(due), "failed": len(due) - len(ok),
        "ttft_ms": ttft, "itl_ms": gaps,
        "out_tok_s": frames_in / log["seconds"],
        "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in due
                    if r["sent"] is not None],
        "errors": sorted({r["error"] for r in due if r["error"]})[:5],
    }


def profile_work(log, p0, p1):
    """Tokens emitted in [p0, p1) (seconds since the warm-up began) and
    the live cached positions each of them had to read."""
    tokens = positions = 0
    for r in log["requests"]:
        n = len(r["prompt"])
        for i, t in enumerate(r["frames"]):
            if p0 <= t < p1:
                tokens += 1
                positions += n + i
    return tokens, positions


def pick_sample(log, seed, tokens, max_requests):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``tokens`` served tokens (or ``max_requests``)."""
    done = [r for r in log["requests"]
            if r["done"] is not None and r["error"] is None and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r["prompt"]) + len(r["tokens"])),
                             r["id"]))
    rng = np.random.default_rng([int(seed), 4])
    rest = [done[1 + i] for i in rng.permutation(len(done) - 1)]
    sample, have = [], 0
    for r in [done[0]] + rest:
        if have >= tokens or len(sample) >= max_requests:
            break
        sample.append(r)
        have += len(r["tokens"])
    return sample


def check_served(cfg, seed, sample, precision="highest"):
    """Teacher-forced comparison with the plain reference: over each
    sampled request's prompt and served tokens, how far the served
    token's logit lies below the reference's best."""
    ref = common.load_reference(cfg)
    dims = cfg["dims"]
    pad_to = int(cfg["check"].get("pad_to", 256))
    rows = int(cfg["check"].get("rows_per_block", 4))
    T = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    T = -(-T // pad_to) * pad_to
    n_rows = -(-len(sample) // rows) * rows      # whole blocks: one shape
    ids = np.zeros((n_rows, T), np.int32)
    served = np.full((n_rows, T), -1, np.int32)
    for b, r in enumerate(sample):
        seq = r["prompt"] + r["tokens"]
        ids[b, :len(seq)] = seq
        n = len(r["prompt"])
        served[b, n - 1:n - 1 + len(r["tokens"])] = r["tokens"]

    def get(names):
        return common.seeded_weights(cfg, seed, names=frozenset(names))
    regret, valid, top = ref.served_regret(get, dims, ids, served,
                                           precision, rows_per_block=rows)
    v = regret[valid]
    return {"regret_max": float(v.max()), "regret_mean": float(v.mean()),
            "tokens_compared": int(valid.sum()),
            "argmax_agree": float((top[valid] == served[valid]).mean())}


class Counters:
    """The program's registry (every counter and gauge) plus JAX's own
    compile count, sampled by the harness; ``peak`` keeps the highest
    sample of each."""

    def __init__(self, registry, compiles):
        self.registry, self.compiles = registry, compiles
        self.peak = {}

    def snapshot(self):
        snap = {"jax.backend_compiles": self.compiles.count}
        for name, m in self.registry.items():
            v = getattr(m, "value", None)
            if isinstance(v, (int, float)):
                snap[name] = v
        for k, v in snap.items():
            if v > self.peak.get(k, float("-inf")):
                self.peak[k] = v
        return snap

    def busy(self):
        return (self.registry.get("serving.slot_occupancy").value
                or self.registry.get("serving.queue_depth").value)


def delta(before, after):
    return {k: after[k] - before[k] for k in after if k in before}


def measure(url, cfg, mix_path, override, args, counters):
    """One run of the load generator: warm-up traffic, the window, the
    grace.  The parent samples the counters at the window's edges and
    once a second inside it and, traced, profiles the device for a few
    seconds in the window's middle.  Times ``w0``, ``w1`` and ``profile``
    are ``time.monotonic()``."""
    import jax
    with open(mix_path) as f:
        mix = common.merged(json.load(f), override)
    start_at = time.monotonic() + 0.5
    w0 = start_at + float(mix["warm_s"])
    w1 = w0 + args.seconds
    p_len = min(float(cfg["check"].get("profile_s", 5.0)),
                args.seconds * 0.5)
    p_start = w0 + (args.seconds - p_len) / 2.0
    win = {"start_at": start_at, "w0": w0, "w1": w1, "before": None,
           "after": None, "profile": None, "p_before": None,
           "p_after": None,
           "profile_dir": os.path.join(common.scratch_dir(), "profile")}
    last = [0.0]

    # The capture starts inside ``start_profile`` and ends inside
    # ``stop_trace``; the clock is read after the one and before the
    # other, so the capture CONTAINS ``[p0, p1]`` and its device events
    # may span a little more than ``p1 - p0`` (``xplane.reduce`` widens
    # the window it reports to hold them).  The counters and
    # ``profile_work`` take ``[p0, p1]``: a roofline share can only be
    # understated by that, never overstated.
    def stop_profile():
        win["p_after"] = counters.snapshot()
        win["profile"][1] = time.monotonic()
        jax.profiler.stop_trace()

    def monitor():
        now = time.monotonic()
        if win["before"] is None and now >= w0:
            win["before"] = counters.snapshot()
        if args.trace and win["profile"] is None and p_start <= now < w1:
            common.start_profile(win["profile_dir"])
            win["p_before"] = counters.snapshot()
            win["profile"] = [time.monotonic(), None]
        if win["profile"] and win["profile"][1] is None \
                and now >= win["profile"][0] + p_len:
            stop_profile()
        if win["after"] is None and now >= w1:
            win["after"] = counters.snapshot()
        if now - last[0] >= 1.0 and w0 <= now < w1:
            last[0] = now
            counters.snapshot()

    win["log"] = run_loadgen(url, mix_path, override, args.seed,
                             args.seconds, cfg["dims"]["vocab_size"],
                             start_at, monitor)
    if win["profile"] and win["profile"][1] is None:
        stop_profile()
    for edge in ("before", "after"):
        if win[edge] is None:
            win[edge] = counters.snapshot()
    win["client"] = client_metrics(win["log"])
    return win


def sweep(url, cfg, mix_path, args, counters):
    """The knee sweep: the mix at each rate under one set-up, one line
    per rate."""
    for rate in args.sweep:
        counters.peak.clear()
        win = measure(url, cfg, mix_path,
                      dict(args.mix_override, rate_per_s=rate), args,
                      counters)
        cm = win["client"]
        common.say(json.dumps({
            "sweep_rate": rate, "attempted": cm["attempted"],
            "failed": cm["failed"],
            "ttft_p50_ms": reducers.percentile(cm["ttft_ms"], 50),
            "ttft_p90_ms": reducers.percentile(cm["ttft_ms"], 90),
            "itl_p95_ms": reducers.percentile(cm["itl_ms"], 95),
            "out_tok_s": cm["out_tok_s"],
            "queue_depth_peak": counters.peak.get("serving.queue_depth"),
            "late_p95_ms": reducers.percentile(cm["late_ms"], 95)}))
        # the child is gone but the engine still decodes what it
        # abandoned: let the slots drain before the next rate
        t_wait = time.monotonic()
        while counters.busy() and time.monotonic() - t_wait < 120.0:
            time.sleep(0.5)


def traced_sources(cfg, args, dev, win, trace, counters, memory_peak):
    """What the per-layer metrics' readers see: the engine's spans inside
    the window, counter deltas, the client's series and the reduced
    device trace (and its own reduction of it)."""
    from . import xplane
    off = (time.perf_counter() - time.monotonic()) * 1e6
    lo, hi = win["w0"] * 1e6 + off, win["w1"] * 1e6 + off
    spans = [e for e in trace["traceEvents"]
             if e.get("ph") in ("X", "i") and lo <= e["ts"] < hi]
    p0, p1 = win["profile"]
    dev_trace = xplane.reduce(
        xplane.find_trace(win["profile_dir"]),
        {e["name"] for e in spans if e["ph"] == "X"}, window_s=p1 - p0)
    p_tok, p_pos = profile_work(win["log"], p0 - win["start_at"],
                                p1 - win["start_at"])
    p_delta = delta(win["p_before"], win["p_after"])
    return {
        "spans": spans,
        "counters": {"delta": delta(win["before"], win["after"]),
                     "profile_delta": p_delta,
                     "peak": counters.peak, "last": win["after"]},
        "client": {"late_ms": win["client"]["late_ms"],
                   "ttft_ms": win["client"]["ttft_ms"]},
        "device": dev_trace,
        # what the profiled interval asked of the chip; the program
        # file's counts take it whole
        "work": {"tokens_emitted": p_tok, "live_positions": p_pos,
                 "prefill_tokens": p_delta.get("serving.prefill_tokens", 0),
                 "num_slots": cfg["engine"]["num_slots"],
                 "counters": p_delta},
        "ctx": {"cfg": cfg,
                "peaks": common.peaks(dev, args.rehearse),
                "window_s": args.seconds,
                "memory_peak_bytes": memory_peak},
    }


def check(cfg, seed, log, flight):
    """(``correct``, the numbers compared), after the server is gone:
    exact counts, then the reference over a sample of finished
    requests."""
    finished = [r for r in log["requests"] if r["done"] is not None]
    numbers = [
        ("finished_with_wrong_length",
         sum(len(r["tokens"]) != r["max_new"] for r in finished), 0),
        ("engine_step_failures", 0 if flight is None else 1, 0)]
    sample = pick_sample(log, seed, int(cfg["check"]["tokens"]),
                         int(cfg["check"]["max_requests"]))
    if not sample:
        numbers.append(("requests_finished", 0, None))
        return common.judge(numbers)
    t_ref = time.monotonic()
    got = check_served(cfg, seed, sample)
    common.say(f"reference: {got['tokens_compared']} served tokens of "
               f"{len(sample)} requests compared in "
               f"{time.monotonic() - t_ref:.1f}s; argmax agrees on "
               f"{100 * got['argmax_agree']:.1f}%")
    for name in ("regret_max", "regret_mean"):
        numbers.append((name, got[name], cfg["limits"][name]))
    return common.judge(numbers)


def run(cell, cfg, mix_path, args, t_proc0):
    from paddle_tpu.serving import Engine, EngineServer

    dev = common.device_info()
    compiles = common.CompileCounter()
    t_a = time.monotonic()
    model = common.load_program(cfg).build(cfg, args.seed)
    t_b = time.monotonic()
    opts = dict(cfg["engine"])
    if args.control:
        opts.update(cfg["controls"][args.control])
        common.say(f"CONTROL {args.control}: engine options {opts}")
    if args.trace:
        opts.update(trace_annotations=True, trace_capacity=1 << 21)
    engine = Engine(model, **opts)
    counters = Counters(engine.registry, compiles)
    common.say(f"set-up so far: imports and device {t_a - t_proc0:.1f}s, "
               f"model and weights {t_b - t_a:.1f}s, engine "
               f"{time.monotonic() - t_b:.1f}s")
    with EngineServer(engine, port=0, result_timeout=600.0) as srv:
        warm_programs(srv.address, cfg, args.seed)
        common.say(f"programs warm after "
                   f"{time.monotonic() - t_proc0:.1f}s")
        if args.sweep:
            sweep(srv.address, cfg, mix_path, args, counters)
            engine.stop(drain=False)
            return None
        win = measure(srv.address, cfg, mix_path, args.mix_override, args,
                      counters)
        memory_peak = common.memory_peak_bytes()
        trace = engine.chrome_trace() if args.trace else None
        flight = engine.last_flight
        engine.stop(drain=False)
    del engine, srv, model
    gc.collect()
    correct, compared = check(cfg, args.seed, win["log"], flight)
    # (the reference's time is not in setup_s)

    cm = win["client"]
    ttft_mean = (sum(cm["ttft_ms"]) / len(cm["ttft_ms"])
                 if cm["ttft_ms"] else None)
    device = dict(dev, memory_peak_bytes=memory_peak)
    breakdown = None
    if not args.trace:
        metrics = common.end_to_end(cell, {
            "setup_s": win["w0"] - t_proc0,
            "ttft_mean_ms": ttft_mean,
            "itl_p95_ms": reducers.percentile(cm["itl_ms"], 95),
            "out_tok_s": cm["out_tok_s"]})
    else:
        src = traced_sources(cfg, args, dev, win, trace, counters,
                             memory_peak)
        metrics = common.layer_metrics(cell, src, args.dump_sources)
        device.update({k: src["device"][k] for k in common.DEVICE_WINDOW})
        breakdown = {"device_ops": src["device"]["device_ops"],
                     "idle_gaps": src["device"]["idle_gaps"]}
    common.say("client: " + json.dumps({
        "ttft_ms": {q: reducers.percentile(cm["ttft_ms"], q)
                    for q in (50, 75, 90, 99)},
        "ttft_mean_ms": ttft_mean,
        "itl_ms": {q: reducers.percentile(cm["itl_ms"], q)
                   for q in (50, 95, 99)},
        "out_tok_s": cm["out_tok_s"],
        "late_p95_ms": reducers.percentile(cm["late_ms"], 95)}))
    if cm["errors"]:
        common.say(f"request errors: {cm['errors']}")
    d = delta(win["before"], win["after"])
    common.say(f"compiles in the window: serving.compiles_total "
               f"+{d.get('serving.compiles_total', 0):g}, jax backend "
               f"compiles +{d['jax.backend_compiles']}")
    return {"correct": correct, "attempted": cm["attempted"],
            "failed": cm["failed"], "metrics": metrics, "device": device,
            "breakdown": breakdown, "compared": compared}
