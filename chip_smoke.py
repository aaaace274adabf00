#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

    python3 chip_smoke.py                 # the check: needs an accelerator
    python3 chip_smoke.py --rehearse-cpu  # same code at `tiny` on the CPU

One process drives the main path through the entry points a user calls
and fails on the first phase that is wrong:

1. serving, the default path (``attn_impl="xla"``: XLA programs whose
   decode attention core is, on one TPU, the Pallas kernel behind
   ``_slot_attn``; ``/healthz`` ``attn_core`` says which form and why):
   ``GPTModel.from_config("gpt3-1.3b")`` in bf16 inside ``Engine(num_slots=8, max_seq_len=2048, kv_block_size=16,
   prefill_chunk=128)`` inside ``EngineServer(port=0)``; two waves of
   eight concurrent ``POST /generate`` over the real socket (one of them
   streamed), then a third wave after ``jax.clear_caches()`` so the same
   programs are built again from the persistent compile cache;
2. serving, the Pallas ragged kernel (``attn_impl="ragged"``), same
   waves, ids compared with phase 1;
3. training: ``gpt2-medium`` bf16, fused loss, AdamW, 8 x 1,024 through
   ``TrainStep``, three steps, then a fourth from the compile cache.

With no accelerator it exits non-zero, names the platform it found and
prints no result; there is no automatic downgrade.  ``--rehearse-cpu``
is the explicit CPU run for use before chip time is spent: its output
says ``platform: cpu`` and its last line carries no ``"ok"``.
``--mesh MPxDP`` (repeatable) is the four-chip form of the serving
phases: a one-chip reference, then each mesh with the XLA path and the
kernel, reporting where parameter and KV-pool shards sit.

The last line of stdout is one JSON object; on the chip
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# A divergence from the reference counts as rounding only when the
# reference itself nearly tied: its logit for the engine's token lies
# within this much of its best.  The weights are random, so the logits
# over 50,304 words are close to flat (top-2 gaps around 0.2, bf16
# steps of 0.016 at their size) and near-ties flip between program
# shapes at any position; a wrong row, lane or block gives a token
# whose logit sits units below the best.
TIE_TOL = 0.25

SIZES = {
    # the chip check: full widths of the largest GPT_CONFIGS entry
    "chip": dict(
        serve_cfg="gpt3-1.3b", dtype="bfloat16", num_slots=8,
        max_seq_len=2048, block=16, chunk=128, new_tokens=32,
        prompt_lens=(40, 40, 200, 200, 200, 520, 520, 520),
        shared_prefix=192,
        train_cfg="gpt2-medium", train_batch=8, train_seq=1024),
    # --rehearse-cpu: the same phases at a size the CPU runs in seconds
    "rehearsal": dict(
        serve_cfg="tiny", dtype=None, num_slots=8,
        max_seq_len=64, block=8, chunk=8, new_tokens=8,
        prompt_lens=(5, 5, 20, 20, 20, 40, 40, 40),
        shared_prefix=16,
        train_cfg="tiny", train_batch=2, train_seq=32),
}


class Failed(Exception):
    """A phase did not meet its check."""


def say(*parts):
    print(*parts, flush=True)


def check(cond, msg):
    if not cond:
        raise Failed(msg)


# --------------------------------------------------------------------------
# compile-cache accounting (jax's own events)
# --------------------------------------------------------------------------

class CacheCounter:
    """Counts persistent-compile-cache reads and writes as JAX reports
    them, so a phase can show that its second build was served from the
    cache."""

    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.writes += 1

    def mark(self):
        return (self.hits, self.writes)

    def since(self, mark):
        return {"cache_hits": self.hits - mark[0],
                "cache_writes": self.writes - mark[1]}


# --------------------------------------------------------------------------
# HTTP client side
# --------------------------------------------------------------------------

def _post_generate(url, body, timeout):
    from paddle_tpu.serving.stream import parse_sse
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if not body.get("stream"):
            return resp.status, json.loads(resp.read())
        tokens, done = [], None
        for event, data in parse_sse(resp):
            if event == "token":
                tokens.append(json.loads(data))
            elif event == "done":
                done = json.loads(data)
            elif event == "error":
                raise Failed(f"stream ended with an error frame: {data}")
        check(done is not None, "stream ended without a done frame")
        done["streamed_frames"] = len(tokens)
        return resp.status, done


def _get(url, path):
    with urllib.request.urlopen(url + path, timeout=30) as resp:
        body = resp.read()
    return body.decode() if path == "/metrics" else json.loads(body)


def _metric(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise Failed(f"/metrics has no {name}")


def run_wave(url, prompts, new_tokens, timeout):
    """Eight concurrent POST /generate from threads of this process
    (the last one streamed); returns the payloads in prompt order."""
    out = [None] * len(prompts)

    def one(i):
        # the sampling fields stay at their defaults: greedy
        body = {"prompt": prompts[i], "max_new_tokens": new_tokens}
        if i == len(prompts) - 1:
            body["stream"] = True
        try:
            out[i] = _post_generate(url, body, timeout)
        except urllib.error.HTTPError as e:
            out[i] = (e.code, e.read().decode(errors="replace"))
        except Exception as e:  # reported by the caller, per request
            out[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    wall = time.perf_counter() - t0
    for i, r in enumerate(out):
        check(r is not None, f"request {i}: no answer after {timeout}s")
        check(not isinstance(r, Exception), f"request {i}: {r!r}")
        check(r[0] == 200, f"request {i}: HTTP {r[0]}: {r[1]}")
    return [r[1] for r in out], wall


# --------------------------------------------------------------------------
# the serving phase
# --------------------------------------------------------------------------

def make_prompts(sz, vocab):
    import numpy as np
    rng = np.random.RandomState(1234)
    prompts = [rng.randint(1, vocab, n).tolist() for n in sz["prompt_lens"]]
    # two requests share a block-aligned prefix
    n = sz["shared_prefix"]
    check(n % sz["block"] == 0, "shared prefix must be block-aligned")
    pair = [i for i, p in enumerate(prompts) if len(p) > n][:2]
    check(len(pair) == 2, "need two prompts longer than the shared prefix")
    prompts[pair[1]][:n] = prompts[pair[0]][:n]
    return prompts


def build_serving_model(sz):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTModel
    paddle.seed(0)
    model = GPTModel.from_config(sz["serve_cfg"], dropout=0.0)
    if sz["dtype"]:
        model.to(dtype=sz["dtype"])
    model.eval()
    return model


def check_ids(gen, vocab, n_new, what):
    check(len(gen) == n_new,
          f"{what}: {len(gen)} generated ids, wanted {n_new}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in gen),
          f"{what}: ids outside the vocabulary: {gen}")


def serving_phase(name, model, sz, prompts, cache, *, attn_impl=None,
                  mesh=None, rebuild=False):
    """Two waves through EngineServer over the socket (plus a third
    after jax.clear_caches() when ``rebuild``); every check fatal.
    Returns (ids per request, report dict)."""
    import jax
    from paddle_tpu.serving import Engine, EngineServer

    kw = {}
    if attn_impl is not None:
        kw["attn_impl"] = attn_impl
    if mesh is not None:
        kw["mesh"] = mesh
    n_new = sz["new_tokens"]
    report = {"phase": name}
    mark = cache.mark()
    t0 = time.perf_counter()
    engine = Engine(model, num_slots=sz["num_slots"],
                    max_seq_len=sz["max_seq_len"],
                    kv_block_size=sz["block"],
                    prefill_chunk=sz["chunk"], **kw)
    report["engine_build_s"] = round(time.perf_counter() - t0, 2)
    vocab = engine.vocab_size
    compile_ms0 = engine._m_compile_ms.sum   # the registry is shared
    with EngineServer(engine, port=0, result_timeout=1100.0) as srv:
        url = srv.address
        hz = _get(url, "/healthz")
        dev0 = jax.devices()[0]
        check(hz["platform"] == dev0.platform
              and hz["device_kind"] == dev0.device_kind,
              f"/healthz places the engine on {hz['platform']} "
              f"{hz['device_kind']}, jax reports {dev0.platform} "
              f"{dev0.device_kind}")
        report["healthz"] = {k: hz[k] for k in (
            "platform", "device_kind", "device_ids", "attn_impl",
            "attn_core", "mesh_shape")}
        if attn_impl is None and hz["attn_core"] is not None:
            # the decode attention's core: the kernel on one TPU at
            # heads of whole lane tiles, the walk everywhere else
            want = ("kernel" if dev0.platform == "tpu" and mesh is None
                    and hz["attn_core"]["head_dim"] % 128 == 0
                    else "walk")
            check(hz["attn_core"]["form"] == want,
                  f"/healthz attn_core is {hz['attn_core']}, wanted "
                  f"the {want} on {dev0.platform}")
        n_dev = mesh[0] * mesh[1] if mesh else 1
        check(len(hz["device_ids"]) == n_dev,
              f"KV pools sit on devices {hz['device_ids']}, wanted "
              f"{n_dev} distinct devices")
        dbg = _get(url, "/debug/requests")["engine"]
        check(dbg["device_ids"] == hz["device_ids"],
              "/debug/requests and /healthz disagree on devices")

        waves = []
        for w in range(3 if rebuild else 2):
            if w == 2:
                # same engine, same programs, built again: the
                # in-memory executables are dropped, so every
                # program is traced again and must come back from
                # the persistent cache
                report["first_build_cache"] = cache.since(mark)
                jax.clear_caches()
                mark_rebuild = cache.mark()
            before = _get(url, "/metrics")
            outs, wall = run_wave(url, prompts, n_new, 1100.0)
            after = _get(url, "/metrics")
            for i, (o, p) in enumerate(zip(outs, prompts)):
                check_ids(o["generated"], vocab, n_new,
                          f"{name} wave {w + 1} request {i}")
                check(o["ids"] == p + o["generated"],
                      f"{name} wave {w + 1} request {i}: ids do "
                      "not start with the prompt")
            check(outs[-1].get("streamed_frames") == n_new,
                  f"{name} wave {w + 1}: streamed request sent "
                  f"{outs[-1].get('streamed_frames')} token frames")
            waves.append({
                "wall_s": round(wall, 2),
                "compiles": int(
                    _metric(after, "serving_compiles_total")
                    - _metric(before, "serving_compiles_total")),
                "prefix_hits": int(
                    _metric(after, "serving_prefix_hits")
                    - _metric(before, "serving_prefix_hits")),
                "ids": [o["generated"] for o in outs]})
        check(engine.last_flight is None,
              f"{name}: the engine recorded a step failure: "
              + str(((engine.last_flight or {}).get("metadata") or {})
                    .get("flight-recorder", {}).get("error")))
        check(waves[1]["ids"] == waves[0]["ids"],
              f"{name}: second wave's greedy ids differ from the "
              "first's")
        check(waves[1]["compiles"] == 0,
              f"{name}: {waves[1]['compiles']} programs compiled "
              "during the second wave")
        report["compiles"] = sum(w["compiles"] for w in waves)
        report["compile_wall_s"] = round(
            (engine._m_compile_ms.sum - compile_ms0) / 1e3, 2)
        report["prefix_hits"] = [w["prefix_hits"] for w in waves]
        check(sum(report["prefix_hits"]) > 0,
              f"{name}: serving_prefix_hits did not move")
        report["wave_wall_s"] = [w["wall_s"] for w in waves]
        # set-up time of the first build: everything the first
        # wave spent beyond a steady wave
        report["cold_build_s"] = round(
            waves[0]["wall_s"] - waves[1]["wall_s"], 2)
        report.setdefault("first_build_cache", cache.since(mark))
        if rebuild:
            check(waves[2]["ids"] == waves[0]["ids"],
                  f"{name}: ids changed after the rebuild")
            report["warm_build_s"] = round(
                waves[2]["wall_s"] - waves[1]["wall_s"], 2)
            report["rebuild_cache"] = cache.since(mark_rebuild)
            check(report["rebuild_cache"]["cache_hits"] > 0,
                  f"{name}: the rebuild read nothing from the "
                  "compile cache")
        if mesh is not None:
            report["shards"] = shard_report(engine)
    return waves[0]["ids"], report


def shard_report(engine):
    """Where one sharded parameter and one KV pool actually sit."""
    import jax
    pool = engine.k_pools[0]
    pool = getattr(pool, "codes", pool)
    param = None
    for nm, p in engine.model.named_parameters():
        if getattr(p, "partition_spec", None) is not None \
                and "qkv" in nm:
            param = (nm, p._data)
            break
    if param is None:  # mp == 1: parameters replicate over dp
        nm, p = next(iter(engine.model.named_parameters()))
        param = (nm, p._data)

    def where(a):
        # device id -> the slice of the global array it holds
        return {str(s.device.id): "[" + ",".join(
            ":" if i.start is None and i.stop is None
            else f"{i.start or 0}:{i.stop}" for i in s.index) + "]"
            for s in a.addressable_shards}

    out = {"kv_pool": where(pool), "param": param[0],
           "param_shards": where(param[1]),
           "state_pos": where(engine._dev_state["pos"])
           if engine._dev_state else None}
    n = engine.mp * engine.dp
    for key in ("kv_pool", "param_shards"):
        check(len(out[key]) == n,
              f"{key} shards sit on devices {sorted(out[key])}, wanted "
              f"{n} distinct of {[d.id for d in jax.devices()]}")
    return out


# --------------------------------------------------------------------------
# agreement with a reference
# --------------------------------------------------------------------------

def reference_ids(model, prompts, n_new):
    """``model.generate()`` per request — requests of one length go as
    one batch, so the reference compiles once per distinct length."""
    import numpy as np
    import paddle_tpu as paddle
    by_len = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(len(p), []).append(i)
    out = [None] * len(prompts)
    for n, idx in sorted(by_len.items()):
        ids = paddle.to_tensor(
            np.asarray([prompts[i] for i in idx], np.int32))
        got = np.asarray(model.generate(
            ids, max_new_tokens=n_new, compiled=True).numpy())
        for row, i in zip(got, idx):
            out[i] = [int(t) for t in row[n:]]
    return out


def first_divergences(got, want):
    return [next((d for d, (a, b) in enumerate(zip(g, w)) if a != b), None)
            for g, w in zip(got, want)]


def compare(name, model, prompts, got, want, sz, what):
    """Report agreement of ``got`` with ``want``; at each request's
    first divergent position ask the model itself (one eager forward
    over the agreed prefix) how close the two tokens were.  Fatal only
    when the reference did not nearly tie (TIE_TOL)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.core import autograd
    from paddle_tpu.core.tensor import Tensor
    divs = first_divergences(got, want)
    rows = [i for i, d in enumerate(divs) if d is not None]
    report = {"against": what,
              "requests_identical": len(got) - len(rows),
              "requests": len(got), "divergences": []}
    if rows:
        seqs = [prompts[i] + want[i][:divs[i]] for i in rows]
        pad = -(-max(len(s) for s in seqs) // sz["block"]) * sz["block"]
        batch = np.zeros((len(seqs), pad), np.int32)
        for r, s in enumerate(seqs):
            batch[r, :len(s)] = s
        # the ids go where the model's parameters are (replicated over
        # their mesh when an engine has sharded them)
        sh = next(iter(model.parameters()))._data.sharding
        if isinstance(sh, NamedSharding):
            sh = NamedSharding(sh.mesh, PartitionSpec())
        with autograd.no_grad():
            logits = model(Tensor(jax.device_put(batch, sh)))
        for r, i in enumerate(rows):
            row = np.asarray(
                logits._data[r, len(seqs[r]) - 1], np.float32)
            top2 = np.sort(row)[-2:]
            d = divs[i]
            at = len(prompts[i]) + d
            gap = float(row.max() - row[got[i][d]])
            report["divergences"].append({
                "request": i, "position": d,
                "at_block_edge": at % sz["block"] == 0,
                "at_chunk_edge": at % sz["chunk"] == 0,
                "top2_margin": round(float(top2[1] - top2[0]), 4),
                "gap_to_engine_token": round(gap, 4)})
    say(f"[{name}] agreement with {what}: "
        f"{report['requests_identical']}/{report['requests']} requests "
        "identical")
    for dv in report["divergences"]:
        say(f"[{name}]   request {dv['request']} diverges at generated "
            f"position {dv['position']}"
            + (" (block edge)" if dv["at_block_edge"] else "")
            + (" (chunk edge)" if dv["at_chunk_edge"] else "")
            + f": reference top-2 margin {dv['top2_margin']}, its gap "
            f"to the engine's token {dv['gap_to_engine_token']}")
    bad = [dv for dv in report["divergences"]
           if dv["gap_to_engine_token"] > TIE_TOL]
    check(not bad,
          f"{name}: divergence from {what} that is not a near-tie "
          f"(gap > {TIE_TOL}): {bad}")
    return report


# --------------------------------------------------------------------------
# the training phase
# --------------------------------------------------------------------------

def training_phase(sz, cache):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPTModel
    from paddle_tpu.parallel.train_step import TrainStep

    paddle.seed(0)
    # bench_gpt2's configuration, as examples/train_gpt2.py writes it
    model = GPTModel.from_config(sz["train_cfg"], dropout=0.1,
                                 fused_loss=True)
    if sz["dtype"]:
        model.to(dtype=sz["dtype"])
    opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                          parameters=model.parameters())
    step = TrainStep(model, opt, loss_fn=None)
    vocab = int(model.embeddings.word_embeddings.weight.shape[0])
    ids = np.random.RandomState(0).randint(
        0, vocab, (sz["train_batch"], sz["train_seq"] + 1)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]

    def one():
        t0 = time.perf_counter()
        loss = float(step.step([x, y]).numpy())   # .numpy() waits
        return loss, time.perf_counter() - t0

    # which attention the step is traced with (one count a call site)
    from paddle_tpu import monitor
    from paddle_tpu.nn.functional.attention import attention_path
    sites = {p: monitor.counter("nn.attention." + p)
             for p in ("blockwise", "dense")}
    before = {p: c.value for p, c in sites.items()}
    mark = cache.mark()
    losses, walls = zip(*[one() for _ in range(3)])
    paths = {p: int(c.value - before[p]) for p, c in sites.items()}
    want = attention_path(jax.default_backend(), sz["train_seq"],
                          sz["train_seq"], model.blocks[0].attn.head_dim,
                          False, devices=step.mesh.size,
                          dtype=sz["dtype"] or "float32")
    report = {"phase": "training", "losses": [round(l, 4) for l in losses],
              "attention_call_sites": paths,
              "step_wall_s": [round(w, 2) for w in walls],
              "cold_build_s": round(walls[0] - walls[2], 2),
              "first_build_cache": cache.since(mark)}
    check(all(np.isfinite(l) for l in losses),
          f"training: loss not finite: {losses}")
    check(0 < paths[want] == sum(paths.values()),
          f"training: the rule says {want}, the step was traced {paths}")
    check(losses[2] < losses[0],
          f"training: loss did not fall over three steps: {losses}")
    # the same program, built again from the persistent cache
    jax.clear_caches()
    mark = cache.mark()
    loss4, wall4 = one()
    check(np.isfinite(loss4), f"training: step 4 loss {loss4}")
    report["warm_build_s"] = round(wall4 - walls[2], 2)
    report["rebuild_cache"] = cache.since(mark)
    check(report["rebuild_cache"]["cache_hits"] > 0,
          "training: the rebuild read nothing from the compile cache")
    return report


# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same phases at `tiny` on the CPU; "
                         "never reads as a pass")
    ap.add_argument("--mesh", action="append", default=[],
                    metavar="MPxDP",
                    help="four-chip form: serving phases on this mesh "
                         "(repeatable), after a one-chip reference")
    args = ap.parse_args(argv)
    meshes = [tuple(int(x) for x in m.lower().split("x"))
              for m in args.mesh]
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        need = max([mp * dp for mp, dp in meshes] or [1])
        flags = os.environ.get("XLA_FLAGS", "")
        if need > 1 and "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={need}"
            ).strip()

    t_start = time.perf_counter()
    import jax
    import jaxlib
    devices = jax.devices()   # a backend that cannot start raises here
    dev = devices[0]
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    say(f"platform: {dev.platform}")
    say(f"device_kind: {dev.device_kind}")
    say(f"device_count: {len(devices)}")
    say(f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    if args.rehearse_cpu:
        say("REHEARSAL on the CPU at `tiny`: this run proves the "
            "script's control flow, nothing about a chip")
    elif dev.platform == "cpu":
        sys.exit("chip_smoke: no accelerator — JAX found platform "
                 f"'cpu' ({dev.device_kind}).  This check does not "
                 "downgrade; pass --rehearse-cpu for the CPU rehearsal.")

    sys.path.insert(0, REPO)
    from paddle_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    say(f"compile_cache_dir: {cache_dir}")
    if args.rehearse_cpu:
        # `tiny` compiles in milliseconds, under JAX's default
        # threshold for keeping a program: keep everything, so that
        # the rebuild checks run here as they do on the chip
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache = CacheCounter()

    sz = SIZES["rehearsal" if args.rehearse_cpu else "chip"]
    reports = []
    try:
        t0 = time.perf_counter()
        model = build_serving_model(sz)
        vocab = int(model.embeddings.word_embeddings.weight.shape[0])
        prompts = make_prompts(sz, vocab)
        say(f"[serving] {sz['serve_cfg']} layers={len(model.blocks)} "
            f"dtype={sz['dtype'] or 'float32'} prompts="
            f"{[len(p) for p in prompts]} new_tokens={sz['new_tokens']} "
            f"(model built in {time.perf_counter() - t0:.1f}s)")

        xla_ids, rep = serving_phase(
            "serving-xla", model, sz, prompts, cache,
            rebuild=not meshes)
        reports.append(rep)
        gc.collect()    # the phase's engine and its KV pools go here
        if not meshes:
            t0 = time.perf_counter()
            ref = reference_ids(model, prompts, sz["new_tokens"])
            rep["reference_s"] = round(time.perf_counter() - t0, 2)
            rep["agreement"] = compare(
                "serving-xla", model, prompts, xla_ids, ref, sz,
                "model.generate()")
        say(json.dumps(rep))

        if not meshes:
            rag_ids, rep = serving_phase(
                "serving-ragged", model, sz, prompts, cache,
                attn_impl="ragged")
            rep["agreement"] = compare(
                "serving-ragged", model, prompts, rag_ids, xla_ids, sz,
                "the XLA phase")
            reports.append(rep)
            say(json.dumps(rep))
            gc.collect()

        for mp, dp in meshes:
            m = model.to_tensor_parallel() if mp > 1 else model
            for impl in (None, "ragged"):
                name = f"serving-mesh{mp}x{dp}-{impl or 'xla'}"
                ids, rep = serving_phase(
                    name, m, sz, prompts, cache, attn_impl=impl,
                    mesh=(mp, dp))
                rep["agreement"] = compare(
                    name, m, prompts, ids, xla_ids, sz,
                    "the one-chip XLA phase")
                reports.append(rep)
                say(json.dumps(rep))
                gc.collect()
            del m
        del model
        gc.collect()

        if not meshes:
            rep = training_phase(sz, cache)
            reports.append(rep)
            say(json.dumps(rep))
    except Failed as e:
        say(f"FAILED: {e}")
        sys.exit(1)

    stats = dev.memory_stats() or {}
    say(f"peak_device_memory_bytes: "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    for rep in reports:
        say(f"phase {rep['phase']}: cold_build_s="
            f"{rep.get('cold_build_s')} warm_build_s="
            f"{rep.get('warm_build_s', 'n/a')} "
            + (f"wave_wall_s={rep['wave_wall_s']} compiles="
               f"{rep['compiles']}" if "wave_wall_s" in rep
               else f"step_wall_s={rep['step_wall_s']} "
                    f"losses={rep['losses']}"))
    say(f"compile_cache_dir: {cache_dir}")
    say(f"total_wall_s: {time.perf_counter() - t_start:.1f}")
    say(f"phases_passed: {[r['phase'] for r in reports]}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.rehearse_cpu:
        say(json.dumps({"rehearsal_passed": True, "device": device}))
    else:
        say(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
