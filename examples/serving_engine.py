"""Continuous-batching serving: N staggered requests share one decode.

``serving_decode.py`` optimizes ONE request's latency (fused
whole-decode, int8 weights).  This demo optimizes AGGREGATE throughput
under concurrent traffic: ``serving.Engine`` runs a single jitted
one-token decode step over a fixed pool of batch slots, admitting
queued requests the moment a slot frees — so one dispatch advances
every in-flight request instead of one.

The script submits N requests with staggered arrival times into a
4-slot engine (greedy, so every output is token-identical to
per-request ``generate()``), then decodes the same requests
sequentially, and prints both aggregate tokens/sec plus a Prometheus
metrics excerpt from the monitor registry.

It then demos the PAGED KV cache (``kv_block_size=``): a shared
system prompt in front of every request — the first request prefills
it once, and every later admission adopts the cached prefix blocks
from the token-trie prefix cache, skipping prefill for the shared
span (serving.kvcache; watch serving_prefix_hits /
serving_prefill_tokens).

It then demos BUDGETED CHUNKED PREFILL (``prefill_chunk=``): a
long prompt arriving while short requests are mid-decode.  Without
chunking, the admission tick runs the whole prompt's prefill before
the decode dispatch — one long emission gap for every decoding slot;
with it, each tick spends at most ``tick_token_budget`` prompt tokens
on fixed-size chunks and still decodes, so the printed per-tick token
counts never drop to zero for the decoders.

Finally it demos SPECULATIVE DECODING (``spec_k=``): a tiny model is
taught a 4-token cycle, then served with the prompt-lookup proposer —
each decode tick drafts 4 tokens from the request's own history,
verifies all 5 positions in ONE dispatch, and keeps the matching
prefix plus the bonus token.  The per-tick printout shows 4-5 tokens
landing per tick instead of 1, token-identical to the plain engine.

Finally it demos ON-DEVICE SAMPLING: sampling is fused into the jitted
decode dispatch — per-slot
temperature/top_k/top_p as traced lanes, rng keys derived on device
from the request seed + emitted-token counter — so a seeded top-p
request emits identical tokens on two fresh engine instances, and a
steady-state tick downloads [B] ids, never the [B, V] logits
(the printed serving.d2h_bytes_per_tick).

Finally it demos TICK-LEVEL TRACING: every engine records phase spans
(admission / prefill chunks / decode dispatch / d2h / emit),
per-request lifecycle instants, and compile events into a bounded
ring buffer — dumped here as a chrome://tracing JSON and summarized
per phase with tools/trace_view.py (on a live server: GET
/debug/trace; on a step failure the same ring auto-dumps as the
flight recorder).

Finally it demos OVERLOAD PROTECTION (``submit(priority=...)``): a
single-slot engine decoding a background stream receives a
high-priority interactive request — the background slot is PREEMPTED
mid-stream (its computed blocks return to the prefix cache, its
request requeues with the emitted tokens preserved), the interactive
request is served with millisecond TTFT, and the background stream
resumes via prefix adoption, finishing token-identical to an
uninterrupted run.

Run: python examples/serving_engine.py
"""
import os
import sys
import time

# allow running as `python examples/<script>.py` from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import Engine


def main():
    paddle.seed(0)
    cfg = os.environ.get("SERVING_CONFIG", "tiny")
    model = GPTModel.from_config(cfg, dropout=0.0)
    model.eval()
    vocab = model.embeddings.word_embeddings.weight.shape[0]
    rng = np.random.RandomState(0)
    n_requests, n_new = 8, 16
    prompts = [rng.randint(0, vocab, (int(l),)).astype(np.int32)
               for l in rng.randint(4, 12, n_requests)]

    # -- sequential per-request decode (the serving_decode.py regime) --
    # warm the compiled prefill/decode programs for every distinct
    # prompt length, keeping XLA compiles out of both timed windows
    warm = {len(p): rng.randint(0, vocab, (len(p),)).astype(np.int32)
            for p in prompts}
    for w in warm.values():
        model.generate(paddle.to_tensor(w[None, :]),
                       max_new_tokens=n_new, compiled=True).numpy()
    t0 = time.perf_counter()
    seq_outs = [model.generate(paddle.to_tensor(p[None, :]),
                               max_new_tokens=n_new,
                               compiled=True).numpy()[0]
                for p in prompts]
    t_seq = time.perf_counter() - t0
    seq_tps = n_requests * n_new / t_seq

    # -- continuous batching: staggered submits into a live engine ----
    engine = Engine(model, num_slots=4)
    engine.start()
    # warm the slot-batched decode + per-length prefill programs
    for w in warm.values():
        engine.submit(w, max_new_tokens=2).result(timeout=120)
    t0 = time.perf_counter()
    reqs = []
    for i, p in enumerate(prompts):
        reqs.append(engine.submit(p, max_new_tokens=n_new))
        if i % 2 == 1:
            time.sleep(0.005)  # staggered arrivals, not one big batch
    outs = [r.result(timeout=120) for r in reqs]
    t_eng = time.perf_counter() - t0
    engine.stop()
    eng_tps = n_requests * n_new / t_eng

    for got, ref in zip(outs, seq_outs):
        assert got.tolist() == ref.tolist(), \
            "continuous batching must stay token-identical to " \
            "per-request generate()"

    print(f"sequential generate(compiled=True): {seq_tps:8.1f} tok/s "
          f"aggregate ({t_seq * 1e3:.0f} ms for {n_requests} requests)")
    print(f"continuous batching (4 slots)     : {eng_tps:8.1f} tok/s "
          f"aggregate ({t_eng * 1e3:.0f} ms, {eng_tps / seq_tps:.1f}x)")

    text = monitor.render_prometheus(engine.registry)
    picks = ("serving_tokens_total", "serving_requests_completed",
             "serving_ttft_ms_count", "serving_tpot_ms_sum")
    print("\nmetrics excerpt (monitor.render_prometheus):")
    for line in text.splitlines():
        if line.startswith(picks):
            print(" ", line)

    # -- paged KV cache: shared system prompt, prefix reuse -----------
    # every request repeats the same 24-token "system prompt"; with
    # kv_block_size the engine pages K/V into shared refcounted blocks
    # and the prefix cache lets admissions 2..N adopt the system
    # prompt's blocks instead of re-prefilling them
    reg = monitor.StatRegistry()
    paged = Engine(model, num_slots=4, kv_block_size=8, registry=reg)
    sysp = rng.randint(0, vocab, (24,)).astype(np.int32)
    chats = [np.concatenate([sysp, p]) for p in prompts]
    refs = [model.generate(paddle.to_tensor(c[None, :]),
                           max_new_tokens=n_new).numpy()[0]
            for c in chats]
    first = paged.submit(chats[0], max_new_tokens=n_new)
    paged.run_until_idle()      # request 1 prefills + caches the prefix
    t0 = time.perf_counter()
    rest = [paged.submit(c, max_new_tokens=n_new) for c in chats[1:]]
    paged.run_until_idle()
    t_paged = time.perf_counter() - t0
    outs = [first.result(timeout=120)] + \
        [r.result(timeout=120) for r in rest]
    for got, ref in zip(outs, refs):
        assert got.tolist() == ref.tolist(), \
            "prefix reuse must stay token-identical to generate()"
    hits = int(reg.get("serving.prefix_hits").value)
    saved = int(reg.get("serving.prefix_hit_tokens").value)
    computed = int(reg.get("serving.prefill_tokens").value)
    print(f"\npaged KV + prefix cache (block=8)  : "
          f"{(len(chats) - 1) * n_new / t_paged:8.1f} tok/s aggregate; "
          f"{hits}/{len(chats) - 1} admissions hit the cached system "
          f"prompt")
    print(f"  prefill tokens computed {computed} "
          f"(cached prefix saved {saved}); "
          f"kv_blocks_in_use={int(reg.get('serving.kv_blocks_in_use').value)}"
          f"/{int(reg.get('serving.kv_blocks_total').value)}")

    # -- chunked prefill: a long prompt must not stall decode ---------
    # two short requests decode while a 144-token prompt arrives; the
    # per-tick printout shows decode continuing every tick under
    # prefill_chunk (monolithic prefill spends one whole tick on the
    # long prompt before its decode dispatch runs)
    paddle.seed(0)
    mixed_model = GPTModel(num_layers=2, hidden_size=64, num_heads=4,
                           vocab_size=128, max_position=256,
                           dropout=0.0)
    mixed_model.eval()
    shorts = [rng.randint(0, 128, (6,)).astype(np.int32)
              for _ in range(2)]
    longp = rng.randint(0, 128, (240,)).astype(np.int32)

    def drive(chunked):
        reg = monitor.StatRegistry()
        kw = dict(num_slots=4, max_seq_len=256, registry=reg)
        if chunked:
            kw.update(prefill_chunk=16, tick_token_budget=32)
        eng = Engine(mixed_model, **kw)
        # warm the compiles so the timed ticks are dispatch-only
        eng.submit(shorts[0], max_new_tokens=2)
        eng.run_until_idle()
        eng.submit(longp, max_new_tokens=2)
        eng.run_until_idle()
        sreqs = [eng.submit(p, max_new_tokens=16) for p in shorts]
        for _ in range(3):
            eng.step()                    # shorts mid-decode
        lreq = eng.submit(longp, max_new_tokens=4)
        ticks = []
        while not (lreq.done() and all(r.done() for r in sreqs)):
            before = sum(len(r.generated) for r in sreqs)
            t0 = time.perf_counter()
            eng.step()
            dt = (time.perf_counter() - t0) * 1e3
            ticks.append((sum(len(r.generated) for r in sreqs) - before,
                          len(lreq.generated) > 0, dt))
        return ticks

    for chunked in (False, True):
        label = ("prefill_chunk=16, budget=32" if chunked
                 else "monolithic prefill")
        ticks = drive(chunked)
        print(f"\nlong prompt ({len(longp)} tok) during decode — "
              f"{label}:")
        for i, (short_toks, long_started, dt) in enumerate(ticks):
            if i >= 8:
                print(f"  ... {len(ticks) - 8} more ticks")
                break
            note = " <- long prompt emitting" if long_started else ""
            print(f"  tick {i + 1}: short decoders +{short_toks} tok "
                  f"({dt:6.1f} ms){note}")
        print(f"  worst tick (the decoders' max inter-token gap): "
              f"{max(dt for _, _, dt in ticks):.1f} ms")

    # -- speculative decoding: draft k, verify in one dispatch --------
    # a model that repeats itself (here: trained on an 11-22-33-44
    # cycle) is the regime speculation exists for — the prompt-lookup
    # proposer drafts the continuation from the request's own history
    # and the verify dispatch accepts whole runs of it
    from paddle_tpu import optimizer
    from paddle_tpu.parallel.train_step import TrainStep
    paddle.seed(3)
    spec_model = GPTModel.from_config("tiny", dropout=0.0,
                                      max_position=128)
    cyc = np.tile(np.array([11, 22, 33, 44], np.int32), 16)
    tstep = TrainStep(spec_model, optimizer.Adam(
        learning_rate=5e-3, parameters=spec_model.parameters()),
        loss_fn=None)
    for _ in range(60):
        tstep.step([cyc[None, :-1].copy(), cyc[None, 1:].copy()])
    tstep.sync_to_layer()
    spec_model.eval()
    prompt = np.tile(np.array([11, 22, 33, 44], np.int32), 3)
    n_spec_new = 24
    ref = spec_model.generate(paddle.to_tensor(prompt[None, :]),
                              max_new_tokens=n_spec_new).numpy()[0]
    reg = monitor.StatRegistry()
    spec_eng = Engine(spec_model, num_slots=2, max_seq_len=64,
                      registry=reg, spec_k=4)  # PromptLookupProposer
    req = spec_eng.submit(prompt, max_new_tokens=n_spec_new)
    acc = reg.get("serving.spec_accepted")
    print(f"\nspeculative decoding (spec_k=4, prompt-lookup) on a "
          f"repetitive prompt:")
    tick = 0
    while not req.done():
        before_tok, before_acc = len(req.generated), acc.value
        spec_eng.step()
        tick += 1
        note = " (admission prefill)" if tick == 1 else ""
        print(f"  tick {tick}: +{len(req.generated) - before_tok} tok, "
              f"{int(acc.value - before_acc)} draft lanes accepted"
              f"{note}")
    assert req.result(timeout=1).tolist() == ref.tolist(), \
        "speculative greedy must stay token-identical to generate()"
    rate = reg.get("serving.spec_acceptance_rate").value
    print(f"  {n_spec_new} tokens in {tick} ticks "
          f"(plain engine: {n_spec_new} ticks); "
          f"acceptance rate {rate:.2f}")

    # -- fused on-device sampling --------------------------------------
    # sampling runs inside the jitted decode tick:
    # per-slot temperature/top_k/top_p ride as traced lanes, the rng
    # key derives on device from the request seed + emitted-token
    # counter, and a steady-state tick downloads only the [B] sampled
    # ids, never the [B, V] logits.  A SEEDED request therefore
    # emits the same tokens on ANY engine instance — run it twice on
    # two fresh engines and compare
    runs, d2h_dev = [], 0
    for _ in range(2):
        reg = monitor.StatRegistry()
        eng = Engine(model, num_slots=4, registry=reg)
        req = eng.submit(prompts[0], max_new_tokens=12,
                         temperature=0.9, top_p=0.9, seed=1234)
        eng.run_until_idle()
        runs.append(req.result(timeout=120)[len(prompts[0]):].tolist())
        d2h_dev = int(reg.get("serving.d2h_bytes_per_tick").value)
    assert runs[0] == runs[1], \
        "seeded device sampling must reproduce across engine instances"
    print(f"\nfused on-device sampling:")
    print(f"  seeded top-p request on two fresh engines -> identical "
          f"tokens: {runs[0]}")
    print(f"  d2h bytes per decode tick: {d2h_dev} ([B] ids + the "
          f"done mask; the [B, V] logits never leave the device)")

    # -- tracing + flight recorder: where did the tick's time go? -----
    # every engine keeps a bounded per-thread ring of phase spans
    # (admission / prefill chunks / spec draft / decode dispatch / d2h
    # sync / emit with batch/layout/accepted-lane args), per-request
    # lifecycle instants (queued -> admitted -> prefix-adopted ->
    # first-token -> finished), and a compile event per new jitted
    # program (serving.compiles_total).  Dump it as chrome://tracing
    # JSON — or GET /debug/trace on a live server — and open it in
    # chrome://tracing / Perfetto, or summarize it in the terminal
    # with tools/trace_view.py.  On a step failure the engine
    # auto-dumps the same ring as a post-mortem "flight recorder"
    # (Engine(flight_dir=...) / Engine.last_flight).
    import importlib.util
    import json
    trace = spec_eng.chrome_trace()   # the speculative demo's engine
    trace_path = "/tmp/paddle_tpu_serving_trace.json"
    with open(trace_path, "w") as f:
        json.dump(trace, f)
    spec = importlib.util.spec_from_file_location(
        "trace_view", os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
            "tools", "trace_view.py"))
    trace_view = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_view)
    rows = trace_view.summarize(trace["traceEvents"])
    print(f"\ntick-level tracing (chrome trace dumped to "
          f"{trace_path} — open in chrome://tracing):")
    for line in trace_view.format_table(rows[:6]).splitlines():
        print(" ", line)
    n_compiles = int(
        spec_eng.registry.get("serving.compiles_total").value)
    print(f"  compile events recorded by the spec engine: "
          f"{n_compiles} (serving.compiles_total — nonzero growth in "
          f"steady state means the program cache is thrashing)")

    # -- async engine loop: overlap host scheduling with device
    # compute.  The default engine (async_depth=2) dispatches tick
    # N+1's fused decode BEFORE consuming tick N's ids — safe because
    # the stop condition (EOS / max_new) is checked on device, which
    # freezes finished lanes and sends back a bit-packed done mask —
    # so admission planning and the emit loop hide behind device
    # compute.  serving.tick_overlap_ms is the host time hidden per
    # tick; serving.d2h_wait_ms is the only remaining sync point.
    def timed_async(depth):
        reg = monitor.StatRegistry()
        eng = Engine(model, num_slots=4, registry=reg,
                     async_depth=depth)
        for p in prompts:                      # warm the compiles
            eng.submit(p, max_new_tokens=2)
        eng.run_until_idle()
        t0 = time.perf_counter()
        rs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        eng.run_until_idle()
        dt = time.perf_counter() - t0
        outs = [r.result(timeout=120)[len(p):].tolist()
                for r, p in zip(rs, prompts)]
        return len(prompts) * 16 / dt, reg, outs

    tps1, _, outs1 = timed_async(1)
    tps2, reg2, outs2 = timed_async(2)
    assert outs2 == outs1, "async greedy streams must match sync"
    ov = reg2.get("serving.tick_overlap_ms")
    dw = reg2.get("serving.d2h_wait_ms")
    print(f"\nasync engine loop (async_depth=2, the default):")
    print(f"  aggregate tok/s: synchronous {tps1:.0f} vs pipelined "
          f"{tps2:.0f} ({tps2 / tps1:.2f}x), greedy streams "
          f"token-identical")
    print(f"  host work hidden behind device compute: "
          f"{ov.mean():.3f} ms/tick (serving.tick_overlap_ms), "
          f"blocking d2h wait {dw.mean():.3f} ms/tick "
          f"(serving.d2h_wait_ms)")
    print(f"  steady-state download per tick: "
          f"{int(reg2.get('serving.d2h_bytes_per_tick').value)} "
          f"bytes ([B] ids + the bit-packed done mask)")
    print(f"  summarize overlap from a trace with: "
          f"python tools/trace_view.py {trace_path} --wall")

    # -- overload protection: priority preemption under slot pressure.
    # One slot, a long low-priority background stream mid-decode, then
    # a high-priority interactive request: the engine EVICTS the
    # background slot mid-stream (its computed blocks go back to the
    # prefix cache, its request requeues with the emitted tokens
    # preserved), serves the interactive request, then RESUMES the
    # background stream — prefix adoption skips the re-prefill and
    # both outputs are token-identical to uninterrupted runs.
    reg = monitor.StatRegistry()
    over = Engine(model, num_slots=1, kv_block_size=8, registry=reg)
    bg_prompt, hot_prompt = prompts[0], prompts[1]
    for _ in range(2):  # twice: the 2nd pass warms the prefix-
        #   adoption prefill shapes, keeping compiles out of TTFT
        for p in (bg_prompt, hot_prompt):
            over.submit(p, max_new_tokens=2)
        over.run_until_idle()
    background = over.submit(bg_prompt, max_new_tokens=24, priority=0)
    for _ in range(8):
        over.step()                      # background is mid-stream
    n_before = len(background.generated)
    hot = over.submit(hot_prompt, max_new_tokens=8, priority=5)
    over.run_until_idle()
    hot_ttft = (hot.first_token_at - hot.submitted_at) * 1e3
    bg_out = background.result(timeout=120)[len(bg_prompt):]
    hot_out = hot.result(timeout=120)[len(hot_prompt):]
    ref_bg = model.generate(paddle.to_tensor(bg_prompt[None, :]),
                            max_new_tokens=24).numpy()[0][len(bg_prompt):]
    ref_hot = model.generate(paddle.to_tensor(hot_prompt[None, :]),
                             max_new_tokens=8).numpy()[0][len(hot_prompt):]
    assert bg_out.tolist() == ref_bg.tolist(), "resumed stream differs"
    assert hot_out.tolist() == ref_hot.tolist()
    print(f"\noverload protection (priority preemption, 1 slot):")
    print(f"  background (priority 0) preempted after {n_before} "
          f"tokens -> requeued with its stream intact "
          f"(preemptions={background.preemptions})")
    print(f"  interactive (priority 5) TTFT {hot_ttft:.1f} ms instead "
          f"of waiting out the background stream")
    print(f"  background resumed and finished token-identical to an "
          f"uninterrupted run (prefix cache adopted "
          f"{int(reg.get('serving.prefix_hit_tokens').value)} tokens "
          f"of its history — no re-prefill)")
    print(f"  counters: preemptions_total="
          f"{int(reg.get('serving.preemptions_total').value)} "
          f"resumed_total="
          f"{int(reg.get('serving.resumed_total').value)}")


if __name__ == "__main__":
    main()
