"""Continuous-batching inference engine over a fixed slot pool.

The per-request path (``GPTModel.generate``) decodes one request per
dispatch: whenever a request finishes early, the compiled decode loop
idles until the next request arrives, and short requests serialize
behind long ones.  This engine instead runs ONE jitted one-token decode
step over a fixed pool of ``num_slots`` batch rows (the TPU-shaped
continuous batching: slot count and cache length are static, so a
single XLA program serves every tick), admitting queued requests into
slots the moment they free up:

  tick:  admit(queue -> free slots, prefill each)  ->
         one slot-batched decode dispatch          ->
         sample per live slot, evict on EOS/max_new_tokens

Sampling runs ON DEVICE inside the decode dispatch (traced per-slot
params, seed+counter keys, device-resident cursors — the tick
downloads [B] ids, not [B, V] logits).

Each slot row computes exactly what a B=1 ``GPTAttention.decode`` at
that slot's position computes (see ``decode_slots``), so under greedy
decoding the engine's outputs are token-identical to per-request
``generate()`` — tests/test_serving.py asserts it.

Observability rides on paddle_tpu.monitor: queue depth, slot occupancy,
tokens/sec, TTFT/TPOT histograms — scrape them through
``monitor.render_prometheus()`` or the serving.httpd endpoint.
"""
from __future__ import annotations

import functools
import json
import os
import queue
import threading
import time
import weakref
from collections import deque
from contextlib import nullcontext

import numpy as np

from .. import monitor
from .kvcache import (BlockPool, KVDtypeMismatch, PrefixCache,
                      export_blocks, import_blocks)
from .lora import AdapterRegistry, LoRAAdapter, UnknownAdapter
from .request import (MAX_SEED, DeadlineShed, QueueFull, RateLimited,
                      Request, RequestQueue, TenantPolicy, TokenBucket)
from .scheduler import Scheduler


class Migrated(RuntimeError):
    """The request was handed off to another replica mid-stream (KV
    block migration) — the terminal verdict its waiter receives on the
    SOURCE engine.  ``emitted`` is the token prefix generated here
    before the handoff (never lost: a holder that cannot complete the
    migration can always fail over with prompt + emitted as context).
    ``payload`` is the full migration payload when ``migrate_out(...,
    deliver="error")`` routed it through the waiter (the waiter owns
    the import), else None (some other holder owns the payload and
    this waiter may only salvage ``emitted``)."""

    def __init__(self, msg, payload=None, emitted=None):
        super().__init__(msg)
        self.payload = payload
        self.emitted = list(emitted or [])


class _MigrateDemand:
    """One cross-thread migration order (export / import / prefix
    warm), registered by any thread and SERVICED BY THE ENGINE THREAD
    at a tick boundary — the same single-writer discipline as every
    other pool/slot mutation, so migration never races a dispatch."""

    __slots__ = ("kind", "args", "done", "result", "error",
                 "registered_at", "waiting")

    def __init__(self, kind, **args):
        self.kind = kind      # "out" | "in" | "prefix_out" | "prefix_in"
        self.args = args
        self.done = threading.Event()
        self.result = None
        self.error = None
        self.registered_at = time.monotonic()
        self.waiting = False  # an "out" whose target is not yet
        #   exportable: retried every tick, but must not keep an
        #   idle engine's loop spinning (the submit that makes it
        #   actionable wakes the loop anyway)

    def complete(self, result):
        self.result = result
        self.done.set()

    def fail(self, error):
        self.error = error
        self.done.set()

    def wait(self, timeout=None):
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"migration {self.kind} demand: no verdict after "
                f"{timeout}s (engine not stepping?)")
        if self.error is not None:
            raise self.error
        return self.result


def _spanned(name, phase=False):
    """Run the decorated engine method under a tracer span: the
    phases of a tick that have no narrower span of their own
    (``phase``: one of those that read the CPU clock where the engine
    lets them, ``Engine._phase_cpu``)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *args, **kwargs):
            with self.tracer.span(name,
                                  cpu=phase and self._phase_cpu):
                return fn(self, *args, **kwargs)
        return wrapped
    return deco


# why the async loop still consumes its ring to empty (``ring.drain``'s
# ``why``, ``serving.ring_drains.<why>``): each is a true sync, where
# the host needs the tokens in flight before it can go on.  A dirty
# slot is none of them: its lanes are patched in dispatch order
# (``Engine._patch_state``)
_RING_DRAINS = {
    "spec": "drafting reads the accepted tokens",
    "tail": "every lane ends inside the ticks in flight",
    "idle": "every slot was freed under the newest dispatch",
    "preempt": "a victim's emitted tokens are requeued with it",
    "migrate": "an export gathers the rows the host has consumed",
    "adapter": "a bank lane flips under no dispatched tick",
}

# slots one patch program rewrites (shorter lists repeat their first
# row, longer ones take more calls): a tick seldom dirties more
_PATCH_ROWS = 4

# a read of the thread's CPU clock dearer than this (us) is kept off
# the phases' spans unless the tracer annotates (Engine._phase_cpu)
_PHASE_CPU_MAX_READ_US = 1.0


class _DeviceWait:
    """One wait of the tick's thread for the device, under the span
    that names it: its wall time goes to ``Engine._blocked_s`` (what
    the tick's ``host_ms`` leaves out) and the thread CPU it burned
    (a runtime may spin while it waits) to ``Engine._blocked_cpu_s``
    (what the tick's ``cpu_ms`` leaves out).  The clocks are the
    engine's own reads, inside the span, so the tick's account is the
    same with ``tracing=False``."""

    __slots__ = ("_eng", "_span", "_w0", "_c0")

    def __init__(self, eng, span):
        self._eng = eng
        self._span = span

    def __enter__(self):
        sp = self._span.__enter__()
        self._w0 = time.perf_counter()
        self._c0 = time.thread_time()
        return sp

    def __exit__(self, *exc):
        c1 = time.thread_time()
        w1 = time.perf_counter()
        eng = self._eng
        eng._blocked_s += w1 - self._w0
        eng._blocked_cpu_s += c1 - self._c0
        return self._span.__exit__(*exc)


def _watch_device(q, tracer, busy_ms, cpu_ms):
    """Body of an engine's device watcher thread: turn each dispatch
    the engine thread hands over into a ``dev.*`` span on the tracer's
    shared ``device`` lane.  One device runs the engine's programs in
    the order they were dispatched, so a program starts when the one
    before it completes (or when it was dispatched, if the device was
    idle by then) and ends when its smallest output is ready; both
    ends are read from ``time.perf_counter``, the clock of every host
    span, so the lane lies over the host's and a hole in it is the
    device idle.  ``busy_ms`` (``serving.dev_busy_ms``) sums the same
    durations.  The completion is read after this thread wakes up, so
    it is late by the wake-up (and the program after it starts as
    late, which keeps the sum right).  The wake-up needs the
    interpreter lock, which the tick's and the handlers' threads hold
    in turn: its lateness grows with what the tick's thread itself
    waits (``serving.tick_wait_ms``), and that, not the device, is why
    ``dev.decode`` reads longer in a contended run of one program.
    ``cpu_ms`` (``serving.dev_watch_cpu_ms``) takes this thread's own
    CPU time, once a dispatch.  The thread holds the tracer and the
    counters but never the engine; ``None`` ends it."""
    prev_done = 0.0
    cpu_seen = time.thread_time()
    while True:
        item = q.get()
        cpu_now = time.thread_time()
        cpu_ms.inc((cpu_now - cpu_seen) * 1e3)
        cpu_seen = cpu_now
        if item is None:
            return
        name, t_dispatch, handle, args = item
        try:
            if handle is not None:
                handle.block_until_ready()
        except Exception:
            # the dispatch died; step recovery rebuilt the pools
            prev_done = time.perf_counter()
            continue
        done = time.perf_counter()
        ts = max(prev_done, t_dispatch)
        prev_done = done
        stats = args.pop("_stats", None)
        if stats is not None:
            for arg, v in zip(stats[1], np.asarray(stats[0])):
                if arg:
                    args[arg] = int(v)
        tracer.emit(name, ts, done - ts, cat="device", args=args)
        busy_ms.inc((done - ts) * 1e3)


class _InflightTick:
    """One dispatched-but-not-consumed decode tick (the async engine
    loop's pipeline entry).  Holds the device handles of the arrays
    the consume side will materialize (ids/done — spec: picks/counts),
    the slot->request bindings AS OF DISPATCH TIME (a slot may be
    evicted and even re-admitted before this tick is consumed; the
    identity check ``slot.request is req`` is what keeps a frozen
    lane's garbage out of a newer request's stream), and a small host
    snapshot of the cursor buffer the dispatch chained from — the
    flight recorder's view of the in-flight ring."""

    __slots__ = ("tick", "kind", "slots", "reqs", "arrays", "batch",
                 "layout", "dispatched_at", "cursors", "spec_lanes",
                 "meta_lanes", "dropped")

    def __init__(self, tick, kind, slots, arrays, batch, layout,
                 cursors, spec_lanes=None, meta_lanes=None):
        self.tick = tick
        self.kind = kind              # "decode" | "spec" | "ragged"
        self.slots = slots
        self.reqs = [s.request for s in slots]
        self.arrays = arrays          # name -> un-materialized device
        self.batch = batch            # handle (jax async dispatch)
        self.layout = layout
        self.dispatched_at = time.monotonic()
        self.cursors = cursors        # host view of the chained-from
        #   state buffer (flight recorder)
        self.spec_lanes = spec_lanes  # per-slot REAL draft lanes as
        #   of dispatch (consume must not re-read the slot: it may
        #   have been rebound by then)
        self.meta_lanes = meta_lanes  # ragged dispatch: per listed
        #   slot (mode, width, lanes) as of dispatch — same
        #   must-not-re-read rule as spec_lanes
        self.dropped = set()          # slots the HOST freed after this
        #   dispatch was queued (a first token that ended its request):
        #   their lanes are live on the device, and consume drops what
        #   they computed instead of calling it drift

    def meta(self):
        """JSON-able metadata for the flight recorder / debug
        surface (never materializes the device arrays — a dump must
        not block on, or mask, a wedged dispatch)."""
        return {
            "tick": self.tick, "kind": self.kind, "batch": self.batch,
            "layout": self.layout,
            "slots": [s.index for s in self.slots],
            "requests": [r.id for r in self.reqs],
            "in_flight_ms": round(
                (time.monotonic() - self.dispatched_at) * 1e3, 3),
            "cursors": self.cursors,
        }


class Engine:
    """In-process continuous-batching engine for a decoder-only LM.

    The engine owns slots, blocks and ticks, and reaches the model only
    through the seam of ``models/programs.py``: ``serving_spec()``
    (the row it keeps per cached position and layer, its longest
    sequence, its vocabulary, the features it cannot honour yet — each
    refused here by name) and ``serving_program(kind, ...)`` (its
    jitted step programs).

    Parameters
    ----------
    model : a ``ServedModel`` — ``GPTModel`` (every option below) or
        ``MLAMoEModel`` (the default paged, chunked path); eval'd;
        ``scan_layers`` models serve through their auto-synced
        unrolled decode twin, like ``generate``.
    num_slots : fixed batch-slot pool size (the compiled tick's B).
    max_seq_len : per-slot KV cache length L (prompt + generated must
        fit); defaults to the model's max_position.
    max_queue : admission queue bound (0 = unbounded); a full queue
        sheds load at ``submit`` with QueueFull.
    kv_block_size : enable the PAGED KV cache (serving/kvcache.py).
        ``None`` (default) keeps the contiguous per-slot rows; an int
        (must divide max_seq_len) carves the pools into fixed-size
        blocks that slots address through block tables — identical
        prompt prefixes share physical blocks, and admission adopts
        cached prefixes so prefill skips the shared span entirely.
        Greedy outputs stay token-identical to the contiguous path
        (same f32 score math over the gathered rows); on TPU a
        near-tie logit may round differently between donor and adopter
        prefill shapes — the same cross-shape caveat as speculative
        decode.  Without ``prefill_chunk`` the paged prefill compiles
        per (context, tail) length.
    kv_blocks : physical block count of the paged pool (default:
        ``num_slots * max_seq_len / kv_block_size`` — the same HBM as
        the contiguous layout; prefix sharing then YIELDS headroom
        that cached prefixes occupy rent-free).  Admission reserves a
        request's worst-case blocks up front, so decode never
        allocates; when the pool cannot cover a request even after LRU
        eviction of unreferenced prefixes, it simply waits in queue.
    prefix_cache : keep finished prompts' full blocks resident in a
        token-trie so later requests adopt them (paged mode only;
        default True).  ``False`` pages without reuse — the A/B
        baseline for the parity tests.
    prefill_chunk : enable BUDGETED CHUNKED PREFILL.  ``None``
        (default) prefills each admitted prompt whole, inline, before
        the tick's decode dispatch, with one compiled program per
        DISTINCT prompt length — fine for tests with few lengths, but
        arbitrary lengths thrash the 8-entry program cache, and one
        long prompt stalls token emission for every decoding slot by
        its full prefill time.  An
        int (must divide max_seq_len) splits each prompt into
        fixed-size chunks run through ONE compiled chunk program
        (bounded compiles); each tick spends at
        most ``tick_token_budget`` prompt tokens on chunks —
        round-robin across PREFILLING slots, resuming partially
        prefilled prompts before starting new ones — and then always
        runs the decode tick for the DECODING slots, so decode latency
        is bounded by the budget, not the longest queued prompt.
        Half-prefilled slots are excluded from decode and sampling
        until their final chunk emits the first token.  Greedy outputs
        stay token-identical to the unchunked engine and to
        ``generate()`` (caveat: on TPU a near-tie logit may round
        differently across program shapes).
        Works with both the contiguous and paged KV layouts.
    tick_token_budget : prompt tokens each tick may spend on prefill
        chunks (default: one ``prefill_chunk``; must be >= it so every
        tick makes progress).  Requires prefill_chunk.
    spec_k : enable SPECULATIVE DECODING (serving/spec.py).  ``None``
        (default) keeps the one-token decode tick; an int k >= 1 makes
        each decode tick gather k draft tokens per slot from the
        ``proposer``, verify all k+1 window positions in ONE jitted
        dispatch (``GPTModel._compiled_fused_spec_verify_fn`` — one
        compiled program per (k, layout), reusing the decode tick's
        ``_slot_attn``), accept ON DEVICE the longest prefix where the
        target's pick equals the draft plus the one bonus token (the
        tick downloads picks + accept counts, never the [B, W, V]
        logits), and advance
        the slot's position/KV write cursor only over the accepted
        lanes — rejected lanes leave garbage rows the next window
        rewrites before any query can see them, so rollback is a pure
        cursor reset.  Greedy outputs stay token-identical to the
        non-speculative engine (lossless greedy acceptance); seeded
        sampling also matches, because the verify window's lane j
        logits equal the one-token tick's logits for the same prefix
        and lane j's key folds the emitted-token counter + j, one draw
        per emitted token either way.  Works with both KV layouts and
        with chunked prefill.
        Capacity: the verify window can write up to ``spec_k`` rows
        past a request's last needed position, so ``submit`` requires
        prompt + max_new_tokens + spec_k <= max_seq_len and the paged
        admission gate reserves the extra blocks up front.
    proposer : draft-token source for speculative decoding (requires
        spec_k); defaults to ``PromptLookupProposer()`` — n-gram match
        against the slot's own prompt + emitted history, zero extra
        model.  ``DraftModelProposer(small_gpt)`` drafts with a
        smaller model sharing the tokenizer/vocab (cross-checked).
    attn_impl : which attention implementation serves the paged
        window dispatches.  ``None`` (default) inherits the model's
        ``GPTModel(attn_impl=...)`` knob (itself defaulting to
        ``"xla"``).  ``"xla"`` keeps one compiled executable per
        (layout, chunk shape, spec_k) window SHAPE — and remains the
        CPU tier-1 parity oracle.  Its decode and verify programs'
        attention core is the model's to choose when it traces them
        (``ServingSpec.attn_core``; the XLA walk, or on one TPU
        with paged floating-point pools at heads of 128 a Pallas
        kernel: GPT's whole core, the same kernel as below, streaming
        each live slot's pages; a grouped-query model's trip of its
        work list, ``ops/gq_walk_trip.py``:
        ``/healthz`` ``attn_core`` names the form and why,
        ``serving.attn_kernel_dispatches`` counts it, and
        construction compiles the model's kernel through Mosaic at
        the shapes its programs will use
        (``ServingSpec.attn_kernel_check``), raising the compiler's
        message if it is refused).  ``"ragged"`` (requires the paged layout) routes the
        decode, spec-verify, and chunked-prefill
        attention core through the Pallas RAGGED PAGED ATTENTION
        kernel (ops/ragged_paged_attn.py; interpret mode on the cpu
        platform only, so tier-1 runs the kernel logic; on any other
        backend construction compiles it through Mosaic at this
        engine's shapes and raises the compiler's message if it is
        refused — it needs head_dim % 128 == 0): per-slot positions,
        window widths, and block tables are kernel DATA, a single
        dispatch carries one-token decode lanes, k+1 verify windows,
        and budgeted prefill chunks side by side, the
        longest-accepted-prefix scan folds into the program's
        epilogue, and the whole (chunk shape, spec_k) compile matrix
        collapses to ONE ``ragged_window`` program — watch
        ``serving.compiles_total`` and the ``decode.ragged_stream``
        trace span (plus ``serving.kv_blocks_walked_per_tick``).
        The kernel body is the flash-style ONLINE-SOFTMAX streaming
        loop: K/V are consumed a step of pages at a time up to each
        lane's causal horizon, so the per-slot working set is
        O(step rows x window) — independent of context length — and
        long contexts are first-class.  Numerics: allclose to the XLA
        oracle (online softmax reorders float summation); GREEDY
        streams are token-identical to the XLA path end-to-end across
        the full layout matrix, seeded streams are deterministic
        (same seed => same stream); both asserted in
        tests/test_ragged_attn.py.
    mesh : TENSOR-PARALLEL SERVING over a device mesh.  ``None``
        (default) serves on one device.  An int / 1-tuple ``mp``
        degree (resolved over the first mp devices via
        ``distributed.mesh.serving_mesh``) or a prebuilt
        ``jax.sharding.Mesh`` shards the model's attention heads,
        FFN, and vocab over the mesh's 'mp' axis — the model must be
        the einsum-form tensor-parallel variant
        (``GPTModel(use_mp=True)`` or a dense checkpoint's
        ``to_tensor_parallel()`` twin), whose parameters carry the
        'mp' PartitionSpecs from distributed/sharding.py.  The
        per-layer KV pools shard over the SAME mesh on the head axis
        (each shard holds its heads' K/V of every block), block
        tables and step cursors replicate, and the fused sampling
        epilogue stays device-side on the all-gathered logits — so
        all four hot dispatch paths compile once per config with the
        sharding baked in, and the steady-state d2h contract ([B]
        ids + done bits) is unchanged.  Greedy AND seeded outputs
        are token-identical to the unsharded engine (same math
        modulo float summation order; asserted in
        tests/test_sharded_serving.py on a forced multi-device CPU
        mesh).  One sharded engine per process owns the global mesh
        (the TP activation constraints read it); unsharded sibling
        engines are unaffected.  Watch ``serving.mesh_devices`` and
        the ``shard.sync`` / ``decode.allgather`` spans.
    kv_budget_mb : size the paged pool from a PER-SHARD HBM budget
        instead of a block count: ``kv_blocks = budget //
        per_shard_block_bytes`` where one logical block costs
        ``n_layers * 2 * block_size * (H/mp) * hd * dtype`` bytes
        per shard — so the same per-chip budget holds mp x the
        blocks on a sharded engine (KV capacity scales with the
        mesh; ``serving.kv_blocks_total`` reflects the aggregate
        logical pool).  Mutually exclusive with ``kv_blocks``;
        requires the paged layout.
    async_depth : ASYNC ENGINE LOOP pipeline depth, 2 (the default)
        or 1.  At depth 2 a tick DISPATCHES tick N+1's fused decode
        BEFORE consuming tick N's ids (jax async dispatch: the
        returned handles are futures; the only blocking sync is the
        consume-side ``np.asarray``, traced as ``decode.d2h_wait``),
        so admission planning and the previous tick's emit/metrics
        loop run in the gap while the device computes — on real
        hardware the inter-tick gap is pure host time, and this
        overlap is what lets kernel-side wins show up as tokens/sec.
        Blind dispatch is safe because the stop condition (EOS /
        max_new) moved ON DEVICE: per-slot eos/remaining-budget lanes
        freeze a finished row inside the dispatch, and a bit-packed
        done mask rides back with the ids, so a steady-state tick
        downloads ids + done-mask bytes and never forces an early
        sync.  The device cursor state is double-buffered: the
        in-flight tick holds the buffer it chained from while
        ``_dev_state`` tracks the newest handles; an admission, an
        eviction, a chunk's progress or a final chunk's first token
        writes the HOST mirrors (the engine's truth of consumed ticks)
        and marks its slot dirty, and the slot's lanes reach the
        device as a small patch program queued behind the decodes in
        flight (``state.patch``; ``serving.state_patches``): no dirty
        event consumes the ring, the mirrors are uploaded whole once
        (``state.push``; ``serving.state_pushes`` 1 in a healthy run),
        and because one device runs its queue in order the streams
        are token-identical to ``async_depth=1`` (the synchronous
        tick, which queues the same patches over nothing in flight).
        The first token of a prefill is picked on the device and read
        (4 bytes) behind the decode that follows it.  What still
        empties the ring are the true syncs (``ring.drain`` says
        ``why``: ``spec``, ``tail``, ``idle``, ``preempt``,
        ``migrate``, ``adapter``).  Speculative mode consumes before drafting
        (draft windows are data-dependent on the previous window's
        accepted tokens), so its overlap is limited to planning.
        Watch ``serving.tick_overlap_ms``
        / ``serving.d2h_wait_ms`` and the ``host.overlap`` spans.
    tracing : keep a per-engine span tracer (monitor/tracing.py) fed
        by every tick: admission / prefill / chunk / decode-dispatch /
        d2h-sync / emit complete-events with args (batch
        size, layout, accepted spec lanes, KV blocks in use),
        per-request lifecycle instants (queued -> admitted ->
        prefix-adopted -> first-token -> finished/evicted), and a
        compile event + ``serving.compiles_total`` bump for every new
        jitted program (layout / spec_k / chunk shape / wall time —
        the production-side compile-thrash detector).  The buffer is a
        bounded per-thread ring (``trace_capacity`` events), so the
        cost is two clock reads and a deque append per span and the
        LAST ~capacity events are always retained — the flight
        recorder.  Download it live via ``/debug/trace`` or
        ``Engine.chrome_trace()``; ``tracing=False`` swaps in a no-op
        tracer.
        Tracing also starts, at the first dispatch, one DEVICE WATCHER
        thread (stopped by ``stop()``): it waits for the smallest
        output of every dispatched program and records ``dev.decode``
        / ``dev.prefill`` spans (``dev.prefill`` carried at least one
        prompt token; args ``program``, ``tick``, ``batch``, ``n``,
        ``req``) on a shared ``device`` lane, on the clock of the host
        spans — a hole in that lane is the device idle, and
        ``tools/trace_view.py --wall`` sums the holes by the host span
        open meanwhile.  ``serving.dev_busy_ms`` sums the same
        durations (its rate is the device's utilisation).  Every phase
        of a tick runs under a span of its own, and the ``tick`` span
        carries ``host_ms``: its duration less the time blocked on the
        device, split into ``cpu_ms`` (thread CPU time: the Python the
        tick's thread ran) and ``wait_ms`` (the rest: the thread
        runnable or blocked, but not by the device, so waiting for
        the interpreter lock that the HTTP handlers and the watcher
        share with it, a sink lock, or the scheduler);
        ``blocked_cpu_ms`` where a wait for the device burned CPU.
        (The thread CPU clock is as fine as the kernel's accounting:
        nanoseconds on stock Linux, 10 ms steps on the benchmark's
        machine, where one tick's split says little and only sums
        over many ticks, the counters below, are to be read.)
        The phases the idle tables name carry their own ``cpu_ms``
        (``admit``, ``chunk.plan``, ``prefill.chunk``,
        ``prefill.d2h``, ``state.push``, ``state.patch``,
        ``first_token``, ``ring.drain``, ``dispatch``,
        ``decode.dispatch``, ``consume``, ``decode.emit``) where a read
        of the thread's CPU clock is cheap (under 1 us:
        ``monitor.tracing.thread_clock_read_us``, asked once at
        construction) or ``trace_annotations`` is on: in a sandbox
        whose kernel is a trap away a read costs 5.5 us alone and far
        more beside the runtime's busy threads (~0.7 ms a tick on the
        benchmark's machine), and the default ring then keeps the
        tick's own split and the counters only; the pure
        waits and the per-token ``stream.emit`` never do.  The sums are
        kept with tracing on or off: ``serving.tick_host_ms``,
        ``serving.tick_cpu_ms``, ``serving.tick_wait_ms``, beside the
        edge's ``serving.http_cpu_ms``, the watcher's
        ``serving.dev_watch_cpu_ms`` and the gauge ``process.cpu_ms``
        (every thread of the process), so four numbers a wall-second
        say whether the interpreter is saturated or the machine is.
        ``http.ingest`` / ``http.first_frame`` / ``http.stream``
        (httpd.py) and the ``req.*`` instants share one ``requests``
        lane that outlives the handler threads.
    trace_capacity : per-thread ring-buffer bound, in events.
    trace_annotations : also enter a ``jax.profiler.TraceAnnotation``
        per span so engine phases land in XPlane/TensorBoard captures
        (off by default: it imports jax in the span path).
    flight_dir : directory for automatic flight-recorder dumps.  A
        failing ``step()`` snapshots the trace ring plus the in-flight
        request states into ``Engine.last_flight`` (always, in
        memory, BEFORE recovery tears the slots down) and, when
        ``flight_dir`` is set, also writes it there as a chrome-trace
        JSON (``flight_tick<N>_<pid>_<ms>.json``) for post-mortems.
    tenants : per-tenant admission policies — dict name ->
        ``TenantPolicy`` (or a plain dict of its kwargs): ``weight``
        sets the tenant's weighted-fair share of queue service within
        a priority tier (start-time fair queuing over token cost, so
        a flooding tenant cannot starve another past its weight), and
        ``rate``/``burst`` arm a token bucket charged
        ``prompt + max_new_tokens`` at submit — over-rate submits
        raise ``RateLimited`` with an honest ``retry_after``.
        Unlisted tenants get weight 1 and no rate limit.
    preemption : allow PRIORITY PREEMPTION (default True).  When the
        best queued request outranks a running one and admission is
        blocked (no free slot, or the paged gate is short on blocks),
        the lowest-priority busy slot is evicted MID-STREAM: in paged
        mode every full block of its computed history (prompt +
        emitted-so-far) goes into the prefix cache first, the request
        requeues at the head of its own lane with its emitted tokens
        preserved, and re-admission adopts the cached span so the
        resume skips re-prefill — the resumed stream is
        token-identical (greedy AND per-seed sampled: the device key
        folds the emitted-token counter) to an uninterrupted run.
        Victims tie-break to the most recently admitted (least sunk
        work).
    shed_deadlines : DEADLINE-AWARE LOAD SHEDDING at submit (default
        True).  Once the drain rate is measured, a request whose
        deadline (``timeout``) is already blown by the estimated
        queue wait — (in-flight remaining + queued work at its
        priority or above) / measured tokens-per-sec — is rejected
        with ``DeadlineShed`` carrying a computed ``retry_after``
        instead of burning slot time on a result nobody will read.
    faults : a ``serving.faults.FaultInjector`` — deterministic,
        seeded failure points (dispatch raise, d2h hang, pool
        exhaustion, slow host tick, proposer failure) threaded
        through the tick for chaos testing; None (default) disables
        every site at zero cost.
    watchdog_s : arm a ``TickWatchdog``: a tick exceeding this many
        seconds (wedged dispatch / hung d2h) is flight-recorded
        immediately and marked, so cooperative blocking points raise
        ``WatchdogTimeout`` into the normal step-failure recovery
        instead of hanging the engine forever.

    ``step()`` is single-threaded by design — run it from one loop
    (``run_until_idle`` or the ``start()`` background thread).
    ``submit()`` is thread-safe and may be called from anywhere
    (e.g. HTTP handler threads).
    """

    def __init__(self, model, num_slots=4, max_seq_len=None,
                 max_queue=0, registry=None,
                 kv_block_size=None, kv_blocks=None, prefix_cache=True,
                 prefill_chunk=None, tick_token_budget=None,
                 spec_k=None, proposer=None,
                 attn_impl=None, mesh=None, kv_budget_mb=None,
                 async_depth=2, tracing=True,
                 trace_capacity=16384, trace_annotations=False,
                 flight_dir=None, tenants=None, preemption=True,
                 shed_deadlines=True, faults=None, watchdog_s=None,
                 weight_dtype=None, kv_dtype=None, adapters=None,
                 max_adapters=None, max_lora_rank=None,
                 kv_host_mb=None):
        if getattr(model, "scan_layers", False):
            model = model._sync_decode_twin()
        model.eval()
        self.model = model
        # the seam (models/programs.py): what the model keeps per
        # cached position and layer, how far it can place a token, and
        # which of this engine's features it cannot honour yet
        sspec = model.serving_spec()
        # -- quantized serving (serving/quant.py) ----------------------
        # weight relayout runs HERE, before the KV-dtype resolution and
        # the parameter/buffer snapshots below, so the int8 codes +
        # scales are registered buffers that ride b_list into every
        # compiled hot path, and kv pools stay in the projection's
        # declared compute dtype
        self._weight_quant = weight_dtype is not None
        if self._weight_quant:
            if str(weight_dtype) != "int8":
                raise ValueError(
                    f"weight_dtype must be 'int8' (or None to serve "
                    f"the checkpoint's own dtype), got {weight_dtype!r}")
            if sspec.tensor_parallel:
                raise ValueError(
                    "weight_dtype='int8' cannot relayout the tensor-"
                    "parallel einsum form (use_mp=True): its fused "
                    "qkv/ffn weights are not nn.Linear layers — "
                    "quantize the dense checkpoint before "
                    "to_tensor_parallel(), or serve it dense")
            from .quant import relayout_weights_int8
            relayout_weights_int8(model)
            sspec = model.serving_spec()  # the projections' compute
            #   dtype is the quantized layers' now
        self._kv_quant = kv_dtype is not None
        if self._kv_quant and str(kv_dtype) != "int8":
            raise ValueError(
                f"kv_dtype must be 'int8' (or None for the compute "
                f"dtype), got {kv_dtype!r}")
        max_position = sspec.max_positions
        self.max_seq_len = int(max_seq_len or max_position)
        if self.max_seq_len > max_position:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"position table ({max_position})")
        self.num_slots = int(num_slots)
        # the HTTP edge validates token ids against this
        self.vocab_size = sspec.vocab_size
        self._refuse_unsupported(sspec, dict(
            contiguous=kv_block_size is None,
            unchunked_prefill=prefill_chunk is None,
            ragged=(attn_impl or getattr(model, "attn_impl", "xla"))
            != "xla",
            spec=spec_k is not None or proposer is not None,
            kv_int8=kv_dtype is not None,
            mp=mesh is not None,
            lora=adapters is not None or max_adapters is not None,
            offload=kv_host_mb is not None))
        lacking = [f for f in self._ROWS_ONLY
                   if sspec.kv.block_rows and f not in sspec.unsupported]
        if lacking:
            raise ValueError(
                f"{type(model).__name__} keeps per-block rows "
                f"(KVRowSpec.block_rows) in the pools' second list, "
                f"which {lacking} pair with the first a layer at a "
                "time as K with V: its ServingSpec.unsupported has to "
                "name them")
        # -- overload protection: tenants, priorities, shedding ---------
        self._tenant_policies = {}
        self._buckets = {}
        for name, pol in (tenants or {}).items():
            if isinstance(pol, dict):
                pol = TenantPolicy(**pol)
            elif not isinstance(pol, TenantPolicy):
                raise ValueError(
                    f"tenants[{name!r}] must be a TenantPolicy or a "
                    f"dict of its kwargs, got {type(pol).__name__}")
            self._tenant_policies[str(name)] = pol
            if pol.rate is not None:
                self._buckets[str(name)] = TokenBucket(pol.rate,
                                                       pol.burst)
        self.queue = RequestQueue(
            max_queue=max_queue,
            weights={n: p.weight
                     for n, p in self._tenant_policies.items()})
        self.scheduler = Scheduler(self.num_slots, self.queue)
        self._preemption = bool(preemption)
        self._shed_deadlines = bool(shed_deadlines)
        self._preempt_log = deque(maxlen=64)  # recent preemptions —
        #   rides in flight-recorder dumps so a post-mortem shows WHY
        #   a slot was evicted
        self._gate_declined = False  # the paged admission gate turned
        #   the queue head away this tick (short on blocks) — the
        #   preemption probe's KV-pressure signal
        self._draining = False       # stop(drain=True) in progress:
        #   no new submits, no new admissions; in-flight slots finish
        self._rate_win = deque(maxlen=64)  # (t, emitted) per emitting
        #   tick — the measured drain rate behind Retry-After and
        #   deadline shedding
        self._ovl_lock = threading.Lock()  # guards _rate_win and
        #   _preempt_log: the engine thread appends while handler /
        #   watchdog threads snapshot (an unguarded deque raises
        #   "mutated during iteration" mid-read)
        self.faults = faults
        self.watchdog_s = (None if watchdog_s is None
                           else float(watchdog_s))
        self._watchdog = None
        self._watchdog_fired = False
        self._tick_started_at = None  # watchdog heartbeat: set at
        #   tick entry, cleared at exit

        import jax.numpy as jnp
        # the row spec: pools, block bytes, wire and debug geometry
        # all come from it (models/programs.py KVRowSpec)
        self._kvspec = sspec.kv
        self._serving_spec = sspec
        # a step that is not one row and one token a lane
        # (models/programs.py StepSpec); None for the models it is
        self._step = sspec.step
        self._nh, self._hd = sspec.kv.num_heads, sspec.kv.head_dim
        self._kv_dtype = kv_dtype = sspec.kv.dtype
        # the dtype LABEL for compiled-program cache keys, /healthz,
        # and the migration wire: a quantized pool keeps _kv_dtype as
        # its f32 COMPUTE dtype (attention math, scratch views) but
        # must never share programs or migrate blocks with an fp
        # engine of the same compute dtype
        self._kv_dtype_str = "int8" if self._kv_quant \
            else str(self._kv_dtype)
        self._weight_dtype_str = "int8" if self._weight_quant \
            else str(self._kv_dtype)
        # -- 2-D (mp, dp) serving mesh (mesh=...) ----------------------
        # ``mesh`` accepts an int mp degree, an (mp,) or (mp, dp)
        # tuple (resolved via distributed.mesh.serving_mesh over the
        # first mp*dp devices), or a prebuilt jax Mesh.  With mp > 1
        # the model must be the einsum-form tensor-parallel variant
        # (GPTModel(use_mp=True), or a dense checkpoint's
        # ``to_tensor_parallel()`` twin): its parameters carry 'mp'
        # PartitionSpecs, and placing params + KV pools sharded makes
        # every existing jitted dispatch compile ONCE PER CONFIG with
        # the sharding baked into the program — GSPMD splits attention
        # heads / FFN / vocab and inserts the psum/all-gather
        # collectives.  With dp > 1 the BATCH shards: each dp shard
        # owns num_slots/dp slot rows of every [B]-leading cursor
        # array, the block tables, and a contiguous range of the KV
        # block pool rows (params replicate over 'dp'), so one
        # compiled program spans both axes — dp multiplies concurrent
        # slots the way mp multiplies per-block capacity.  The
        # host-side tick protocol (host mirrors, [B]-id downloads,
        # the 17 B steady-state d2h) is unchanged.
        self.mesh = None
        self.mp = 1
        self.dp = 1
        self.mesh_axes = None
        self._repl_sharding = None
        self._kv_sharding = None
        self._kv_scale_sharding = None
        self._state_sharding = None
        self._table_sharding = None
        self._kv_block_bytes_per_shard = None
        self._kv_code_bytes_per_shard = None
        self._kv_scale_bytes_per_shard = None
        if mesh is not None:
            import jax
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec)
            from ..distributed import mesh as mesh_mod
            if isinstance(mesh, (int, np.integer)):
                mesh = mesh_mod.serving_mesh(int(mesh))
            elif isinstance(mesh, (tuple, list)):
                if len(mesh) not in (1, 2):
                    raise ValueError(
                        f"mesh shape must be (mp,) or (mp, dp), got "
                        f"{tuple(mesh)} — the serving engine shards "
                        "over a tensor-parallel and a data-parallel "
                        "axis")
                mesh = mesh_mod.serving_mesh(
                    int(mesh[0]),
                    int(mesh[1]) if len(mesh) == 2 else 1)
            elif not isinstance(mesh, Mesh):
                raise ValueError(
                    f"mesh must be an int mp degree, an (mp,) / "
                    f"(mp, dp) tuple, or a jax Mesh, got "
                    f"{type(mesh).__name__}")
            self.mesh = mesh
            self.mp = int(mesh.shape.get("mp", 1))
            self.dp = int(mesh.shape.get("dp", 1))
            extra = {k: int(v) for k, v in mesh.shape.items()
                     if k not in ("mp", "dp") and int(v) > 1}
            if extra:
                # a pp/sp/... axis would silently REPLICATE params and
                # KV pools across it (the serving specs only name
                # 'mp' and 'dp') — not a silent HBM tax
                raise ValueError(
                    f"serving mesh must shard only the 'mp' and 'dp' "
                    f"axes; got extra axes {extra} — build one with "
                    "distributed.mesh.serving_mesh(mp, dp)")
            self.mesh_axes = ({k: int(v) for k, v in mesh.shape.items()
                               if int(v) > 1} or {"mp": 1})
            if self.mp > 1:
                if not sspec.tensor_parallel:
                    raise ValueError(
                        "mesh with mp > 1 requires the tensor-parallel"
                        " model form: build with GPTModel(use_mp=True)"
                        " or convert a dense checkpoint with "
                        "model.to_tensor_parallel() — the dense fused "
                        "qkv layout cannot shard its head axis (see "
                        "distributed/sharding.py)")
                if self._nh % self.mp:
                    raise ValueError(
                        f"num_heads ({self._nh}) must divide by the "
                        f"mesh's mp degree ({self.mp}) — attention "
                        "shards whole heads")
            if self.dp > 1 and self.num_slots % self.dp:
                raise ValueError(
                    f"num_slots ({self.num_slots}) must divide by the "
                    f"mesh's dp degree ({self.dp}) — each dp shard "
                    "owns an equal contiguous range of batch slots")
            if self.mp * self.dp > 1:
                # the TP layers' activation sharding constraints
                # (distributed/sharding.py _constraint) read the
                # process-global mesh, and the shard_map-wrapped
                # ragged kernel discovers its mesh the same way; one
                # sharded engine per process owns it (sibling
                # UNSHARDED engines are unaffected — dense models
                # carry no constraints and the unsharded kernel path
                # never consults the mesh)
                mesh_mod.set_mesh(mesh)
            # the canonical serving layout table lives in
            # distributed/sharding.py (SERVING_SPECS) so the engine,
            # the shard_map-wrapped ragged kernel, and the tests
            # agree on one source of truth; specs name 'dp' even at
            # dp == 1 (a size-1 axis), so the program shape is
            # uniform across layouts
            from ..distributed.sharding import serving_sharding
            self._repl_sharding = serving_sharding(mesh, "replicated")
            self._kv_sharding = serving_sharding(mesh, "kv")
            self._kv_scale_sharding = serving_sharding(mesh,
                                                       "kv_scale")
            self._state_sharding = serving_sharding(mesh, "state")
            self._table_sharding = serving_sharding(mesh, "table")
            # place params per their TP PartitionSpecs (replicated
            # when none — and always replicated over 'dp'): every
            # compiled dispatch then sees sharded weight inputs and
            # GSPMD partitions the program
            for _, p in model.named_parameters():
                spec = getattr(p, "partition_spec", None)
                sh = (NamedSharding(mesh, spec) if spec is not None
                      else self._repl_sharding)
                p._data = jax.device_put(p._data, sh)
            for _, b in model.named_buffers():
                b._data = jax.device_put(b._data, self._repl_sharding)
        self._kv_budget_mb = (None if kv_budget_mb is None
                              else float(kv_budget_mb))
        self._chunk = None
        self._tick_budget = None
        if prefill_chunk is not None:
            c = int(prefill_chunk)
            if c < 1 or self.max_seq_len % c:
                raise ValueError(
                    f"prefill_chunk must be >= 1 and divide max_seq_len"
                    f" ({self.max_seq_len}), got {c} — dividing keeps "
                    "the chunk window from clamping onto live cache "
                    "rows")
            b = int(tick_token_budget) if tick_token_budget is not None \
                else c
            if b < c:
                raise ValueError(
                    f"tick_token_budget ({b}) must cover at least one "
                    f"prefill_chunk ({c}), or no tick could ever make "
                    "prefill progress")
            self._check_aligned("prefill_chunk", c)
            self._chunk = c
            self._tick_budget = b
        elif tick_token_budget is not None:
            raise ValueError(
                "tick_token_budget requires prefill_chunk (it bounds "
                "the chunked-prefill spend per tick)")
        self._spec_k = None
        self.proposer = None
        if spec_k is not None:
            k = int(spec_k)
            if k < 1:
                raise ValueError(f"spec_k must be >= 1, got {k}")
            if k + 2 > self.max_seq_len:
                raise ValueError(
                    f"spec_k={k} leaves no room for any request in a "
                    f"{self.max_seq_len}-position slot (the verify "
                    "window needs prompt + max_new_tokens + spec_k to "
                    "fit)")
            self._spec_k = k
            if proposer is None:
                from .spec import PromptLookupProposer
                proposer = PromptLookupProposer()
            pv = getattr(proposer, "vocab_size", None)
            if pv is not None and self.vocab_size is not None \
                    and int(pv) != self.vocab_size:
                raise ValueError(
                    f"proposer vocab ({pv}) != target model vocab "
                    f"({self.vocab_size}) — a draft from a different "
                    "tokenizer can never match and only burns the "
                    "verify window")
            self.proposer = proposer
        elif proposer is not None:
            raise ValueError(
                "proposer requires spec_k (the draft window width "
                "fixes the compiled verify program's shape)")
        async_depth = int(async_depth)
        if async_depth < 1:
            raise ValueError(
                f"async_depth must be >= 1, got {async_depth}")
        self.async_depth = async_depth
        self._paged = kv_block_size is not None
        if self._kv_quant:
            if not self._paged:
                raise ValueError(
                    "kv_dtype='int8' requires the paged KV layout "
                    "(kv_block_size=...): quantization is per-block — "
                    "the contiguous pools have no block granularity "
                    "to hang a scale on")
        if self._paged:
            bsz = int(kv_block_size)
            if bsz < 1 or self.max_seq_len % bsz:
                raise ValueError(
                    f"kv_block_size must be >= 1 and divide max_seq_len"
                    f" ({self.max_seq_len}), got {bsz}")
            self._check_aligned("kv_block_size", bsz)
            if self._kvspec.block_rows and self._chunk \
                    and self._chunk % bsz:
                # a lane that is still prefilling takes a discarded
                # decode step at its next chunk's first row, which
                # writes the tail of the block that holds that row: the
                # chunk rewrites it only if that block is not the one
                # its own state comes from
                raise ValueError(
                    f"prefill_chunk ({self._chunk}) must be a multiple "
                    f"of kv_block_size ({bsz}): "
                    f"{type(self.model).__name__} keeps a state in "
                    "every block's tail, and a chunk that starts "
                    "inside a block would continue from a tail the "
                    "lane's discarded decode step has overwritten")
            self._bs = bsz
            self._bps = self.max_seq_len // bsz  # blocks per full slot
            # per-shard footprint of ONE logical block: each mesh
            # shard stores only its H/mp heads' K/V rows, so a fixed
            # per-chip HBM budget (kv_budget_mb) buys mp x the blocks
            # — sharding the model scales KV capacity, not just
            # weights (KVRowSpec.block_bytes)
            # quantized pools store int8 codes plus the parallel f32
            # scale pool; both count against the budget so capacity
            # accounting adds up (code + scale components exposed as
            # serving.kv_block_bytes / serving.kv_scale_bytes)
            store_dtype = "int8" if self._kv_quant else self._kv_dtype
            self._kv_code_bytes_per_shard = self._kvspec.block_bytes(
                bsz, self.mp, dtype=store_dtype)
            self._kv_block_bytes_per_shard = self._kvspec.block_bytes(
                bsz, self.mp, dtype=store_dtype,
                scale_dtype="float32" if self._kv_quant else None)
            self._kv_scale_bytes_per_shard = (
                self._kv_block_bytes_per_shard
                - self._kv_code_bytes_per_shard)
            if kv_budget_mb is not None:
                if kv_blocks is not None:
                    raise ValueError(
                        "kv_budget_mb and kv_blocks are two answers to"
                        " one question (pool size) — pass one")
                # per-chip budget -> per-dp-shard block count; every
                # dp shard owns its own pool range, so the managed
                # total scales dp x on top of the mp x that the
                # smaller per-shard block bytes already buy: capacity
                # scales mp*dp at a fixed per-chip HBM budget
                managed = self.dp * int(
                    self._kv_budget_mb * 2 ** 20
                    // self._kv_block_bytes_per_shard)
            else:
                managed = (self.num_slots * self._bps
                           if kv_blocks is None else int(kv_blocks))
                # the dp shard ranges must be equal; round an explicit
                # kv_blocks UP so capacity is never silently reduced
                managed += -managed % self.dp
            if managed // self.dp < self._bps:
                # blame the knob the caller actually turned
                src = (f"kv_budget_mb={self._kv_budget_mb:g} "
                       f"(-> {managed // self.dp} blocks at "
                       f"{self._kv_block_bytes_per_shard} B/block/"
                       "shard)" if kv_budget_mb is not None
                       else f"kv_blocks={managed}"
                       + (f" (/{self.dp} dp shards)"
                          if self.dp > 1 else ""))
                raise ValueError(
                    f"{src} cannot hold even one max-length request "
                    f"({self._bps} blocks"
                    + (" per dp shard)" if self.dp > 1 else ")"))
            self._kv_managed = managed
            self._prefix_enabled = bool(prefix_cache)
        elif kv_budget_mb is not None:
            raise ValueError(
                "kv_budget_mb requires the paged KV layout "
                "(kv_block_size=...): the contiguous pools are sized "
                "by num_slots * max_seq_len, not by a block budget")
        # -- host-RAM offload tier (serving/offload.py) -----------------
        # A second, much larger home for KV blocks the device pool
        # evicts: demotes ride the prefix trie's evict hook (async
        # gather, materialized at tick boundaries), promotes ride the
        # admission gate's prefix match (host hit -> import into fresh
        # blocks, seed the trie, skip prefill for the restored span).
        self.host_store = None
        if kv_host_mb is not None:
            if not self._paged:
                raise ValueError(
                    "kv_host_mb requires the paged KV layout "
                    "(kv_block_size=...): the host tier parks whole "
                    "blocks — the contiguous pools have none")
            if not self._prefix_enabled:
                raise ValueError(
                    "kv_host_mb requires prefix_cache=True: demotes "
                    "are fed by the trie's eviction and promotes by "
                    "admission's prefix match")
            from .offload import HostBlockStore
            self.host_store = HostBlockStore(
                kv_host_mb, self._bs, self._nh, self._hd,
                self._kvspec.n_layers, self._kv_dtype_str)
        # -- ragged paged attention (attn_impl="ragged") ----------------
        if attn_impl is None:
            attn_impl = getattr(model, "attn_impl", "xla")
        if attn_impl not in ("xla", "ragged"):
            raise ValueError(
                f"attn_impl must be 'xla' or 'ragged', "
                f"got {attn_impl!r}")
        if attn_impl == "ragged" and not self._paged:
            raise ValueError(
                "attn_impl='ragged' requires the paged KV "
                "layout (kv_block_size=...): the kernel reads K/V "
                "through per-slot block tables — the contiguous "
                "layout keeps the XLA path")
        self.attn_impl = attn_impl
        self._ragged = attn_impl == "ragged"
        # the ONE ragged program's static window: wide enough for a
        # one-token decode lane, the k+1 spec-verify window, and a
        # prefill chunk — per-slot width is runtime data, so the
        # engine compiles exactly one paged window program however
        # traffic mixes (the compile-matrix collapse)
        self._wmax = max(1, (self._spec_k + 1) if self._spec_k else 1,
                         self._chunk or 1)
        if self._ragged:
            import jax
            dev = (self.mesh.devices.flat[0] if self.mesh is not None
                   else jax.devices()[0])
            if dev.platform != "cpu":
                # off the cpu platform there is no interpret mode:
                # prove NOW that Mosaic takes the kernel at this
                # engine's per-shard shapes, so a refusal is a
                # construction error carrying the compiler's words —
                # not a failing first tick that step() flight-records
                # and carries on from
                from ..ops.ragged_paged_attn import compile_check
                try:
                    compile_check(
                        num_slots=self.num_slots // self.dp,
                        window=self._wmax,
                        num_heads=self._nh // self.mp,
                        head_dim=self._hd, block_size=self._bs,
                        blocks_per_slot=self._bps,
                        num_blocks=self._kv_managed // self.dp + 1,
                        dtype=self._kv_dtype, quant=self._kv_quant,
                        device=dev)
                except Exception as e:
                    raise ValueError(
                        f"attn_impl={attn_impl!r} does not compile "
                        f"for {dev.device_kind}: {e}") from e
        self._ragged_fn = None  # resolved jitted ragged-window handle
        # -- the decode attention's core: kernel or walk ----------------
        # the model chooses from what it can see (platform, layout,
        # pool dtype, head size, mesh, slots: models/programs.py
        # slot_attn_core) when it traces the decode / verify programs;
        # the engine asks the same rule here for its counters and
        # /healthz, and has Mosaic take the model's kernel NOW at the
        # shapes those programs will compile
        # (ServingSpec.attn_kernel_check)
        self._attn_core = None
        if sspec.attn_core is not None and not self._ragged:
            self._attn_core = dict(sspec.attn_core(
                paged=self._paged, quant=self._kv_quant,
                table_rows=self.max_seq_len,
                block_size=self._bs if self._paged else None,
                slots=self.num_slots),
                pool_dtype=self._kv_dtype_str)
        self._attn_kernel = (self._attn_core or {}).get("form") == "kernel"
        # the host twin of what the kernel fetches (_rows_walked); None:
        # the served model's own rule, ServingSpec.decode_rows
        self._kernel_rows = None
        if self._attn_kernel:
            import jax
            self._kernel_rows = sspec.attn_kernel_rows
            dev = jax.devices()[0]
            if dev.platform != "cpu":
                try:
                    sspec.attn_kernel_check(
                        num_slots=self.num_slots, block_size=self._bs,
                        blocks_per_slot=self._bps,
                        num_blocks=self._kv_managed + 1,
                        dtype=self._kv_dtype, spec_k=self._spec_k,
                        device=dev)
                except Exception as e:
                    raise ValueError(
                        "the decode attention's kernel does not compile "
                        f"for {dev.device_kind}: {e}") from e
        self._zero_scale_fn = None  # jitted fresh-block scale zeroer
        #   (kv_dtype='int8'; compiled once per config — see
        #   _zero_fresh_scales)
        # -- multi-adapter (LoRA) lanes (serving/lora.py) ---------------
        # "which adapter" is per-slot DATA gathered from fixed-shape
        # banks inside the traced programs, so every adapter — loaded
        # now or hot-loaded later — shares the engine's one compiled
        # program per config.
        self.adapters = None
        if adapters is not None or max_adapters is not None:
            if self.mesh is not None or sspec.tensor_parallel:
                raise ValueError(
                    "adapters cannot combine with tensor-parallel "
                    "serving (mesh=... / use_mp models): the LoRA "
                    "delta rides the dense out_proj form")
            init = dict(adapters or {})
            for _nm, _ad in init.items():
                if not isinstance(_ad, LoRAAdapter):
                    raise TypeError(
                        f"adapters[{_nm!r}] must be a LoRAAdapter, "
                        f"got {type(_ad).__name__}")
            n_ad = (int(max_adapters) if max_adapters is not None
                    else max(len(init), 1))
            if n_ad < len(init):
                raise ValueError(
                    f"max_adapters={n_ad} cannot hold the "
                    f"{len(init)} adapters passed at construction")
            r_max = (int(max_lora_rank) if max_lora_rank is not None
                     else max([a.rank for a in init.values()] or [8]))
            self.adapters = AdapterRegistry(
                self._kvspec.n_layers, sspec.hidden_size, n_ad, r_max)
            for _nm in sorted(init):
                self.adapters.load(_nm, init[_nm])
        # -- tracing / flight recorder ---------------------------------
        self.tracer = (monitor.Tracer(capacity=trace_capacity,
                                      annotate=trace_annotations)
                       if tracing else monitor.NullTracer())
        # device watcher (see _watch_device): its queue exists iff
        # tracing is on, its thread starts at the first dispatch
        self._dev_q = queue.SimpleQueue() if tracing else None
        self._dev_thread = None
        self._blocked_s = 0.0   # time this tick spent waiting on the
        #   device (d2h syncs, collectives): tick less this = host_ms
        self._blocked_cpu_s = 0.0  # thread CPU burned inside those
        #   waits (_DeviceWait): left out of the tick's cpu_ms
        self._cpu_carry_ms = 0.0   # CPU time a coarse clock charged to
        #   a tick beyond its host_ms: the next ticks' (_step_inner)
        # the phases' own cpu_ms: where a read of the thread's CPU
        # clock is cheap (0.3 us on stock Linux), or in the detailed
        # mode (annotations on).  Where it is a trap into a sandbox's
        # kernel (5.5 us alone and far more beside the runtime's busy
        # threads: ~0.7 ms a tick on the benchmark's machine) the
        # default ring keeps the tick's own split and the counters
        self._phase_cpu = bool(tracing) and (
            bool(trace_annotations)
            or monitor.tracing.thread_clock_read_us()
            <= _PHASE_CPU_MAX_READ_US)
        self._flight_dir = flight_dir
        self.last_flight = None        # chrome-trace dict of the most
        self.last_flight_path = None   # recent step failure (+ file)
        self.tick_no = 0
        self._reset_pools()

        params = dict(model.named_parameters())
        self._params = params
        self._pnames = sorted(params)
        self._bnames_all = tuple(sorted(dict(model.named_buffers())))

        # -- metrics -----------------------------------------------------
        reg = registry or monitor.default_registry()
        self.registry = reg
        self._m_queue = reg.gauge(
            "serving.queue_depth", "requests waiting for a slot")
        self._m_occ = reg.gauge(
            "serving.slot_occupancy", "busy slots out of num_slots")
        self._m_slots = reg.gauge(
            "serving.slot_total", "configured slot pool size")
        self._m_slots.set(self.num_slots)
        self._m_mesh = reg.gauge(
            "serving.mesh_devices", "devices in this engine's serving "
            "mesh (mp x dp shards; 1 = unsharded single device)")
        self._m_mesh.set(self.mesh.size if self.mesh is not None else 1)
        self._m_tokens = reg.counter(
            "serving.tokens_total", "generated tokens")
        self._m_reqs = reg.counter(
            "serving.requests_total", "submitted requests")
        self._m_done = reg.counter(
            "serving.requests_completed", "finished requests")
        self._m_timeout = reg.counter(
            "serving.requests_timeout", "requests expired in queue")
        self._m_ttft = reg.histogram(
            "serving.ttft_ms", "time to first token (ms)")
        self._m_tpot = reg.histogram(
            "serving.tpot_ms", "time per output token after the first "
            "(ms, per finished request)")
        self._m_rate = monitor.RateMeter(reg.gauge(
            "serving.tokens_per_sec", "windowed decode throughput"))
        # paged-KV surface (registered always so dashboards see the
        # names; they stay zero in contiguous mode)
        self._m_prefill_tokens = reg.counter(
            "serving.prefill_tokens", "prompt tokens actually computed"
            " in prefill (prefix-cache hits skip the shared span)")
        self._m_kv_blocks = reg.gauge(
            "serving.kv_blocks_in_use", "paged KV blocks referenced by"
            " slots or cached prefixes")
        self._m_kv_total = reg.gauge(
            "serving.kv_blocks_total", "paged KV pool size in blocks")
        self._m_kv_block_bytes = reg.gauge(
            "serving.kv_block_bytes", "per-shard K/V ROW bytes of one "
            "logical block across all layers (int8 code bytes when "
            "kv_dtype='int8')")
        self._m_kv_scale_bytes = reg.gauge(
            "serving.kv_scale_bytes", "per-shard scale-pool bytes of "
            "one logical block (0 unless kv_dtype='int8') — "
            "kv_blocks_total * (kv_block_bytes + kv_scale_bytes) "
            "<= kv_budget_mb")
        if self._paged:
            self._m_kv_total.set(self._kv_managed)
            self._m_kv_block_bytes.set(self._kv_code_bytes_per_shard)
            self._m_kv_scale_bytes.set(self._kv_scale_bytes_per_shard)
        self._m_kv_row_bytes = reg.gauge(
            "serving.kv_row_bytes", "bytes one cached position takes "
            "over all layers as the pools store it: their dtype, and "
            "a row padded to whole 128-lane tiles (K and V rows of "
            "every head, or a latent row)")
        self._m_kv_row_bytes.set(self._kvspec.position_bytes(
            "int8" if self._kv_quant else None))
        # counters the model's step programs feed: an int32 vector
        # each returns beside its outputs (ServingSpec.counters),
        # added up when the tick's ids are downloaded
        self._m_program = [
            reg.counter("serving." + name, "summed over the decode "
                        "and chunk programs' runs (the model's "
                        "ServingSpec.counters)")
            for name, _ in sspec.counters]
        self._m_prefix_hits = reg.counter(
            "serving.prefix_hits", "admissions that adopted a cached "
            "prompt prefix")
        self._m_prefix_hit_tokens = reg.counter(
            "serving.prefix_hit_tokens", "prompt tokens served from "
            "cached prefix blocks (prefill skipped)")
        self._m_prefix_evictions = reg.counter(
            "serving.prefix_evictions", "cached prefix blocks evicted "
            "(LRU) under pool pressure")
        # chunked-prefill surface (registered always; zero when
        # prefill_chunk is off)
        self._m_chunks = reg.counter(
            "serving.prefill_chunks", "chunked-prefill dispatches")
        # how the device-resident step state follows the host: whole
        # uploads (1 after warm-up in a healthy run: the first tick's,
        # then one a step-failure rebuild) beside the per-slot patch
        # programs queued behind the decodes in flight, and what still
        # empties the ring, by reason
        self._m_state_pushes = reg.counter(
            "serving.state_pushes", "whole-state uploads of the step "
            "state (the first tick, and the rebuild after a failed "
            "step)")
        self._m_state_patches = reg.counter(
            "serving.state_patches", "per-slot state patch programs "
            "queued in dispatch order (admission, chunk progress, a "
            "first token, eviction)")
        self._m_ring_drains = {
            why: reg.counter(
                f"serving.ring_drains.{why}", "times the in-flight "
                f"ring was consumed to empty: {what}")
            for why, what in _RING_DRAINS.items()}
        self._m_stall = reg.histogram(
            "serving.decode_stall_ms", "gap between consecutive decode "
            "dispatches while slots were decoding — the time decoders "
            "stalled on interleaved prefill work (ms)")
        self._m_decode_batch = reg.gauge(
            "serving.decode_batch", "DECODING slots in the latest "
            "decode dispatch")
        # speculative-decoding surface (registered always; zero when
        # spec_k is off)
        self._m_spec_proposed = reg.counter(
            "serving.spec_proposed", "draft lanes proposed to the "
            "speculative verify dispatch")
        self._m_spec_accepted = reg.counter(
            "serving.spec_accepted", "draft lanes accepted (their "
            "token emitted from a matched lane)")
        self._m_spec_windows = reg.counter(
            "serving.spec_windows", "per-slot verify windows scored "
            "(one speculative dispatch covers every DECODING slot; "
            "a request's final window may propose fewer than spec_k "
            "lanes, so accepted/windows is the honest mean-accepted-"
            "lanes denominator)")
        self._m_spec_rate = reg.gauge(
            "serving.spec_acceptance_rate", "accepted / proposed "
            "draft lanes, cumulative over this engine's lifetime")
        self._m_spec_tpt = reg.gauge(
            "serving.spec_tokens_per_tick", "tokens emitted per "
            "DECODING slot by the latest speculative verify dispatch "
            "(1.0 = nothing accepted, spec_k+1 = full window)")
        self._m_d2h = reg.gauge(
            "serving.d2h_bytes_per_tick", "bytes the latest decode "
            "dispatch downloaded to the host (the sampled ids + "
            "accept counts + done mask, never the logits)")
        self._m_fused_ticks = reg.counter(
            "serving.fused_sample_ticks", "decode dispatches that "
            "sampled on device")
        self._m_rows_walked = reg.counter(
            "serving.decode_rows_walked", "cache rows the decode / "
            "verify dispatches fetched, summed over slots, from the "
            "host's position mirror.  Where the attention core is the "
            "kernel (serving.attn_kernel_dispatches): what the kernel "
            "copies into VMEM, rows as stored, sums and weights "
            "float32, nothing for a parked slot (GPT, "
            "ops/ragged_paged_attn.py stream_rows: every live slot's "
            "pages to its own window's end; a grouped-query model, "
            "ops/gq_walk_trip.py: the work list's items and no "
            "padding item, walk_rows(padded=False)).  Where it is "
            "the XLA walk, the served model's rule "
            "(ServingSpec.decode_rows; models/programs.py walk_rows: a "
            "work list of (slot, chunk) items taken a whole trip at a "
            "time, the last trip's padding items included).  Over "
            "serving.decode_rows_table it is the share of the table "
            "read, 1.0 = every row of every slot")
        self._m_attn_kernel = reg.counter(
            "serving.attn_kernel_dispatches", "decode and verify "
            "dispatches whose attention core is a Pallas kernel that "
            "streams pages under its arithmetic (the host knows it from "
            "the program it built: /healthz attn_core); the others "
            "of serving.fused_sample_ticks walked in XLA")
        self._m_rows_table = reg.counter(
            "serving.decode_rows_table", "cache rows of every slot's "
            "whole table, summed over the same dispatches")
        self._m_rows_live = reg.counter(
            "serving.decode_rows_live", "cache rows some query of the "
            "same dispatches can see, summed over the slots that hold "
            "a position: serving.decode_rows_walked over it is what "
            "the walk reads for every row it needs (1.0 = nothing "
            "else)")
        self._m_kv_blocks_walked = reg.gauge(
            "serving.kv_blocks_walked_per_tick", "KV blocks the "
            "ragged kernel walked in the latest dispatch, summed over "
            "lanes: the streaming kernel (attn_impl='ragged') stops "
            "at each lane's causal horizon ceil((pos + width) / "
            "block_size), so this tracks LIVE context")
        # max context length any request has reached on this engine
        # (slot cursor high-water: prefilled prompt + decoded tokens)
        # — surfaced in /healthz and /debug/requests so the fleet's
        # long-context exposure is observable per replica
        self._max_context_len = 0
        # async-loop surface (registered always; overlap stays empty
        # and async_depth reads 1 when the loop is synchronous)
        self._m_async_depth = reg.gauge(
            "serving.async_depth", "engine pipeline depth (1 = "
            "synchronous tick, 2 = tick N+1 dispatched before tick N "
            "is consumed)")
        self._m_async_depth.set(self.async_depth)
        self._m_overlap = reg.histogram(
            "serving.tick_overlap_ms", "host work (admission planning "
            "+ previous tick's emit/metrics) done per tick WHILE a "
            "decode dispatch was in flight — the scheduling time the "
            "async loop hides behind device compute (ms)")
        self._m_d2h_wait = reg.histogram(
            "serving.d2h_wait_ms", "blocking wait materializing a "
            "dispatched tick's ids + done mask (ms) — the only sync "
            "point of the async loop; near-zero means the host fully "
            "hid its work behind device compute")
        # compile-event surface: every NEW jitted program of this
        # engine's model (any trigger — this engine, a sibling engine,
        # generate()) bumps the counter and lands in the trace; a
        # steady-state increase is the compile-thrash signal the
        # bounded chunk/spec shapes exist to prevent
        self._m_compiles = reg.counter(
            "serving.compiles_total", "new jitted programs compiled "
            "since engine start (first-call trace + XLA compile "
            "events; nonzero growth in steady state = the program "
            "cache is thrashing)")
        self._m_compile_ms = reg.histogram(
            "serving.compile_ms", "wall time of each new program's "
            "first call (jax trace + XLA compile + first run, ms)")
        self._m_compile_wall = reg.counter(
            "serving.compile_wall_ms", "summed wall time of every new "
            "program's first call (the same times as "
            "serving.compile_ms, ms): what set-up spends tracing, "
            "compiling or loading from the cache, and first running "
            "its programs")
        self._m_dev_busy = reg.counter(
            "serving.dev_busy_ms", "summed duration of the dev.* spans"
            " (ms): device time of the engine's dispatches as the "
            "device watcher saw them complete — its rate is the "
            "device's utilisation, with no profiler attached (0 with "
            "tracing=False)")
        # the host by thread: what the tick's thread, the HTTP
        # handlers' and the device watcher's each spent on a CPU, and
        # the whole process (tracing on or off).  Over a window the
        # process less these three is the runtime's own threads
        # (transfers, PJRT), which hold no interpreter lock
        self._m_tick_host = reg.counter(
            "serving.tick_host_ms", "summed host_ms of the ticks: a "
            "tick's wall time less its waits for the device "
            "(decode.d2h_wait / decode.d2h, prefill.d2h, "
            "decode.allgather) (ms)")
        self._m_tick_cpu = reg.counter(
            "serving.tick_cpu_ms", "thread CPU time of the tick's "
            "thread inside tick_host_ms: the Python the tick ran "
            "(ms; what a coarse CPU clock charges a tick beyond its "
            "host_ms goes to the next ticks, so read it over many)")
        self._m_tick_wait = reg.counter(
            "serving.tick_wait_ms", "tick_host_ms less tick_cpu_ms: "
            "the tick's thread neither waiting for the device nor "
            "running, so waiting for the interpreter lock, a sink "
            "lock or the scheduler (ms)")
        self._m_http_cpu = reg.counter(
            "serving.http_cpu_ms", "thread CPU time of the HTTP "
            "handler threads in http.ingest and http.stream, summed "
            "over threads; a live stream adds its share at least "
            "every 32 frames (ms)")
        self._m_http_frames = reg.counter(
            "serving.http_frames", "SSE frames written by streamed "
            "responses (token, heartbeat and terminal frames)")
        self._m_http_bytes = reg.counter(
            "serving.http_bytes_out", "bytes of those frames")
        self._m_dev_watch_cpu = reg.counter(
            "serving.dev_watch_cpu_ms", "thread CPU time of the "
            "device watcher thread (ms; 0 with tracing=False)")
        self._m_proc_cpu = reg.gauge(
            "process.cpu_ms", "time.process_time() at the last tick: "
            "CPU time of every thread of the process (ms)")
        # overload-protection surface: preemption / shedding /
        # fairness / chaos (registered always; zero when idle)
        self._m_preempt = reg.counter(
            "serving.preemptions_total", "mid-stream preemptions: a "
            "running lower-priority request evicted back to the "
            "queue (emitted tokens preserved; paged blocks returned "
            "to the prefix cache)")
        self._m_resumed = reg.counter(
            "serving.resumed_total", "re-admissions of previously "
            "preempted requests (prefix adoption skips the shared "
            "span's re-prefill in paged mode)")
        self._m_shed_deadline = reg.counter(
            "serving.shed_deadline_total", "requests rejected at "
            "submit because the estimated queue drain already blew "
            "their deadline (DeadlineShed, honest Retry-After)")
        self._m_shed_rate = reg.counter(
            "serving.shed_rate_limited_total", "requests rejected at "
            "submit by a tenant token bucket (RateLimited)")
        self._m_shed_queue = reg.counter(
            "serving.shed_queue_full_total", "requests rejected at "
            "submit because the admission queue was at max_queue")
        self._m_drain_tps = reg.gauge(
            "serving.drain_rate_tps", "measured decode drain rate "
            "(tokens/sec over the recent emitting-tick window) — the "
            "denominator of Retry-After and deadline-shed estimates")
        self._m_watchdog = reg.counter(
            "serving.watchdog_fires", "ticks the watchdog declared "
            "wedged (flight-recorded; cooperative blocks raise into "
            "step recovery)")
        self._m_faults = reg.counter(
            "serving.faults_injected", "fault-injection sites fired "
            "(serving/faults.py — nonzero only under a chaos "
            "harness)")
        self._m_proposer_failures = reg.counter(
            "serving.proposer_failures", "proposer calls that raised "
            "— degraded to an empty draft window (verify emits the "
            "bonus token) instead of failing the tick")
        self._m_kv_migrated = reg.counter(
            "serving.kv_blocks_migrated", "paged KV blocks exported "
            "toward another replica (stream migration + prefix "
            "warming; counted on the EXPORT side only, so a shared "
            "registry never double-counts a transfer)")
        self._m_kv_host_blocks = reg.gauge(
            "serving.kv_host_blocks", "KV blocks resident in the "
            "host-RAM offload tier (kv_host_mb=...)")
        self._m_kv_host_bytes = reg.gauge(
            "serving.kv_host_bytes", "bytes the host-RAM offload tier "
            "holds (codes + scales for int8 pools)")
        self._m_offload_demotes = reg.counter(
            "serving.offload_demotes", "KV blocks demoted device -> "
            "host at eviction (materialized at tick boundaries)")
        self._m_offload_promotes = reg.counter(
            "serving.offload_promotes", "KV blocks promoted host -> "
            "device at admission (restored instead of recomputed)")
        self._m_offload_hit_tokens = reg.counter(
            "serving.offload_hit_tokens", "prompt tokens whose "
            "prefill was skipped via a host-tier restore (the "
            "host-side share of prefix_hit_tokens)")
        # weakref'd listener: a collected engine returns False from the
        # callback and the model drops it — engines must not leak into
        # the model's listener list across their lifetimes
        wm = weakref.WeakMethod(self._on_compile)

        def _compile_cb(kind, key, wall_s, _wm=wm):
            bound = _wm()
            if bound is None:
                return False
            bound(kind, key, wall_s)
            return True

        self._compile_cb = _compile_cb
        self._compile_cb_active = False
        self._register_compile_listener()

        self._last_decode_end = None  # stall anchor: end of the last
        #   decode dispatch, cleared when no slot is decoding
        self._evicted_in_tick = 0     # monotonic eviction counter; the
        #   tick reads DELTAS to keep the occupancy gauge exact without
        #   re-locking the scheduler after the decode dispatch
        self._insert_fn = None
        self._state_fns = None  # (unpack, patch, {sampled: first}):
        #   _state_programs
        self._fused_fn = None   # resolved fused decode+sample handle
        self._fused_spec_fn = None  # fused verify+sample/accept handle
        self._p_arrays = None   # lazy snapshots of param/buffer handles
        self._b_arrays = None   # (see refresh_params)
        self._thread = None
        self._stop = threading.Event()
        self._wake = threading.Event()  # event-driven loop wake:
        #   submit() sets it, so an idle engine blocks instead of
        #   polling and admission latency stops paying poll jitter
        self._mig_lock = threading.Lock()
        self._migrate_demands = []  # _MigrateDemand orders, registered
        #   by any thread (migrate_out / migrate_in / export_prefix /
        #   import_prefix) and serviced by the engine thread at the
        #   next tick boundary
        self._migration_log = deque(maxlen=64)  # {"tick","dir",...}
        self._overlap_acc = 0.0  # per-tick overlapped-host-work clock
        self._drain_on_exit = None  # set to a loop's stop event when
        #                             that loop must drain on exit

    def _alloc_pool(self, shape):
        """One per-layer K/V pool, mesh-sharded on the head axis when
        the engine serves tensor-parallel: each shard materializes
        only its H/mp heads' slice (axis 2 in both layouts), so pool
        HBM per chip shrinks by mp — the headroom kv_budget_mb turns
        into extra logical blocks.  Sharded pools are allocated by a
        COMPILED zeros program with the sharding as its output spec,
        so each device materializes only its own shard — a whole-pool
        array staged through one device would defeat the very
        capacity scaling, since an aggregate pool sized for the mesh
        need not fit any single chip.  (Not
        make_array_from_callback: its per-shard host callback
        segfaults intermittently under this jax version.)"""
        import jax.numpy as jnp
        if self._kv_sharding is None:
            if self._kv_quant:
                from .quant import QuantKV
                return QuantKV(
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros((shape[0], shape[2]), jnp.float32))
            return jnp.zeros(shape, self._kv_dtype)
        import jax
        fn = getattr(self, "_pool_zeros_fn", None)
        if fn is None:
            shape = tuple(shape)
            dtype = self._kv_dtype
            if self._kv_quant:
                from .quant import QuantKV

                def zeros():
                    return QuantKV(
                        jnp.zeros(shape, jnp.int8),
                        jnp.zeros((shape[0], shape[2]), jnp.float32))

                out_sh = QuantKV(self._kv_sharding,
                                 self._kv_scale_sharding)
            else:

                def zeros():
                    return jnp.zeros(shape, dtype)

                out_sh = self._kv_sharding
            # cached: the pool shape is fixed per engine, and the
            # step-failure recovery path re-allocates repeatedly
            fn = self._pool_zeros_fn = jax.jit(
                zeros, out_shardings=out_sh)
        return fn()

    def kv_geometry(self):
        """The block geometry the row spec gives (what ``/healthz``,
        ``/debug/requests`` and the migration wire report); None for
        the contiguous layout."""
        return self._kvspec.geometry(self._bs) if self._paged else None

    def step_report(self):
        """What the served model says of a step that is not one row
        and one token a lane (``StepSpec.report``; ``/healthz``
        ``step``); None for a model whose step is."""
        return dict(self._step.report) if self._step is not None else None

    # every feature a ``ServingSpec.unsupported`` may name, by the
    # option that asks for it (a key that is not here is a refusal
    # nothing reads: tests/test_mla_moe.py holds the models to it)
    _REFUSABLE = {
        "contiguous": "kv_block_size=None (contiguous KV)",
        "unchunked_prefill": "prefill_chunk=None",
        "ragged": "attn_impl='ragged'",
        "spec": "spec_k / proposer (speculative verify)",
        "kv_int8": "kv_dtype='int8'",
        "mp": "mesh=... (mp / dp > 1)",
        "lora": "adapters / max_adapters (LoRA banks)",
        "offload": "kv_host_mb (host offload)",
        "migration": "KV migration (migrate_out / import)",
        "sampling": "temperature / top_k / top_p (a sampled request)",
    }

    # the features whose code takes ``v_pools`` to be a V pool a layer
    # (``zip(k_pools, v_pools)``: the contiguous insert, int8 scales,
    # the mesh's pool sharding, the host tier, the migration wire): a
    # model whose second list holds per-block rows has to refuse them
    _ROWS_ONLY = ("contiguous", "kv_int8", "mp", "offload", "migration")

    def _check_aligned(self, option, value):
        """A served model whose positions come in groups
        (``StepSpec.align``) keeps chunk starts and block edges on
        them."""
        align = self._step.align if self._step is not None else 1
        if value % align:
            raise ValueError(
                f"{option} ({value}) must be a multiple of "
                f"{align}: {type(self.model).__name__} prefills, "
                f"commits and adopts positions {align} at a time")

    def _refuse_unsupported(self, sspec, used):
        """Raise, naming the option and the missing piece, for every
        engine feature in use that the model's ``ServingSpec`` lists
        as unsupported; nothing is silently ignored."""
        for feature, on in used.items():
            if on and feature in sspec.unsupported:
                raise ValueError(
                    f"{type(self.model).__name__} cannot be served "
                    f"with {self._REFUSABLE[feature]} yet: it lacks "
                    f"{sspec.unsupported[feature]}")

    def _slot_shard(self, i):
        """The dp mesh shard that owns batch slot ``i``: slots divide
        into ``dp`` contiguous ranges of ``num_slots/dp`` rows,
        matching the ``P('dp', ...)`` sharding of every [B]-leading
        device array (always 0 when dp == 1)."""
        return int(i) // (self.num_slots // self.dp)

    def _reset_pools(self):
        """(Re)allocate the per-layer K/V pools and per-slot step
        state.  Also the failure-recovery path: a decode dispatch that
        dies AFTER consuming its donated pools leaves them deleted, so
        the loop handler must rebuild before the next tick.  In paged
        mode the block pool, prefix cache, and block tables are rebuilt
        with the arrays — cached prefixes die with the device rows
        they described."""
        import jax.numpy as jnp
        if self._paged:
            # +dp: each dp shard's pool range leads with one reserved
            # scratch block that its parked (inactive) slots read and
            # write through — their garbage compute may not touch a
            # block some live request owns, and under shard_map a
            # slot can only address rows inside its OWN shard's range
            # (dp == 1: one scratch block, physical row 0, as before)
            leading = (self._kv_managed + self.dp, self._bs)
            self.block_pool = BlockPool(
                self._kv_managed + self.dp, self._bs,
                reserved_blocks=1, shards=self.dp,
                # chaos-harness hook: a scheduled "pool_exhaust" tick
                # turns this alloc into NoFreeBlocks (no-op when no
                # injector is attached)
                fault_hook=lambda n: self._fault("pool_exhaust"))
            self.prefix_cache = PrefixCache(
                self.block_pool,
                evict_hook=(self._offload_demote_hook
                            if self.host_store is not None else None)) \
                if self._prefix_enabled else None
            # pending demote gathers die with the pools they read
            # (step-failure recovery re-allocates) — drop, don't flush
            self._offload_pending = []
            self._offload_pending_keys = set()
            # per-slot scratch row: slot i belongs to dp shard
            # i // (num_slots/dp) and parks on THAT shard's reserved
            # row (all zeros at dp == 1); a parked/padded table entry
            # is this row, never a literal 0
            self._slot_scratch = np.asarray(
                [self.block_pool.scratch_row(self._slot_shard(i))
                 for i in range(self.num_slots)], np.int32)
            self._block_tables = np.repeat(
                self._slot_scratch[:, None], self._bps, axis=1).copy()
            self._slot_blocks = [[] for _ in range(self.num_slots)]
        else:
            leading = (self.num_slots, self.max_seq_len)
        # one pool a layer for each row the model keeps: K and V for
        # full attention; a latent cache keeps one row and no V, and
        # its ``v_pools`` is the empty list every program passes
        # through
        shapes = self._kvspec.pool_shapes(leading)
        layers = range(self._kvspec.n_layers)
        self.k_pools = [self._alloc_pool(shapes[0]) for _ in layers]
        if self._kvspec.block_rows:
            # what a model keeps once a block (KVRowSpec.block_rows: a
            # layer's state in its blocks' tails) takes the second
            # list: [num_blocks, width] pools under the same block ids,
            # donated and returned by the step programs as the row
            # pools are
            self.v_pools = [
                self._alloc_pool(shape) for shape in
                self._kvspec.block_pool_shapes(leading[0])]
        else:
            self.v_pools = ([self._alloc_pool(shapes[1]) for _ in layers]
                            if len(shapes) > 1 else [])
        # where this engine runs, read off the pools themselves (fixed
        # per config): /healthz and /debug/requests report it, so a
        # caller asserts the chip through the server's own surface
        pool0 = self.k_pools[0]
        devs = sorted(getattr(pool0, "codes", pool0).sharding.device_set,
                      key=lambda d: d.id)
        self.placement = {"platform": devs[0].platform,
                          "device_kind": devs[0].device_kind,
                          "device_ids": [d.id for d in devs]}
        # host-side per-slot step state: MIRRORS of the
        # device-resident cursors, the engine's truth of CONSUMED
        # ticks.  An admission / eviction / chunk marks its slot dirty
        # and the slot's lanes are patched on the device in dispatch
        # order (_patch_state); the whole is uploaded once (_push_state)
        self._pos = np.zeros(self.num_slots, np.int32)
        self._cur_tok = np.zeros(
            (self.num_slots, self._step.rows if self._step else 1),
            np.int32)
        # the step program's own word a lane (StepSpec.open): mirrored,
        # never read; 0 = the lane does not step
        self._flags = np.zeros(self.num_slots, np.int32)
        # per-slot sampling lanes: temperature 0 is the
        # greedy sentinel, seed words feed core/rng.request_key, and
        # _sctr tracks each request's emitted-token count — the rng
        # fold counter that makes a seed reproduce across restarts
        self._temp = np.zeros(self.num_slots, np.float32)
        self._topk = np.zeros(self.num_slots, np.int32)
        self._topp = np.ones(self.num_slots, np.float32)
        self._seed_lo = np.zeros(self.num_slots, np.uint32)
        self._seed_hi = np.zeros(self.num_slots, np.uint32)
        self._sctr = np.zeros(self.num_slots, np.int32)
        # device-side stop-condition lanes: per-slot eos id (-1 =
        # none) and remaining token budget — a lane whose budget hits
        # zero freezes inside the dispatch, which is what makes
        # dispatching tick N+1 before consuming tick N safe
        self._eos = np.full(self.num_slots, -1, np.int32)
        self._rem = np.zeros(self.num_slots, np.int32)
        # per-slot LoRA lane (0 = base model); mirrors like the rest
        self._aid = np.zeros(self.num_slots, np.int32)
        self._dev_state = None   # device handles of the step state
        #   (None: the next dispatch uploads the mirrors whole)
        self._dirty_slots = set()  # slots whose device lanes are stale
        #   vs the mirrors: patched before the next dispatch
        self._first_pending = []  # prefills' first tokens not yet
        #   picked: (slot, request, logits handle); the next state sync
        #   queues their pick on the device (_pick_first_tokens)...
        self._first_picked = []   # ...and these wait to be read, once
        #   the decode behind them is queued: (slot, request, id handle)
        self._stats_pending = []  # (tick, handle): chunk programs'
        #   counter vectors, read with the first consumed tick that was
        #   dispatched after them (ready by then: one device, in order)
        self._ring = []  # dispatched-but-unconsumed ticks, oldest
        #   first (async_depth > 1); recovery and shutdown clear it —
        #   the dropped handles die with the rebuilt pools

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, eos_token_id=None,
               timeout=None, temperature=1.0, top_k=0, top_p=1.0,
               seed=None, priority=0, tenant=None, adapter=None):
        """Queue one generation request; returns its Request handle
        (block on ``request.result()``).

        ``priority``: higher-priority requests are served first and
        may PREEMPT running lower-priority streams under slot/KV
        pressure (``Engine(preemption=...)``).  ``tenant``: the
        weighted-fair / rate-limit accounting bucket
        (``Engine(tenants=...)``); None = the default tenant.

        Overload shedding happens HERE, at the edge: ``QueueFull``
        (queue at max_queue), ``RateLimited`` (tenant bucket empty),
        and ``DeadlineShed`` (the measured drain rate says the
        deadline is already unmeetable) all carry an honest
        ``retry_after`` estimate."""
        if self._draining:
            raise QueueFull(
                "engine draining: stop(drain=True) in progress — no "
                "new admissions", retry_after=None)
        if temperature <= 0:
            raise ValueError(
                f"temperature must be > 0, got {temperature} (greedy is "
                "the default when no sampling params are set)")
        if top_p <= 0 or top_p > 1:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        # coerce in the CALLER's thread: a bad eos/seed must fail this
        # submit, not crash the shared engine loop mid-decode
        try:
            eos_token_id = None if eos_token_id is None \
                else int(eos_token_id)
            seed = None if seed is None else int(seed)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"eos_token_id/seed must be ints or None: {e}") from None
        if seed is not None and not 0 <= seed < MAX_SEED:
            raise ValueError(
                f"seed must be in [0, 2**63), got {seed}: the device "
                "sampling key derivation packs the seed into two "
                "32-bit words")
        if adapter is not None:
            if self.adapters is None:
                raise UnknownAdapter(
                    f"adapter {adapter!r} requested but this engine "
                    "serves none (Engine(adapters=... / "
                    "max_adapters=N))")
            adapter = str(adapter)
            self.adapters.lane(adapter)  # raises UnknownAdapter now,
            #   in the caller's thread, instead of failing mid-admit
        req = Request(prompt, max_new_tokens, eos_token_id=eos_token_id,
                      timeout=timeout, temperature=temperature,
                      top_k=top_k, top_p=top_p, seed=seed,
                      priority=priority, tenant=tenant, adapter=adapter)
        self._refuse_unsupported(self._serving_spec,
                                 dict(sampling=req.do_sample))
        total = len(req.prompt) + req.max_new_tokens
        margin = self._spec_k or 0
        if total + margin > self.max_seq_len:
            spec_note = (f" + spec_k ({margin}) speculative window "
                         "margin" if margin else "")
            raise ValueError(
                f"prompt ({len(req.prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}){spec_note} = {total + margin} "
                f"exceeds the slot cache length ({self.max_seq_len})")
        # per-tenant token bucket: sustained over-rate traffic is
        # turned away before it can occupy queue places
        bucket = self._buckets.get(req.tenant)
        if bucket is not None:
            if req.cost_tokens > bucket.burst:
                # no amount of waiting admits a request larger than
                # the bucket itself — a finite Retry-After here would
                # be a lie that livelocks a well-behaved client
                self._m_shed_rate.inc()
                self.tracer.instant(
                    "req.shed", cat="request", req=req.id,
                    reason="rate_limited", tenant=req.tenant)
                raise RateLimited(
                    f"request {req.id} costs {req.cost_tokens} tokens"
                    f" but tenant {req.tenant!r}'s bucket holds at "
                    f"most {bucket.burst:g} — it can never be "
                    "admitted under this rate limit (split the "
                    "request or raise the tenant's burst)",
                    retry_after=None)
            wait = bucket.take(req.cost_tokens)
            if wait is not None:
                self._m_shed_rate.inc()
                self.tracer.instant(
                    "req.shed", cat="request", req=req.id,
                    reason="rate_limited", tenant=req.tenant,
                    retry_after_s=round(wait, 3))
                raise RateLimited(
                    f"tenant {req.tenant!r} over its token rate "
                    f"({self._tenant_policies[req.tenant].rate:g} "
                    f"tok/s): request {req.id} needs "
                    f"{req.cost_tokens} tokens, bucket refills in "
                    f"{wait:.2f}s", retry_after=round(wait, 3))
        # deadline-aware shedding: once the drain rate is measured, a
        # request whose wait estimate already blows its deadline is
        # rejected NOW with the honest backoff, instead of timing out
        # in queue (or worse, decoding for a caller that gave up)
        if self._shed_deadlines and req.deadline is not None:
            est = self.estimate_queue_wait(priority=req.priority)
            budget = req.deadline - req.submitted_at
            if est is not None and est > budget:
                if bucket is not None:
                    bucket.refund(req.cost_tokens)  # did no work —
                    #   a shed must not also drain the rate budget
                retry = round(est - budget, 3)
                self._m_shed_deadline.inc()
                self.tracer.instant(
                    "req.shed", cat="request", req=req.id,
                    reason="deadline", est_wait_s=round(est, 3),
                    retry_after_s=retry)
                raise DeadlineShed(
                    f"request {req.id} cannot meet its {budget:.2f}s "
                    f"deadline: estimated queue wait {est:.2f}s at "
                    "the measured drain rate; retry after "
                    f"{retry:.2f}s", retry_after=retry)
        # instant BEFORE put: once the request is in the queue the
        # engine thread may admit (even first-token) it concurrently,
        # and the ts-sorted timeline must keep queued -> admitted order
        self.tracer.instant("req.queued", cat="request", req=req.id,
                            prompt=int(len(req.prompt)),
                            max_new=req.max_new_tokens,
                            priority=req.priority, tenant=req.tenant,
                            adapter=req.adapter)
        if adapter is not None:
            # pin AFTER every shed check — a shed submit must not
            # leak a lane reference.  The pin drops via the request's
            # finish callback; every terminal path (evict, queue
            # timeout/expire, drain, Migrated) runs _finish.
            req._adapter_id = self.adapters.pin(adapter)
            req._finish_cbs.append(
                lambda _r, _n=adapter: self.adapters.unpin(_n))
        try:
            self.queue.put(req)
        except QueueFull as e:
            if adapter is not None:
                self.adapters.unpin(adapter)  # never queued — the
                #   finish callback will not run
            if bucket is not None:
                bucket.refund(req.cost_tokens)  # see deadline shed
            self._m_shed_queue.inc()
            e.retry_after = self._queue_full_retry_after()
            self.tracer.instant("req.shed", cat="request",
                                req=req.id, reason="queue_full",
                                retry_after_s=e.retry_after)
            raise
        self._m_reqs.inc()
        self._m_queue.set(self.queue.depth())
        self._wake.set()  # event-driven wake: an idle loop admits
        #   this request now, not up to a poll interval later
        return req

    # ------------------------------------------------------------------
    def _p_list(self):
        """Parameter arrays in pnames order, snapshotted once — the
        decode tick is per-token hot path, and the ~n_params dict walk
        never changes after init.  Call refresh_params() after mutating
        weights (quantization, checkpoint load) mid-serving."""
        if self._p_arrays is None:
            self._p_arrays = [self._params[k]._data
                              for k in self._pnames]
        return self._p_arrays

    def _b_list(self):
        """Buffer arrays sorted by name — every compiled path here
        (prefill, chunk prefill, slot decode) orders buffers as
        sorted(named_buffers()), so one snapshot serves all three."""
        if self._b_arrays is None:
            bufs = dict(self.model.named_buffers())
            self._b_arrays = [bufs[k]._data for k in sorted(bufs)]
        return self._b_arrays

    def refresh_params(self):
        """Re-snapshot param/buffer handles after external weight
        mutation (the compiled programs themselves are keyed on names
        and dtypes and survive value changes).  Cached prefixes are
        K/V computed under the OLD weights — an adopter would silently
        decode against stale state — so the prefix cache is flushed
        (blocks still referenced by in-flight slots stay alive until
        their eviction)."""
        self._p_arrays = None
        self._b_arrays = None
        if self._paged and self.prefix_cache is not None:
            self.prefix_cache.clear()

    # -- LoRA lane plumbing (serving/lora.py) --------------------------
    def _lora_key(self, key):
        """Extend a compiled-path cache key with the adapter-bank
        geometry.  Adapter IDENTITY is data (the per-slot lane index);
        only n_lanes/r_max — fixed at construction — shape the trace,
        so loading adapter #2, #3, ... never mints a new program."""
        if self.adapters is None:
            return key
        return key + (("lora", self.adapters.n_lanes,
                       self.adapters.r_max),)

    def _lora_args_state(self, st):
        """Trailing ``*lora`` operands of a fused dispatch: the
        device-resident per-slot lane ids plus the two banks.  Empty
        when this engine serves no adapters — adapter-free engines
        trace exactly the programs they always traced."""
        if self.adapters is None:
            return ()
        return (st["aid"], self.adapters.a_bank, self.adapters.b_bank)

    def _lora_args_slot(self, req):
        """B=1 prefill/chunk variant: one slot's lane as a [1] lane
        array (prefill programs are per-request, so the lane rides the
        call instead of the pooled state)."""
        if self.adapters is None:
            return ()
        import jax.numpy as jnp
        return (jnp.asarray([req._adapter_id], jnp.int32),
                self.adapters.a_bank, self.adapters.b_bank)

    # -- overload protection: drain estimate / shedding / faults -------
    # drain-rate staleness horizon: entries older than this are
    # dropped, and a window whose NEWEST entry is older reads None —
    # an idle gap between bursts must not stretch the measured span
    # (rate would collapse by orders of magnitude and every deadline
    # submit after the gap would be spuriously shed)
    _RATE_HORIZON_S = 10.0

    def drain_rate(self, now=None):
        """Measured decode drain rate (tokens/sec) over the recent
        emitting-tick window; None until at least two emitting ticks
        exist inside the staleness horizon.  The denominator of every
        Retry-After the engine computes — honest because it is
        measured, not configured."""
        now = time.monotonic() if now is None else now
        with self._ovl_lock:
            snap = list(self._rate_win)
        win = [w for w in snap
               if now - w[0] <= self._RATE_HORIZON_S]
        if len(win) < 2:
            return None
        span = win[-1][0] - win[0][0]
        if span <= 1e-3:
            return None
        # tokens strictly after the window's first stamp, over the
        # stamped span — the first entry only anchors the clock
        return sum(n for _, n in win[1:]) / span

    def estimate_queue_wait(self, priority=0):
        """Seconds until a request submitted NOW at ``priority`` would
        reach a slot, from the measured drain rate: (in-flight
        remaining + queued work at its priority or above) / rate.
        None while the rate is unmeasured (a cold engine never
        sheds); 0.0 when nothing queues ahead AND the request would
        be placed next tick anyway — a free slot exists, or priority
        preemption would evict a lower-priority stream for it — so a
        partially-loaded engine never sheds against work it would not
        actually wait for."""
        rate = self.drain_rate()
        if rate is None or rate <= 0:
            return None
        backlog = self.queue.backlog_tokens(min_priority=priority)
        # snapshot the request refs ONCE: submit() runs on handler
        # threads while the engine thread evicts, so re-reading
        # slot.request after a None-check could observe the eviction
        # mid-expression
        reqs = [r for r in (s.request
                            for s in self.scheduler.busy_slots())
                if r is not None]
        if backlog == 0:
            if len(reqs) < self.num_slots:
                return 0.0
            if self._preemption and any(r.priority < priority
                                        for r in reqs):
                return 0.0
        # with preemption on, strictly-lower-priority in-flight
        # streams are not work this request waits behind — it would
        # evict them — so only same-or-higher-priority remaining
        # counts toward the estimate
        inflight = sum(r.remaining for r in reqs
                       if not self._preemption
                       or r.priority >= priority)
        return (inflight + backlog) / rate

    def _queue_full_retry_after(self):
        """Honest 503 backoff for a full queue: the measured time for
        ONE queue place to drain (total backlog / drain rate, per
        queued request); 1.0s when the rate is still unmeasured."""
        rate = self.drain_rate()
        depth = self.queue.depth()
        if rate is None or rate <= 0 or depth == 0:
            return 1.0
        return round(max(self.queue.backlog_tokens() / rate / depth,
                         0.05), 3)

    def _fault(self, site):
        """Consult the fault injector at a named failure point: a
        no-op (None injector or unscheduled tick) costs one attribute
        read; a scheduled site counts, traces, and performs its
        action (which may raise into the step-failure recovery)."""
        f = self.faults
        if f is not None and f.scheduled(site, self.tick_no):
            self._m_faults.inc()
            self.tracer.instant("fault.injected", cat="fault",
                                site=site, tick=self.tick_no)
            f.fire(site, self.tick_no, self)

    def _preempt_history(self):
        """Locked snapshot of the preemption/requeue ring (handler and
        watchdog threads read it while the engine thread appends)."""
        with self._ovl_lock:
            return list(self._preempt_log)

    @_spanned("post_admit")
    def _post_admit(self, admitted, timed_out, tr):
        """Shared post-admission phase of both tick paths.  Reconciles
        the admitted list against the preemption round — a handler
        thread can land a higher-priority submit in the window between
        the admit and preemption phases, so a slot admitted earlier
        THIS tick may since have been evicted or rebound; keeping one
        entry per still-bound slot is what stops the prefill loop from
        binding a consumed ``_kv_plan`` twice or dereferencing a freed
        slot — then emits the admitted/resumed instants and accounts
        the timeouts.  Returns the reconciled admitted list."""
        uniq = []
        for slot in admitted:
            if slot.request is not None and slot not in uniq:
                uniq.append(slot)
        for slot in uniq:
            req = slot.request
            tr.instant("req.admitted", cat="request",
                       req=req.id, slot=slot.index)
            if req.preemptions:
                self._m_resumed.inc()
                tr.instant("req.resumed", cat="request", req=req.id,
                           slot=slot.index,
                           tokens=len(req.generated))
        if timed_out:
            self._m_timeout.inc(len(timed_out))
            self._m_done.inc(len(timed_out))
            for req in timed_out:
                tr.instant("req.evicted", cat="request", req=req.id,
                           reason="timeout")
        return uniq

    # -- priority preemption -------------------------------------------
    @staticmethod
    def _history(req):
        """Prompt and every token sent so far: the ids whose rows a
        slot holds (the keys of what enters the prefix cache when a
        stream is preempted or ends)."""
        return (np.concatenate([req.prompt,
                                np.asarray(req.generated, np.int32)])
                if req.generated else req.prompt)

    def _preempt(self, slot, tr):
        """Evict a RUNNING request mid-stream under priority pressure
        and requeue it with its emitted tokens preserved.  Paged mode
        first inserts every FULL block of the computed history
        (prompt + emitted-so-far — ``slot.pos`` rows of K/V) into the
        prefix cache, so re-admission adopts the span and the resume
        skips re-prefill; the frozen ``req._ctx`` snapshot is what a
        re-admission prefills.  The resumed stream is token-identical
        to an uninterrupted run: greedy trivially, sampled because
        the device key folds the emitted-token counter (the next draw
        is draw #len(generated) either way).  Caller must have
        DRAINED the async ring: an in-flight lane whose request vanished un-done would
        otherwise raise the consume-side drift check."""
        req = slot.request
        i = slot.index
        ctx = self._history(req)
        if self._paged and self.prefix_cache is not None \
                and not req._adapter_id:
            # slot.pos rows of K/V are computed (decoding slots: the
            # last emitted token's row is pending, exactly pos rows
            # valid; prefilling slots: pos == prefilled) — only full
            # blocks under that bound are adoptable.  Adapter lanes
            # never share: LoRA on out_proj shifts the residual
            # stream, so layers >= 1 K/V depend on the adapter
            n_full = min(slot.pos // self._bs,
                         len(self._slot_blocks[i]))
            if n_full:
                self.prefix_cache.insert(ctx,
                                         self._slot_blocks[i][:n_full])
        plan = getattr(req, "_kv_plan", None)
        if plan is not None:
            # admitted-but-not-yet-prefilled victim (a concurrent
            # higher-priority submit landed between admission and
            # prefill): its gate reservation was never bound to the
            # slot, so return it here — adopted prefix refs fall back
            # to the cache's own, fresh blocks free
            del req._kv_plan
            if self._paged:
                pctx, pfresh, _ = plan
                self.block_pool.decref(pctx + pfresh)
        self.scheduler.release(slot)
        self._release_slot_kv(i)
        self._park_state(i)
        req._ctx = ctx
        req.preemptions += 1
        self.queue.requeue(req)
        self._m_preempt.inc()
        with self._ovl_lock:
            self._preempt_log.append({
                "tick": self.tick_no, "request": req.id, "slot": i,
                "priority": req.priority, "tenant": req.tenant,
                "generated": len(req.generated),
                "preemptions": req.preemptions,
            })
        tr.instant("req.preempted", cat="request", req=req.id,
                   slot=i, tokens=len(req.generated),
                   priority=req.priority)

    @_spanned("preempt")
    def _preempt_round(self, now, tr):
        """Admission-phase preemption loop: while the best queued
        priority outranks a running request and admission is blocked
        — every slot busy, or the paged gate just declined the head
        for lack of blocks — evict the lowest-priority busy slot
        (tie-break: most recently admitted, least sunk work) and
        retry admission.  Returns (admitted_slots, timed_out,
        emitted) — emitted counts tokens from any async-ring drain
        the eviction forced."""
        admitted, timed_out, emitted = [], [], 0
        if not self._preemption or self._draining:
            return admitted, timed_out, emitted
        for _ in range(2 * self.num_slots):
            pri = self.queue.best_priority()
            if pri is None:
                break
            blocked = (self.scheduler.free_count() == 0
                       or (self._paged and self._gate_declined))
            if not blocked:
                break
            victims = [s for s in self.scheduler.busy_slots()
                       if s.request is not None
                       and s.request.priority < pri]
            if not victims:
                break
            victim = min(victims,
                         key=lambda s: (s.request.priority, -s.seq))
            if self._ring:
                # consume in-flight ticks first: the victim's device
                # lane is NOT done, and the consume-side drift check
                # must never see a vanished live request
                emitted += self._drain_ring(tr, "preempt")
                vr = victim.request
                if vr is None or vr.priority >= pri \
                        or self.scheduler.free_count() > 0:
                    # the drain finished the victim — or freed some
                    # OTHER slot: admit into the capacity that now
                    # exists instead of evicting a live stream for
                    # it, then re-probe from the top
                    self._gate_declined = False
                    more, t2 = self.scheduler.admit(
                        now,
                        gate=self._kv_gate if self._paged else None)
                    admitted += more
                    timed_out += t2
                    continue
            self._preempt(victim, tr)
            self._gate_declined = False
            more, t2 = self.scheduler.admit(
                now, gate=self._kv_gate if self._paged else None)
            admitted += more
            timed_out += t2
        return admitted, timed_out, emitted

    # -- KV block migration --------------------------------------------
    # A migration is a block-table rewrite plus a bytes transfer: the
    # source gathers its slot's FULL blocks device->host
    # (kvcache.export_blocks), tears the slot down exactly like a
    # preemption (prefix insert, release, park) but finishes the
    # request with ``Migrated`` instead of requeueing it, and the
    # resume snapshot (prompt, emitted tokens, sampling params, the
    # EFFECTIVE seed) rides alongside the bytes.  The
    # destination scatters the blocks into its own pool
    # (kvcache.import_blocks), registers them under its prefix trie,
    # and queues an equivalent Request — whose normal admission
    # prefix-matches the adopted blocks and binds the sample state at
    # fold-counter len(generated), i.e. the stream resumes through the
    # SAME proven preemption-resume path, token-identically.
    #
    # All four public entry points (migrate_out / migrate_in /
    # export_prefix / import_prefix) are thread-safe: they register a
    # _MigrateDemand and the ENGINE THREAD services it at the next
    # tick boundary (``_service_migrations``), after draining any
    # in-flight async ring — pool and slot state stay single-writer.
    # Fault sites: ``migrate_export`` declines an export with the
    # stream untouched, ``migrate_import`` rolls the destination's
    # fresh allocation back to refcount 0 (it adopts nothing), and
    # ``migrate_wire`` is thrown by transports between the two.

    def _register_demand(self, demand):
        if demand.kind in ("out", "in", "prefix_out", "prefix_in"):
            self._refuse_unsupported(self._serving_spec,
                                     {"migration": True})
        with self._mig_lock:
            self._migrate_demands.append(demand)
        self._wake.set()
        return demand

    def _migrate_pending(self):
        with self._mig_lock:
            return len(self._migrate_demands)

    def _migrate_actionable(self):
        """True when a registered demand can make progress on the next
        tick — the idle loop's wake condition.  A waiting export (no
        eligible victim yet) is excluded: whatever makes it actionable
        (a submit, an import) wakes the loop itself."""
        with self._mig_lock:
            return any(not d.waiting for d in self._migrate_demands)

    def _migration_history(self):
        """Locked snapshot of the migration ring (handler threads read
        it for ``/debug/requests`` while the engine thread appends)."""
        with self._mig_lock:
            return list(self._migration_log)

    def _await_demand(self, d, wait, timeout):
        if not wait:
            return d
        try:
            return d.wait(timeout)
        except TimeoutError:
            # withdraw the order if the engine has not yet picked it
            # up; if servicing already started the verdict lands in
            # the demand unobserved — an exported stream's payload
            # still reaches its waiter via Migrated.emitted salvage
            with self._mig_lock:
                if d in self._migrate_demands:
                    self._migrate_demands.remove(d)
            raise

    def live_request_ids(self):
        """Ids of the requests currently BOUND to slots (prefilling
        included), in slot order — the SIGTERM drain's worklist: each
        one is exported to a peer via ``migrate_out(request_id=...)``
        as soon as it is decoding.  Queued-but-unadmitted requests
        are deliberately absent: a draining engine admits nothing, so
        they have emitted nothing and fail over with zero lost work.
        Thread-safe (``busy_slots`` snapshots under the scheduler
        lock)."""
        return [s.request.id for s in self.scheduler.busy_slots()
                if s.request is not None]

    def migrate_out(self, request_id=None, min_tokens=1,
                    deliver="return", wait=True, timeout=30.0):
        """Export a LIVE decoding stream off this engine.  With
        ``request_id=None`` the engine picks a victim (lowest
        priority, most work remaining); otherwise the named request is
        exported once it is decoding with ``min_tokens`` emitted.  The
        stream's waiter unblocks with ``Migrated`` (its ``emitted``
        always carries the tokens generated here).  ``deliver``:

        - ``"return"`` — the migration payload is this call's return
          value (``{"payload": ..., "generated": [...], "completed":
          False}``); the waiter's Migrated carries payload=None.  The
          HTTP export handler path.
        - ``"error"`` — the payload rides INSIDE the waiter's Migrated
          exception and this call returns payload=None; whoever holds
          the stream (the router's generate loop) owns the import.
          Exactly-once by construction: there is a single payload
          holder either way.

        A request that finishes before the export lands returns
        ``{"completed": True, "generated": [...], "payload": None}``.
        A scheduled ``migrate_export`` fault raises here and leaves
        the stream running untouched."""
        if deliver not in ("return", "error"):
            raise ValueError(f"deliver must be 'return' or 'error', "
                             f"got {deliver!r}")
        d = self._register_demand(_MigrateDemand(
            "out", request_id=request_id, min_tokens=int(min_tokens),
            deliver=deliver))
        return self._await_demand(d, wait, timeout)

    def migrate_in(self, payload, wait=True, timeout=30.0):
        """Adopt a migrated stream: scatter its KV blocks into this
        engine's pool + prefix trie (all-or-nothing) and queue an
        equivalent Request that resumes the stream token-identically.
        Accepts either a live payload (``kv["data"]`` an ndarray) or
        the JSON wire form (``kv["data_b64"]`` — decoded here, under
        the ``migrate.wire`` span, so the byte-level transfer cost is
        attributable in traces).  Returns ``{"request": Request,
        "blocks": n}`` — the caller streams ``request.result()`` like
        any submit.  Raises ValueError (geometry mismatch / malformed
        payload), QueueFull (draining or full queue), or an injected
        ``migrate_import`` fault; in every failure the destination
        owns nothing."""
        kv = payload.get("kv") if isinstance(payload, dict) else None
        with self.tracer.span(
                "migrate.wire", cat="serving",
                blocks=int(kv.get("n_blocks") or 0) if kv else 0):
            if not isinstance(payload, dict) \
                    or not isinstance(payload.get("request"), dict):
                raise ValueError(
                    "migration payload must carry a 'request' dict "
                    "(see Engine.migrate_out)")
            if kv is not None and "data_b64" in kv:
                from .kvcache import payload_from_json
                payload = payload_from_json(payload)
                kv = payload.get("kv")
            if not payload["request"].get("prompt"):
                raise ValueError(
                    "migration payload request has no prompt")
            if kv is not None and kv.get("n_blocks") \
                    and kv.get("data") is None:
                raise ValueError(
                    "migration payload kv names n_blocks but carries "
                    "no data")
        d = self._register_demand(_MigrateDemand("in", payload=payload))
        return self._await_demand(d, wait, timeout)

    def export_prefix(self, tokens, wait=True, timeout=30.0):
        """Cross-replica prefix warming, export side: gather the
        longest cached prefix of ``tokens`` from this engine's trie.
        Returns a migration payload with ``request=None`` and a
        ``prefix`` token list (import with ``import_prefix``), or None
        when nothing is cached (or the engine is contiguous/has no
        trie)."""
        d = self._register_demand(_MigrateDemand(
            "prefix_out", tokens=[int(t) for t in tokens]))
        return self._await_demand(d, wait, timeout)

    def import_prefix(self, payload, wait=True, timeout=30.0):
        """Cross-replica prefix warming, import side: adopt a peer
        trie's exported blocks into this engine's prefix cache, so the
        next admission of a prompt sharing that prefix skips its
        prefill.  Returns ``{"blocks": n, "tokens": n*block_size}``
        (zeros when the payload is empty or this engine cannot hold
        it).  Accepts live or JSON wire form, like ``migrate_in``."""
        if payload is None:
            return {"blocks": 0, "tokens": 0}
        kv = payload.get("kv") if isinstance(payload, dict) else None
        with self.tracer.span(
                "migrate.wire", cat="serving",
                blocks=int(kv.get("n_blocks") or 0) if kv else 0):
            if kv is not None and "data_b64" in kv:
                from .kvcache import payload_from_json
                payload = payload_from_json(payload)
        d = self._register_demand(_MigrateDemand(
            "prefix_in", payload=payload))
        return self._await_demand(d, wait, timeout)

    # -- hot adapter load / unload (serving/lora.py) -------------------
    def load_adapter(self, name, adapter, wait=True, timeout=30.0):
        """Hot-load a LoRA adapter under ``name`` while serving.  The
        swap rides the migration-demand machinery: the ENGINE THREAD
        services it at the next tick boundary after draining any
        in-flight async ring, so the bank write is single-writer and
        no dispatched tick straddles it.  Pure data movement — bank
        shapes are fixed at construction, so the compile probe sees
        nothing.  Raises RegistryFull (no free lane), ValueError
        (shape mismatch / duplicate name), or an injected
        ``adapter_load`` fault (banks and inventory untouched)."""
        if self.adapters is None:
            raise RuntimeError(
                "this engine serves no adapters: construct with "
                "Engine(adapters=...) or max_adapters=N to reserve "
                "bank lanes")
        if not isinstance(adapter, LoRAAdapter):
            raise TypeError(
                f"expected LoRAAdapter, got {type(adapter).__name__}")
        d = self._register_demand(_MigrateDemand(
            "adapter_load", name=str(name), adapter=adapter))
        return self._adapter_await(d, wait, timeout)

    def unload_adapter(self, name, wait=True, timeout=30.0):
        """Unload adapter ``name``: refuse (AdapterInUse) while any
        in-flight request pins it, else zero its lane and free it.
        Same tick-boundary servicing as ``load_adapter``."""
        if self.adapters is None:
            raise RuntimeError("this engine serves no adapters")
        d = self._register_demand(_MigrateDemand(
            "adapter_unload", name=str(name)))
        return self._adapter_await(d, wait, timeout)

    def _adapter_await(self, d, wait, timeout):
        t = self._thread
        if t is None or not t.is_alive():
            # no background loop running: service inline on the
            # caller's thread (the single-writer rule holds — nothing
            # else is stepping; synchronous drivers call load/unload
            # between their own step() calls)
            self._service_migrations(self.tracer)
        return self._await_demand(d, wait, timeout)

    def _service_adapter(self, d, tr):
        """Engine-thread half of load/unload_adapter.  Drains the
        async ring first — a dispatched tick read the OLD banks and
        must be consumed against them before the lane flips.  Handles
        its own failure (d.fail) so the drained-token count always
        reaches the tick accounting.  Returns tokens emitted by the
        drain."""
        emitted = self._drain_ring(tr, "adapter") if self._ring else 0
        name = d.args["name"]
        try:
            with tr.span("lora.swap", cat="serving", op=d.kind,
                         adapter=name):
                self._fault("adapter_load")
                if d.kind == "adapter_load":
                    lane = self.adapters.load(name, d.args["adapter"])
                    tr.instant("adapter.loaded", cat="serving",
                               adapter=name, lane=lane)
                else:
                    lane = self.adapters.unload(name)
                    tr.instant("adapter.unloaded", cat="serving",
                               adapter=name, lane=lane)
            d.complete({"name": name, "lane": lane})
        except Exception as e:  # noqa: BLE001 — verdict channel
            d.fail(e)
        return emitted

    def _service_migrations(self, tr):
        """Engine-thread service point, called at the top of both tick
        paths: pop the registered demands, act on each (an "out" whose
        target is not yet exportable waits for a later tick), and
        never let a per-demand failure — injected or organic — escape
        into step recovery.  Returns tokens emitted by any ring drain
        an export forced."""
        with self._mig_lock:
            if not self._migrate_demands:
                return 0
            demands = list(self._migrate_demands)
            self._migrate_demands = []
        emitted = 0
        keep = []
        for d in demands:
            try:
                if d.kind == "out":
                    verdict, n = self._service_migrate_out(d, tr)
                    emitted += n
                    if verdict == "wait":
                        d.waiting = True
                        keep.append(d)
                elif d.kind == "in":
                    self._service_migrate_in(d, tr)
                elif d.kind in ("adapter_load", "adapter_unload"):
                    emitted += self._service_adapter(d, tr)
                elif d.kind == "prefix_out":
                    self._service_prefix_out(d, tr)
                else:
                    self._service_prefix_in(d, tr)
            except Exception as e:  # noqa: BLE001 — verdict channel
                d.fail(e)
        if keep:
            with self._mig_lock:
                # demands registered while servicing appended to the
                # emptied list; waiting orders go back ahead of them
                self._migrate_demands = keep + self._migrate_demands
        return emitted

    def _find_out_candidate(self, d):
        """Resolve an export demand to its current (slot, request).
        Unpinned demands pick a victim among decoding slots meeting
        the min_tokens bar — lowest priority first, then most work
        remaining, then lowest slot index (deterministic under a
        seeded schedule) — and pin the Request HANDLE so later ticks
        track the same stream even across its eviction (a stream that
        finishes before the export lands must resolve as completed,
        not vanish).  Returns (None, req) when the request exists but
        is not in a slot, (None, None) when unknown."""
        req = d.args.get("req")
        if req is not None:
            return self.scheduler.find(req.id), req
        rid = d.args["request_id"]
        if rid is None:
            cands = [s for s in self.scheduler.busy_slots()
                     if s.request is not None and s.decoding
                     and not s.request._adapter_id
                     and len(s.request.generated)
                     >= d.args["min_tokens"]]
            if not cands:
                return None, None
            victim = min(cands, key=lambda s: (s.request.priority,
                                               -s.request.remaining,
                                               s.index))
            d.args["req"] = victim.request
            return victim, victim.request
        slot = self.scheduler.find(rid)
        if slot is not None:
            d.args["req"] = slot.request
            return slot, slot.request
        for r in self.queue.pending():
            if r.id == rid:
                d.args["req"] = r
                return None, r
        return None, None

    @staticmethod
    def _finish_out_done(d, req):
        """The export target reached a terminal state before the
        export landed: a clean finish completes the demand (nothing
        to migrate — the tokens are all here), a failed or
        already-migrated stream fails it with that verdict."""
        if req.error is not None:
            d.fail(req.error)
        else:
            d.complete({"completed": True, "payload": None,
                        "generated": [int(t) for t in req.generated]})

    def _service_migrate_out(self, d, tr):
        """One export attempt.  Returns (verdict, emitted): verdict
        "wait" re-registers the demand for the next tick, "done" has
        completed or failed it."""
        slot, req = self._find_out_candidate(d)
        if req is None:
            if d.args["request_id"] is not None:
                d.fail(KeyError(
                    f"no live request {d.args['request_id']} to "
                    "migrate"))
                return "done", 0
            return "wait", 0  # no eligible victim yet
        if req.done():
            self._finish_out_done(d, req)
            return "done", 0
        if req._adapter_id:
            # the payload format carries no adapter identity — a
            # destination would resume through its BASE lane, silently
            # changing the model mid-stream.  The router's failover
            # path (re-submit prompt+emitted with model=) covers
            # adapter streams instead.
            d.fail(RuntimeError(
                f"request {req.id} decodes through adapter "
                f"{req.adapter!r}: KV migration does not carry "
                "adapter lanes — drain it, or let the caller fail "
                "over with prompt+emitted"))
            return "done", 0
        if slot is None or not slot.decoding \
                or len(req.generated) < d.args["min_tokens"]:
            return "wait", 0
        emitted = 0
        if self._ring:
            # freeze point: the slot's device cursor must be the
            # host-consumed view before its rows are gathered, and the
            # consume-side drift check must never see a vanished live
            # request — same discipline as preemption
            emitted += self._drain_ring(tr, "migrate")
            if req.done():
                self._finish_out_done(d, req)
                return "done", emitted
            slot = self.scheduler.find(req.id)
            if slot is None or not slot.decoding:
                return "wait", emitted
        try:
            self._fault("migrate_export")
        except Exception as e:  # noqa: BLE001 — injected decline
            d.fail(e)  # the stream keeps running on this engine
            return "done", emitted
        payload = self._export_slot(slot, tr,
                                    deliver=d.args["deliver"])
        d.complete({
            "completed": False,
            "generated": list(payload["request"]["generated"]),
            "payload": payload if d.args["deliver"] == "return"
            else None})
        return "done", emitted

    def _export_gather(self, blocks, req=None):
        """``export_blocks`` on this engine's pools, shown on the
        device lane (``dev.gather``): the gather is synchronous, so it
        is complete when the watcher hears of it."""
        t0 = time.perf_counter()
        data = export_blocks(self.k_pools, self.v_pools, blocks)
        self._dev_note("migrate_export", None, role="gather", req=req,
                       t_dispatch=t0)
        return data

    def _export_slot(self, slot, tr, deliver):
        """Freeze + gather + tear down one decoding slot (ring already
        drained, fault site already consulted).  The teardown is
        preemption-shaped — full blocks into the trie (the source
        keeps the warm prefix), release, park — but terminal: the
        waiter unblocks with ``Migrated`` instead of the request
        requeueing."""
        req = slot.request
        i = slot.index
        ctx = (np.concatenate([req.prompt,
                               np.asarray(req.generated, np.int32)])
               if req.generated else req.prompt)
        kv = None
        n_full = 0
        with tr.span("migrate.export", cat="serving", req=req.id) as sp:
            if self._paged:
                # decoding slots hold exactly slot.pos computed rows
                # (the last emitted token's row is pending) — only
                # full blocks under that bound travel; the partial
                # tail is recomputed by the destination's
                # prefix-adoption prefill
                n_full = min(slot.pos // self._bs,
                             len(self._slot_blocks[i]))
                blocks = self._slot_blocks[i][:n_full]
                if n_full:
                    data = self._export_gather(blocks, req.id)
                    kv = dict(self.kv_geometry(),
                              dtype=self._kv_dtype_str,
                              n_blocks=n_full)
                    if self._kv_quant:
                        # quantized export: codes + their per-block
                        # scales travel together
                        kv["data"], kv["scales"] = data
                    else:
                        kv["data"] = data
                if self.prefix_cache is not None and n_full:
                    self.prefix_cache.insert(ctx, blocks)
            payload = {
                "version": 1,
                "request": {
                    "source_id": req.id,
                    "prompt": [int(t) for t in req.prompt],
                    "generated": [int(t) for t in req.generated],
                    "max_new_tokens": req.max_new_tokens,
                    "eos_token_id": req.eos_token_id,
                    "temperature": req.temperature,
                    "top_k": req.top_k, "top_p": req.top_p,
                    # the EFFECTIVE seed: an unseeded sampled stream
                    # defaults to its request id, and the destination
                    # mints a NEW id — carrying the resolved value
                    # keeps the resumed draws identical either way
                    "seed": (int(req.sample_seed) if req.do_sample
                             else req.seed),
                    "priority": req.priority, "tenant": req.tenant,
                    "preemptions": req.preemptions,
                },
                "kv": kv,
            }
            sp.args.update(blocks=n_full, tokens=len(req.generated))
        self.scheduler.release(slot)
        self._release_slot_kv(i)
        self._park_state(i)
        self._m_kv_migrated.inc(n_full)
        self._m_done.inc()  # terminal HERE, like a timeout: keeps
        #   in-flight = submitted - completed consistent per engine
        with self._mig_lock:
            self._migration_log.append({
                "tick": self.tick_no, "dir": "out",
                "request": req.id, "blocks": n_full,
                "tokens": len(req.generated)})
        tr.instant("req.migrated_out", cat="request", req=req.id,
                   blocks=n_full, tokens=len(req.generated))
        req._finish(Migrated(
            f"request {req.id} migrated out after "
            f"{len(req.generated)} token(s)",
            payload=payload if deliver == "error" else None,
            emitted=req.generated))
        return payload

    def _adopt_blocks(self, kv, ctx, tr):
        """Validate + allocate + scatter + trie-adopt a payload's KV
        blocks (engine thread).  Returns the adopted block ids, or []
        when the payload carries none or this engine cannot hold them
        (contiguous layout / no trie — the request still imports
        whole, its admission re-prefills instead of adopting).
        All-or-nothing: a geometry mismatch, a scheduled
        ``migrate_import`` fault, or a scatter failure rolls the
        fresh allocation back to refcount 0 and raises — the
        destination owns nothing."""
        if kv is None or not kv.get("n_blocks"):
            return []
        if not self._paged or self.prefix_cache is None:
            return []
        n = int(kv["n_blocks"])
        # dtype FIRST, as its own machine-readable refusal: int8 codes
        # adopted by an fp engine (or fp rows by a quantized one)
        # would be garbage at best — peers must agree on kv_dtype
        # before geometry even matters
        peer_dtype = str(kv.get("dtype"))
        if peer_dtype != self._kv_dtype_str:
            raise KVDtypeMismatch(
                f"migration payload kv dtype {peer_dtype!r} does not "
                f"match this engine's {self._kv_dtype_str!r}: "
                "adopting nothing (peers must serve the same "
                "kv_dtype)")
        want = self.kv_geometry()
        got = {k: kv.get(k) for k in want}
        if got != want:
            raise ValueError(
                f"migration payload geometry {got} does not match "
                f"this engine ({want}): adopting nothing")
        # adopted blocks land in ONE dp shard (the trie is per-shard
        # so the whole run stays block-local): pick the emptiest
        shard = max(range(self.dp),
                    key=lambda d: self.block_pool.free_count(d))
        short = n - self.block_pool.free_count(shard)
        if short > 0:
            evicted = self.prefix_cache.evict(short, shard=shard)
            if evicted:
                self._m_prefix_evictions.inc(len(evicted))
        blocks = self.block_pool.alloc(n, shard=shard)
        #   may raise NoFreeBlocks
        try:
            self._fault("migrate_import")
            with tr.span("migrate.import", cat="serving", blocks=n):
                self.k_pools, self.v_pools = import_blocks(
                    self.k_pools, self.v_pools, blocks, kv["data"],
                    scales=kv.get("scales"))
            # hand ownership to the trie: insert takes one ref per
            # NEW node, then the alloc ref drops — the blocks are the
            # cache's exactly like a finished request's, and the
            # admission gate's prefix match re-refs them per adopter.
            # (A depth already cached keeps ITS block; ours frees at
            # the decref — same tokens, same content, consistent.)
            self.prefix_cache.insert(ctx, blocks)
        except BaseException:
            self.block_pool.decref(blocks)  # refcount 0, freed
            raise
        self.block_pool.decref(blocks)
        return blocks

    def _service_migrate_in(self, d, tr):
        """Adopt one migrated stream: blocks into pool+trie, then an
        equivalent Request through the normal queue — admission
        prefix-matches the adopted blocks and ``_bind_sample_state``
        rebinds the rng at fold counter len(generated), the proven
        preemption-resume path."""
        if self._draining:
            raise QueueFull("engine is draining: not accepting "
                            "migrations")
        payload = d.args["payload"]
        rq = payload["request"]
        generated = [int(t) for t in rq.get("generated") or []]
        ctx = [int(t) for t in rq["prompt"]] + generated
        blocks = self._adopt_blocks(payload.get("kv"), ctx, tr)
        req = Request(
            rq["prompt"], rq["max_new_tokens"],
            eos_token_id=rq.get("eos_token_id"),
            temperature=rq.get("temperature", 1.0),
            top_k=rq.get("top_k", 0), top_p=rq.get("top_p", 1.0),
            seed=rq.get("seed"), priority=rq.get("priority", 0),
            tenant=rq.get("tenant"))
        req.generated = generated
        req._ctx = np.asarray(ctx, np.int32)
        req.preemptions = int(rq.get("preemptions") or 0) + 1
        #   counts the handoff; admission emits req.resumed for it
        self.queue.put(req)
        self._m_reqs.inc()
        with self._mig_lock:
            self._migration_log.append({
                "tick": self.tick_no, "dir": "in", "request": req.id,
                "source": rq.get("source_id"), "blocks": len(blocks),
                "tokens": len(generated)})
        tr.instant("req.migrated_in", cat="request", req=req.id,
                   source=rq.get("source_id"), blocks=len(blocks),
                   tokens=len(generated))
        d.complete({"request": req, "blocks": len(blocks)})

    def _service_prefix_out(self, d, tr):
        """Prefix-warming export: gather the trie's longest cached
        prefix of the demand's tokens.  Completes with None when
        nothing is cached."""
        tokens = d.args["tokens"]
        if not self._paged or self.prefix_cache is None:
            d.complete(None)
            return
        try:
            self._fault("migrate_export")
        except Exception as e:  # noqa: BLE001 — injected decline
            d.fail(e)
            return
        blocks, m = self.prefix_cache.match(tokens)
        # host-tier continuation: blocks this engine evicted to host
        # RAM still beat the peer's recompute — walk the store for
        # consecutive continuation entries past the device match
        host_parts = []
        if self.host_store is not None:
            from .offload import prefix_key
            i = m // self._bs
            limit = (len(tokens) - 1) // self._bs
            while i < limit:
                ent = self.host_store.get(
                    prefix_key(tokens, (i + 1) * self._bs))
                if ent is None:
                    break
                host_parts.append(ent)
                i += 1
        if not blocks and not host_parts:
            d.complete(None)
            return
        data = scales = None
        if blocks:
            try:
                with tr.span("migrate.export", cat="serving",
                             blocks=len(blocks), prefix=True):
                    data = self._export_gather(blocks)
            finally:
                self.block_pool.decref(blocks)  # drop match's refs
            if self._kv_quant:
                data, scales = data
        if host_parts:
            hd = np.stack([p[0] for p in host_parts], axis=2)
            hs = (np.stack([p[1] for p in host_parts], axis=2)
                  if host_parts[0][1] is not None else None)
            data = (hd if data is None
                    else np.concatenate((data, hd), axis=2))
            if hs is not None:
                scales = (hs if scales is None
                          else np.concatenate((scales, hs), axis=2))
        n_blocks = len(blocks) + len(host_parts)
        m_total = m + len(host_parts) * self._bs
        tier = ("mixed" if blocks and host_parts
                else "host" if host_parts else "device")
        kv = dict(self.kv_geometry(), dtype=self._kv_dtype_str,
                  n_blocks=n_blocks)
        if self._kv_quant:
            kv["data"], kv["scales"] = data, scales
        else:
            kv["data"] = data
        payload = {
            "version": 1, "request": None,
            "prefix": [int(t) for t in tokens[:m_total]],
            "tier": tier,
            "kv": kv}
        self._m_kv_migrated.inc(n_blocks)
        with self._mig_lock:
            self._migration_log.append({
                "tick": self.tick_no, "dir": "prefix_out",
                "blocks": n_blocks, "tokens": m_total,
                "tier": tier})
        d.complete(payload)

    def _service_prefix_in(self, d, tr):
        """Prefix-warming import: adopt a peer trie's blocks.  The
        exported prefix covers exactly n_blocks * block_size tokens,
        so every block registers under the trie."""
        payload = d.args["payload"]
        tokens = [int(t) for t in payload.get("prefix") or []]
        blocks = self._adopt_blocks(payload.get("kv"), tokens, tr)
        if blocks:
            with self._mig_lock:
                self._migration_log.append({
                    "tick": self.tick_no, "dir": "prefix_in",
                    "blocks": len(blocks),
                    "tokens": len(blocks) * self._bs})
            tr.instant("prefix.warmed", cat="serving",
                       blocks=len(blocks))
        d.complete({"blocks": len(blocks),
                    "tokens": len(blocks) * self._bs if blocks else 0})

    # -- host-RAM offload tier (serving/offload.py) ---------------------
    def _offload_demote_hook(self, tokens, block):
        """PrefixCache evict hook: enqueue an async device gather of
        the dying block's rows BEFORE the pool reference drops.  The
        gather is dispatched HERE — jax arrays are immutable and
        device execution is in-order, so the snapshot stays consistent
        even though later dispatches donate the pools — but
        materialized (d2h) at the next tick boundary
        (``_service_offload``), double-buffered behind the next
        dispatch so the engine thread never blocks mid-tick.  A
        scheduled ``offload_demote`` fault, a duplicate content
        address, or any gather failure degrades to the pre-offload
        behavior: the block simply frees, the store sees nothing
        (the trie swallows hook exceptions for the same reason)."""
        if self.host_store is None:
            return
        try:
            self._fault("offload_demote")
        except Exception:
            return  # scheduled demote failure: free without spilling
        from .offload import prefix_key
        key = prefix_key(tokens)
        if key in self.host_store or key in self._offload_pending_keys:
            return  # content-addressed dedup: this prefix is parked
        import jax.numpy as jnp
        ids = jnp.asarray([int(block)], jnp.int32)
        if self._kv_quant:
            data = jnp.stack(
                [jnp.stack((jnp.take(kp.codes, ids, axis=0),
                            jnp.take(vp.codes, ids, axis=0)))
                 for kp, vp in zip(self.k_pools, self.v_pools)])
            scales = jnp.stack(
                [jnp.stack((jnp.take(kp.scale, ids, axis=0),
                            jnp.take(vp.scale, ids, axis=0)))
                 for kp, vp in zip(self.k_pools, self.v_pools)])
        else:
            data = jnp.stack(
                [jnp.stack((jnp.take(kp, ids, axis=0),
                            jnp.take(vp, ids, axis=0)))
                 for kp, vp in zip(self.k_pools, self.v_pools)])
            scales = None
        self._dev_note("offload_demote", data, role="gather")
        self._offload_pending_keys.add(key)
        self._offload_pending.append((key, data, scales))

    def _service_offload(self, tr):
        """Tick-boundary transfer drain: materialize the demote
        gathers the PREVIOUS tick's evictions enqueued and park them
        in the host store.  Runs right after ``_service_migrations``
        in both tick paths — by now the gathers have had a full
        dispatch of device time to complete, so ``np.asarray`` is a
        copy-out, not a stall (the double buffer)."""
        if self.host_store is None or not self._offload_pending:
            return
        pending = self._offload_pending
        self._offload_pending = []
        self._offload_pending_keys = set()
        store = self.host_store
        with tr.span("offload.service", blocks=len(pending)):
            for key, data, scales in pending:
                with tr.span("offload.demote", cat="serving",
                             key=key) as sp:
                    try:
                        d = np.asarray(data)[:, :, 0]
                        s = (np.asarray(scales)[:, :, 0]
                             if scales is not None else None)
                        ok = store.put(key, d, s)
                    except Exception:
                        ok = False  # a dead gather (pools recovered
                        #   mid-flight) must not fail the tick
                    if ok:
                        self._m_offload_demotes.inc()
                    sp.args.update(stored=bool(ok))
            self._m_kv_host_blocks.set(len(store))
            self._m_kv_host_bytes.set(store.bytes_used)

    def _flush_offload(self):
        """Drain pending demotes at loop-idle boundaries
        (``run_until_idle`` exit, the ``start()`` loop's idle branch,
        ``_drain``) — an eviction in the last tick before idle must
        not strand its gather until the next burst of traffic."""
        try:
            self._service_offload(self.tracer)
        except Exception:
            self._offload_pending = []
            self._offload_pending_keys = set()

    def _promote_blocks(self, req, tokens, ctx, m, fresh):
        """Host-tier leg of paged admission: after the device trie
        matched ``m`` tokens, probe the host store for consecutive
        continuation blocks and restore them into the leading
        ``fresh`` reservations — import the payload, seed the device
        trie, and let ``_bind_kv_plan`` count the span exactly like a
        device prefix hit.  Returns the number of promoted blocks; 0
        on miss, scheduled ``offload_promote`` fault, or import
        failure — the fresh blocks then stay plain prefill targets
        (recompute), never half-restored."""
        store = self.host_store
        if store is None or not fresh:
            return 0
        from .offload import prefix_key
        bs = self._bs
        first = m // bs
        limit = (len(tokens) - 1) // bs  # leave >=1 token to prefill
        keys = []
        for i in range(first, min(limit, first + len(fresh))):
            key = prefix_key(tokens, (i + 1) * bs)
            if key not in store:  # presence probe: no LRU touch
                break
            keys.append(key)
        if not keys:
            return 0
        try:
            self._fault("offload_promote")
        except Exception:
            return 0  # scheduled promote failure: fall back to
            #   recompute — the store entry stays, untouched
        datas, scls = [], []
        for key in keys:
            ent = store.get(key)
            if ent is None:
                break  # demote-side LRU raced the probe
            datas.append(ent[0])
            scls.append(ent[1])
        n = len(datas)
        if not n:
            return 0
        blocks = fresh[:n]
        with self.tracer.span("offload.promote", cat="serving",
                              req=req.id, blocks=n) as sp:
            data = np.stack(datas, axis=2)
            scales = (np.stack(scls, axis=2)
                      if scls[0] is not None else None)
            try:
                self.k_pools, self.v_pools = import_blocks(
                    self.k_pools, self.v_pools, blocks, data, scales)
            except Exception:
                return 0  # pools untouched (import is all-or-nothing)
            self.prefix_cache.insert(tokens[:(first + n) * bs],
                                     ctx + blocks)
            sp.args.update(tokens=n * bs)
        self._m_offload_promotes.inc(n)
        self._m_offload_hit_tokens.inc(n * bs)
        req._host_restored = getattr(req, "_host_restored", 0) + n * bs
        self.tracer.instant("req.host_restored", cat="request",
                            req=req.id, blocks=n, tokens=n * bs)
        self._m_kv_host_blocks.set(len(store))
        self._m_kv_host_bytes.set(store.bytes_used)
        return n

    # -- tracing / flight recorder / debug surface ---------------------
    def _dev_note(self, program, handle, batch=0, n=0, req=None,
                  role=None, t_dispatch=None, stats=(), width=None):
        """Hand the device watcher one dispatch the engine thread just
        made.  ``program`` is the ``_compile_probe`` kind, ``handle``
        the program's smallest output (never a pool, never donated;
        None = already complete), ``batch`` its decode lanes, ``n``
        the prompt tokens it carried and ``req`` the request when it
        served one.  The span is ``dev.prefill`` when ``n`` >= 1 and
        ``dev.decode`` when 0 — whatever the program, so the names
        hold for the XLA paths (separate programs) and the ragged
        window (one program) alike; ``role="gather"`` marks a KV
        copy-out that shares the stream.  ``t_dispatch`` defaults to
        now, the dispatch call having just returned.  A no-op with
        ``tracing=False``."""
        q = self._dev_q
        if q is None:
            return
        if t_dispatch is None:
            t_dispatch = time.perf_counter()
        if self._dev_thread is None:
            self._start_watcher()
        if role is None:
            role = "prefill" if n else "decode"
        args = {"program": program, "tick": self.tick_no,
                "batch": batch, "n": n}
        if req is not None:
            args["req"] = req
        if width is not None:
            args["width"] = width  # rows a lane (StepSpec.rows)
        if stats:
            # the program's counter vector: the watcher reads it once
            # the program is done and names the span's arguments
            args["_stats"] = (stats[0], [
                a for _, a in self._serving_spec.counters])
        q.put(("dev." + role, t_dispatch, handle, args))

    def _start_watcher(self):
        q = self._dev_q
        t = threading.Thread(
            target=_watch_device, daemon=True,
            args=(q, self.tracer, self._m_dev_busy,
                  self._m_dev_watch_cpu),
            name="paddle_tpu-serving-device")
        t.start()
        self._dev_thread = t
        # an engine dropped without stop() takes its watcher with it
        self._dev_finalizer = weakref.finalize(self, q.put, None)

    def _stop_watcher(self, timeout):
        """End the watcher after it has seen every dispatch handed to
        it so far (``stop()``); the next dispatch starts a new one."""
        t = self._dev_thread
        if t is None:
            return
        self._dev_finalizer.detach()
        self._dev_q.put(None)
        t.join(timeout)
        if t.is_alive():
            # wedged on a dispatch that never completes: leave it its
            # queue (the sentinel is in it) and start over
            self._dev_q = queue.SimpleQueue()
        self._dev_thread = None

    def _register_compile_listener(self):
        """Subscribe this engine to the model's compile events
        (idempotent).  ``stop()`` unsubscribes — a stopped engine must
        not keep counting sibling engines' compiles into its registry
        — and ``start()`` re-subscribes for the restart path; the
        weakref inside the callback still covers engines discarded
        without a stop()."""
        if self._compile_cb_active:
            return
        add = getattr(self.model, "add_compile_listener", None)
        if add is not None:
            add(self._compile_cb)
            self._compile_cb_active = True

    def _unregister_compile_listener(self):
        if not self._compile_cb_active:
            return
        remove = getattr(self.model, "remove_compile_listener", None)
        if remove is not None:
            remove(self._compile_cb)
        self._compile_cb_active = False

    def _on_compile(self, kind, key, wall_s):
        """Compile-event hook (models/gpt.py ``add_compile_listener``):
        count it, histogram the wall time, and back-date a trace span
        over the compile so it nests inside whatever engine phase
        triggered it."""
        self._m_compiles.inc()
        self._m_compile_ms.observe(wall_s * 1e3)
        self._m_compile_wall.inc(wall_s * 1e3)
        # keep only the scalar fields of the program cache key — it
        # embeds the full parameter-name tuple, useless in a trace
        brief = ([x for x in key
                  if isinstance(x, (int, float, str, bool))]
                 if isinstance(key, tuple) else [str(key)])
        self.tracer.emit(
            f"compile:{kind}", time.perf_counter() - wall_s, wall_s,
            cat="compile",
            args={"key": brief, "wall_ms": round(wall_s * 1e3, 3)})

    def chrome_trace(self):
        """Current trace ring as a Catapult JSON dict (chrome://tracing
        / Perfetto); served by ``/debug/trace``."""
        return self.tracer.chrome_trace(
            process_name=f"paddle_tpu-serving pid={os.getpid()}")

    def streams_active(self):
        """Live TokenStream sinks across slot-bound + queued requests
        — the /healthz streaming-load signal (cheap: two locked
        snapshots, no device work)."""
        n = 0
        for s in self.scheduler.busy_slots():
            if s.request is not None:
                n += len(s.request._sinks)
        for r in self.queue.pending():
            n += len(r._sinks)
        return n

    def debug_requests(self):
        """In-flight slot/request states + queued requests as plain
        JSON-able dicts — the ``/debug/requests`` payload and the
        flight recorder's context block.  Readable from any thread
        while the engine decodes (one locked scheduler pass; the
        request fields it reads are single-writer ints)."""
        now = time.monotonic()
        # which un-consumed dispatch does each slot's DEVICE cursor
        # belong to?  (the newest in-flight tick containing the slot;
        # None = the host-consumed view is current)
        ring = list(self._ring)
        cursor_tick = {}
        for inf in ring:  # oldest -> newest, so the newest wins
            for s in inf.slots:
                cursor_tick[s.index] = inf.tick
        slots = []
        streams_active = 0
        for view in self.scheduler.debug_view():
            view["cursor_tick"] = cursor_tick.get(view["slot"])
            req = view.pop("request")
            if req is not None:
                view["request_id"] = req.id
                view["prompt_len"] = int(len(req.prompt))
                view["generated"] = len(req.generated)
                view["max_new_tokens"] = req.max_new_tokens
                view["do_sample"] = bool(req.do_sample)
                view["first_token"] = req.first_token_at is not None
                view["age_ms"] = round((now - req.submitted_at) * 1e3,
                                       3)
                view["preemptions"] = req.preemptions
                view["adapter"] = req.adapter
                view["streams"] = len(req._sinks)
                view["restored_from_host"] = getattr(
                    req, "_host_restored", 0)  # tokens whose prefill
                #   a host-tier promote skipped (0 = never restored)
                streams_active += len(req._sinks)
            if self._paged:
                view["kv_blocks"] = len(self._slot_blocks[view["slot"]])
            slots.append(view)
        queued = []
        for r in self.queue.pending():
            streams_active += len(r._sinks)
            queued.append({
                "request_id": r.id, "prompt_len": int(len(r.prompt)),
                "max_new_tokens": r.max_new_tokens,
                "priority": r.priority, "tenant": r.tenant,
                "preemptions": r.preemptions, "adapter": r.adapter,
                "queued_ms": round((now - r.submitted_at) * 1e3, 3),
                "deadline_in_s": (None if r.deadline is None
                                  else round(r.deadline - now, 3)),
            })
        return {
            "tick": self.tick_no, "slots": slots, "queue": queued,
            "streams_active": streams_active,
            "in_flight_ticks": [inf.tick for inf in ring],
            "preemptions": self._preempt_history()[-16:],
            "migrations": self._migration_history()[-16:],
            "migrations_pending": self._migrate_pending(),
            "offload": (None if self.host_store is None
                        else self.host_store.stats()),
            "engine": {
                **self.placement,
                "num_slots": self.num_slots,
                "max_seq_len": self.max_seq_len,
                "layout": "paged" if self._paged else "contiguous",
                "prefill_chunk": self._chunk,
                "spec_k": self._spec_k,
                "attn_impl": self.attn_impl,
                "attn_core": self._attn_core,
                "max_context_len": self._max_context_len,
                "mesh_shape": self.mesh_axes,
                "mp": self.mp,
                "dp": self.dp,
                "kv_block_bytes_per_shard":
                    self._kv_block_bytes_per_shard,
                "weight_dtype": self._weight_dtype_str,
                "kv_dtype": self._kv_dtype_str,
                "kv_block_bytes": self._kv_code_bytes_per_shard,
                "kv_scale_bytes": self._kv_scale_bytes_per_shard,
                "kv_row_bytes": self._m_kv_row_bytes.value,
                "kv_geometry": self.kv_geometry(),
                "kernels": self._serving_spec.kernels,
                "step": self.step_report(),
                "residual": self._serving_spec.residual,
                "attention": self._serving_spec.attention,
                "experts": self._serving_spec.experts,
                "layer_state": self._serving_spec.state,
                "async_depth": self.async_depth,
                "tracing": bool(self.tracer.enabled),
                "preemption": self._preemption,
                "draining": self._draining,
                "watchdog_s": self.watchdog_s,
                "adapters_loaded": (0 if self.adapters is None
                                    else len(self.adapters)),
                "adapters": (None if self.adapters is None
                             else self.adapters.describe()),
            }}

    def _record_flight(self, exc):
        """Flight recorder: snapshot the trace ring + in-flight
        request states at the moment of a step failure, BEFORE
        recovery tears the slots down.  Always lands on
        ``self.last_flight``; additionally written to ``flight_dir``
        as chrome-trace JSON when configured.  Must never mask the
        real failure, so it swallows its own errors."""
        try:
            trace = self.chrome_trace()
            trace["metadata"] = {
                "flight-recorder": {
                    "error": repr(exc),
                    "tick": self.tick_no,
                    "dumped_at_unix": round(time.time(), 3),
                    "requests": self.debug_requests(),
                    # preemption/requeue history: WHY slots were
                    # evicted in the ticks leading up to the failure
                    "preemptions": self._preempt_history(),
                    # async pipeline state at the failure: BOTH cursor
                    # buffers — the host mirrors (the "next" buffer
                    # admissions/evictions dirty) and, per un-consumed
                    # in-flight tick, the buffer its dispatch chained
                    # from — plus the futures' metadata, all captured
                    # BEFORE recovery evicts and rebuilds
                    "async": {
                        "async_depth": self.async_depth,
                        "dirty_slots": sorted(self._dirty_slots),
                        "in_flight": [inf.meta()
                                      for inf in list(self._ring)],
                        "next_buffer": {
                            "pos": self._pos.tolist(),
                            "cur_tok": self._cur_tok[:, 0].tolist(),
                            "rem": self._rem.tolist(),
                            "eos": self._eos.tolist(),
                            "ctr": self._sctr.tolist(),
                        },
                    },
                }}
            self.last_flight = trace
            if self._flight_dir:
                os.makedirs(self._flight_dir, exist_ok=True)
                path = os.path.join(
                    self._flight_dir,
                    f"flight_tick{self.tick_no}_{os.getpid()}_"
                    f"{int(time.time() * 1e3)}.json")
                with open(path, "w") as f:
                    json.dump(trace, f)
                self.last_flight_path = path
        except Exception:
            pass

    # -- paged KV cache (serving/kvcache.py) ---------------------------
    def _kv_gate(self, req, slot):
        """Paged admission gate — the scheduler consults it before
        binding a slot.  Matches the prompt against the prefix cache
        (adopting the shared span's blocks), then reserves every block
        the request could need UP FRONT, so decode never allocates and
        a running request can never die of pool pressure mid-stream.
        Under pressure, LRU-evicts unreferenced cached prefixes; if the
        pool still cannot cover the non-shared span, returns False and
        the request waits at the queue head.

        Data-parallel meshes: every lookup/eviction/reservation here
        is scoped to the BINDING SLOT's dp shard — the slot can only
        gather rows inside its own shard's pool range, so a prefix
        cached by another shard is invisible to it and the blocks
        must come from its own range.

        Speculative decoding widens the worst case by ``spec_k``: the
        verify window writes rejected-lane K/V up to spec_k positions
        past the cursor, and reserving those rows HERE is what makes
        rollback a cursor reset instead of a pool operation — every
        window position lands in blocks the slot already owns.

        Resume-aware: a preempted request's ``context`` is its frozen
        prompt+emitted snapshot and ``remaining`` its unemitted
        budget, so the worst case is the same total the original
        admission reserved — and the blocks the preemption returned
        to the prefix cache match here, which is what makes resume a
        cursor-and-refcount operation instead of a re-prefill."""
        tokens = req.context
        shard = self._slot_shard(slot.index)
        s = len(tokens)
        end = s + req.remaining + (self._spec_k or 0)
        if self._step is not None:
            # a step of several rows writes the whole of its last group
            end += -end % self._step.align
        n_total = -(-end // self._bs)
        ctx, m = ([], 0)
        if self.prefix_cache is not None and not req._adapter_id:
            # adapter lanes never share cached K/V: LoRA on out_proj
            # shifts the residual stream, so layers >= 1 K/V depend
            # on the adapter — a base-lane prefix would be wrong.
            # (A prefill that yields no token needs no position left
            # to run: every whole block may be adopted.)
            ctx, m = self.prefix_cache.match(
                tokens, shard=shard, keep=0 if self._step else 1)
        need = n_total - len(ctx)
        short = need - self.block_pool.free_count(shard)
        if short > 0 and self.prefix_cache is not None:
            evicted = self.prefix_cache.evict(short, shard=shard)
            if evicted:
                self._m_prefix_evictions.inc(len(evicted))
        if need > self.block_pool.free_count(shard):
            self.block_pool.decref(ctx)  # the cache keeps its own refs
            self._gate_declined = True   # preemption probe: the head
            #   is being held back by blocks, not by slots
            return False
        fresh = self.block_pool.alloc(need, shard=shard)
        if self.host_store is not None and not req._adapter_id:
            # second tier: the device trie answered first, the host
            # store restores the consecutive continuation (if any)
            # into the leading fresh blocks
            n_promo = self._promote_blocks(req, tokens, ctx, m, fresh)
            if n_promo:
                ctx = ctx + fresh[:n_promo]
                fresh = fresh[n_promo:]
                m += n_promo * self._bs
        req._kv_plan = (ctx, fresh, m)
        return True

    def _release_slot_kv(self, i):
        """Return slot i's block references (eviction path): cached
        prefix blocks fall back to the cache's reference and stay
        resident; decode-span blocks free.

        Decodes already queued may still write one row each into these
        blocks (the slot's lane is parked by a patch queued BEHIND
        them, ``_park_state``), and the pool may hand the blocks to
        this very tick's admission.  That is sound only because one
        device runs its queue in order: the new owner's chunk program
        is queued after those decodes, rewrites every row it reads and
        masks by its own ``pos`` all that lies past it, and a block
        that entered the prefix cache holds whole blocks below ``pos``
        only, which no frozen or dropped lane writes."""
        if not self._paged:
            return
        self.block_pool.decref(self._slot_blocks[i])
        self._slot_blocks[i] = []
        self._block_tables[i, :] = self._slot_scratch[i]

    def _bind_kv_plan(self, slot):
        """Install the admission gate's block reservation
        (``req._kv_plan``) into the slot's table and count the prefix
        hit; returns (ctx, fresh, m).  Shared by the monolithic paged
        prefill and chunked admission."""
        req = slot.request
        ctx, fresh, m = req._kv_plan
        del req._kv_plan
        i = slot.index
        blocks = ctx + fresh
        self._slot_blocks[i] = blocks
        # scratch-padded tail: the pad is the slot's OWN dp shard's
        # scratch row (row 0 at dp == 1)
        row = np.full(self._bps, self._slot_scratch[i], np.int32)
        row[:len(blocks)] = blocks
        self._block_tables[i] = row
        if m:
            self._m_prefix_hits.inc()
            self._m_prefix_hit_tokens.inc(m)
            self.tracer.instant("req.prefix_adopted", cat="request",
                                req=req.id, tokens=m,
                                blocks=len(ctx))
        if self._kv_quant and fresh:
            self._zero_fresh_scales(fresh)
        return ctx, fresh, m

    def _zero_fresh_scales(self, fresh):
        """Zero the SCALE rows of freshly reserved quantized blocks
        (``kv_dtype='int8'``).  A recycled block's stale int8 codes
        would otherwise survive into the touched-block
        read-modify-write's amax recomputation (dequantized garbage
        raising the fresh block's scale); zeroing just the scale row
        nullifies them (``codes * 0 = 0``) without touching the code
        pool — unwritten rows then read exactly 0.0, masked by the
        same causal-position rule that hides fp stale garbage.  The
        index vector is padded to ``_bps`` by REPEATING the first
        fresh block (an idempotent re-zero that stays inside the
        reserving slot's own dp shard — a cross-shard pad row would
        be unaddressable once the tables go data-parallel), so ONE compiled
        program serves every admission regardless of reservation
        size — the no-retracing rule of the paged hot paths."""
        import jax
        import jax.numpy as jnp
        fn = self._zero_scale_fn
        if fn is None:
            def zero(k_pools, v_pools, idx):
                from .quant import QuantKV
                new_k, new_v = [], []
                for kp, vp in zip(k_pools, v_pools):
                    new_k.append(QuantKV(
                        kp.codes, kp.scale.at[idx].set(0.0)))
                    new_v.append(QuantKV(
                        vp.codes, vp.scale.at[idx].set(0.0)))
                return new_k, new_v

            fn = self._zero_scale_fn = jax.jit(
                zero, donate_argnums=(0, 1))
        pad = np.full(self._bps, fresh[0], np.int32)
        pad[:len(fresh)] = fresh
        self.k_pools, self.v_pools = fn(
            self.k_pools, self.v_pools, jnp.asarray(pad))

    def _dequant_span(self, tr, batch):
        """``decode.dequant``: the host-side attribution span of a
        QUANTIZED dispatch, nested inside ``decode.dispatch`` /
        ``decode.ragged_stream``.  The per-block dequant itself runs FUSED
        inside the compiled program (codes x scale adjacent to the
        gather), so there is no separate host phase to time — this
        wraps the same dispatch call and records the worst-case code
        bytes the gather dequantizes (full tables), making quantized
        dispatches distinguishable in a trace (``tools/trace_view.py
        --wall`` breaks the span out).  fp engines emit nothing."""
        if not self._kv_quant:
            import contextlib
            return contextlib.nullcontext()
        return tr.span(
            "decode.dequant", cat="serving", batch=batch,
            code_bytes=batch * self._bps
            * (self._kv_code_bytes_per_shard or 0))

    # -- per-slot sampling lanes ---------------------------------------
    def _bind_sample_state(self, slot):
        """Install the admitted request's sampling lane into the state
        mirrors (admission): temperature 0 marks a greedy lane, the
        seed words feed the on-device key derivation, and the rng
        counter restarts at 0 — so two engines given the same seed
        emit the same sampled tokens.  The slot is marked dirty: its
        lanes (and the table row ``_bind_kv_plan`` installs) reach the
        device as a patch queued before the next dispatch.

        A GREEDY request's lane binds CONSTANT zero seed words, not
        its id-derived default seed: its draw is discarded (argmax),
        but under the rbg PRNG — this repo's TPU-native default — a
        vmapped categorical's bits depend on the WHOLE key batch, so
        an unstable junk key (request ids are a process-global
        counter) would perturb the *seeded neighbors'* streams and
        break their reproduce-across-restarts contract whenever a
        greedy request shared the batch."""
        req = slot.request
        i = slot.index
        if req.do_sample:
            self._temp[i] = req.temperature
            self._topk[i] = req.top_k
            self._topp[i] = req.top_p
            lo, hi = req.seed_words()
        else:
            self._temp[i] = 0.0
            self._topk[i] = 0
            self._topp[i] = 1.0
            lo, hi = 0, 0
        self._seed_lo[i] = lo
        self._seed_hi[i] = hi
        # rng fold counter = tokens already emitted: 0 on a fresh
        # admission, len(generated) on a preemption resume — so the
        # next device draw is draw #len(generated) either way and a
        # seeded stream is unchanged across a preemption
        self._sctr[i] = len(req.generated)
        # device-side stop-condition lanes: the dispatch itself checks
        # EOS / max_new against these, so a blind-dispatched tick can
        # never advance a finished request (resume: only the unemitted
        # budget remains)
        self._eos[i] = (-1 if req.eos_token_id is None
                        else int(req.eos_token_id))
        self._rem[i] = req.remaining
        # LoRA lane: which adapter this slot decodes through (0 =
        # base).  Data like everything else here — never a retrace.
        self._aid[i] = req._adapter_id
        self._dirty_slots.add(i)

    def _park_state(self, i):
        """Park slot i's step + sampling lanes (eviction): frozen
        zeros keep the inactive row's (discarded) compute in-bounds
        and greedy-cheap until the next admission overwrites them.
        The slot is marked dirty, so the zeros (and the scratch row
        ``_release_slot_kv`` left in its table) are patched in behind
        the decodes in flight — a mid-window eviction may have
        advanced the device cursor further than the host consumed;
        what those decodes computed for the lane is dropped at
        consume."""
        self._pos[i] = 0
        self._cur_tok[i] = 0
        self._flags[i] = 0
        self._temp[i] = 0.0
        self._topk[i] = 0
        self._topp[i] = 1.0
        self._seed_lo[i] = 0
        self._seed_hi[i] = 0
        self._sctr[i] = 0
        self._eos[i] = -1
        self._rem[i] = 0  # rem 0 = the device freezes this lane
        self._aid[i] = 0  # parked compute runs the base lane (zeros)
        self._dirty_slots.add(i)

    def _rows_walked(self, width=1):
        """Rows of a slot's table the XLA slot-window attention walks
        in the dispatch about to be issued, the mean over slots, from
        the position mirror: the mirror trails the device by the ticks
        in flight, each of which moved a lane by at most ``width``
        rows.  How far the walk goes is the served model's to say
        (``ServingSpec.decode_rows``), or the kernel's where the
        attention core is a kernel (``ServingSpec.attn_kernel_rows``
        where the model gives one; the dispatch
        is then one of ``serving.attn_kernel_dispatches``).  Counted into
        ``serving.decode_rows_walked`` / ``_live`` / ``_table``;
        returned for the ``decode.dispatch`` span."""
        ahead = width * (1 + len(self._ring))
        walked = (self._kernel_rows or self._serving_spec.decode_rows)(
            self._pos, ahead, self.max_seq_len,
            self._bs if self._paged else None)
        if self._attn_kernel:
            self._m_attn_kernel.inc()
        self._m_rows_walked.inc(walked)
        self._m_rows_live.inc(int(np.minimum(
            self._pos[self._pos > 0] + ahead, self.max_seq_len).sum()))
        self._m_rows_table.inc(self.max_seq_len * self.num_slots)
        return walked // self.num_slots

    def _state_mirrors(self):
        """``(key, mirror)`` of every lane of the step state, in the
        order ``_state_rows`` packs them and the device programs
        unpack them."""
        lanes = [("tok", self._cur_tok), ("pos", self._pos),
                 ("ctr", self._sctr), ("temp", self._temp),
                 ("topk", self._topk), ("topp", self._topp),
                 ("slo", self._seed_lo), ("shi", self._seed_hi),
                 ("eos", self._eos), ("rem", self._rem)]
        if self.adapters is not None:
            lanes.append(("aid", self._aid))
        if self._step is not None:
            lanes.append(("flags", self._flags))
        if self._paged:
            # per-slot scratch block ids (constant per engine config,
            # but rides the state dict so the ragged dispatch
            # signature stays uniform): masked/parked lanes park in
            # their OWN dp shard's scratch row
            lanes += [("tables", self._block_tables),
                      ("scratch", self._slot_scratch)]
        return lanes

    def _state_rows(self, slots):
        """Slots' lanes packed from the mirrors as ONE int32 matrix,
        a row a slot: the slot index, then every lane of
        ``_state_mirrors`` (float32 and uint32 lanes as their bit
        patterns).  A private copy, so a mirror write that lands
        before the (asynchronous) transfer has run cannot reach the
        device."""
        idx = np.asarray(slots, np.int32)
        return np.concatenate(
            [idx[:, None]]
            + [m[idx].view(np.int32).reshape(len(idx), -1)
               for _, m in self._state_mirrors()], axis=1)

    def _state_programs(self):
        """The small programs that keep the device-resident step
        state, built once an engine: ``unpack(rows) -> state`` (the
        whole upload, split on the device), ``patch(state, rows) ->
        state`` (``_PATCH_ROWS`` slots' lanes replaced where they lie;
        the state donated) and ``first[sampled](state, logits, slot)
        -> (state, id)`` (a prefill's first token picked from its
        last-position logits and written into the slot's ``tok``
        lane).  Slots and values are data, never a retrace.  The pick
        is two programs, chosen by the request: the sampling tail
        (two sorts over the vocabulary) takes the TPU's compiler
        ~20 s, which an engine that serves greedy requests never
        pays; like ``sample_rows`` before it, the sampled one is
        compiled by the first sampled request.  Under a mesh all
        return the state's own shardings, so no dispatch re-shards."""
        if self._state_fns is not None:
            return self._state_fns
        import jax
        import jax.numpy as jnp
        from ..models.gpt import sample_rows
        fields = [(key, m[0].size, m.dtype, m.ndim)
                  for key, m in self._state_mirrors()]

        def lanes(rows):
            out, o = {}, 1
            for key, width, dtype, ndim in fields:
                col = rows[:, o:o + width]
                o += width
                if dtype != np.int32:
                    col = jax.lax.bitcast_convert_type(col, dtype)
                out[key] = col if ndim == 2 else col[:, 0]
            return out

        def patch(state, rows):
            idx = rows[:, 0]
            return {k: state[k].at[idx].set(v)
                    for k, v in lanes(rows).items()}

        kw_state, kw_first = {}, {}
        if self._repl_sharding is not None:
            repl = self._repl_sharding
            sh = {f[0]: self._state_sharding or repl for f in fields}
            kw_state = dict(out_shardings=sh)
            kw_first = dict(out_shardings=(sh, repl))

        def pick(sampled):
            def first(state, logits, slot):
                # argmax for a greedy request (the first maximum, as
                # np.argmax), else the draw every other path makes:
                # the lane's filters and fold(request_key, ctr) with
                # the counter of the token BEING picked (the patch
                # ahead of this program already counts it)
                def lane(k):
                    return jax.lax.dynamic_slice(state[k], (slot,), (1,))
                row = logits.astype(jnp.float32).reshape(1, -1)
                ids = (sample_rows(row, lane("temp"), lane("topk"),
                                   lane("topp"), lane("slo"),
                                   lane("shi"), lane("ctr") - 1)
                       if sampled else
                       jnp.argmax(row, axis=-1).astype(jnp.int32))
                tok = jax.lax.dynamic_update_slice(
                    state["tok"], ids[:, None], (slot, 0))
                return dict(state, tok=tok), ids
            first.__name__ = first.__qualname__ = (
                "first_token_sampled" if sampled else "first_token")
            return jax.jit(first, donate_argnums=(0,), **kw_first)

        for fn, name in ((lanes, "state_unpack"), (patch, "state_patch")):
            fn.__name__ = fn.__qualname__ = name
        self._state_fns = (
            jax.jit(lanes, **kw_state),
            jax.jit(patch, donate_argnums=(0,), **kw_state),
            {sampled: pick(sampled) for sampled in (False, True)})
        return self._state_fns

    def _push_state(self):
        """Upload the state mirrors whole as the device-resident step
        state: the first dispatch of an engine, and the rebuild after
        ``_reset_pools`` (a failed step) — nothing else.  ONE packed
        transfer (``_state_rows`` of every slot), split into the
        state's arrays on the device.  A steady-state tick reuses the
        handles the last dispatch returned and uploads nothing; a
        dirty slot is patched (``_patch_state``).  The ring is empty
        in both cases, and has to be: the mirrors only reflect
        CONSUMED ticks, so uploading them under an un-consumed
        dispatch would rewind every live slot's device cursor by a
        tick."""
        assert not self._ring, \
            "_push_state with ticks in flight: patch the dirty slots"
        import jax
        unpack, _, _ = self._state_programs()
        rows = self._state_rows(range(self.num_slots))
        # mesh-sharded engine: every [num_slots]-leading cursor
        # row-shards over 'dp' (each dp shard owns ITS slots' cursors
        # and block-table rows; at dp == 1 the spec degenerates to
        # replication over 'mp'), which the unpack program's output
        # shardings say.  The placement is a cross-shard barrier,
        # traced as shard.sync so its cost is visible in
        # trace_view --wall
        sync = (self.tracer.span("shard.sync",
                                 shards=self.mp * self.dp,
                                 mp=self.mp, dp=self.dp)
                if self.mp * self.dp > 1 else nullcontext())
        with self.tracer.span("state.push", cpu=self._phase_cpu,
                              bytes=int(rows.nbytes)), sync:
            if self._repl_sharding is not None:
                rows = jax.device_put(rows, self._repl_sharding)
            self._dev_state = unpack(rows)
        self._dirty_slots.clear()
        self._m_state_pushes.inc()

    def _patch_state(self):
        """Queue the dirty slots' lanes as patch programs behind
        whatever is in flight: each rewrites ``_PATCH_ROWS`` slots'
        entries of the (donated) state from the mirrors and touches
        no other slot, so a live lane's cursor is never rewound and
        nothing has to be consumed first.  One device runs its queue
        in order: a patch queued after decode N and before decode N+1
        is seen by N+1 and not by N, which is what consuming the ring
        and uploading the mirrors gave."""
        _, patch, _ = self._state_programs()
        slots = sorted(self._dirty_slots)
        self._dirty_slots.clear()
        with self.tracer.span("state.patch", cpu=self._phase_cpu,
                              slots=len(slots)) as sp:
            nbytes = 0
            for o in range(0, len(slots), _PATCH_ROWS):
                part = slots[o:o + _PATCH_ROWS]
                part += part[:1] * (_PATCH_ROWS - len(part))
                rows = self._state_rows(part)
                nbytes += int(rows.nbytes)
                self._dev_state = patch(self._dev_state, rows)
                self._m_state_patches.inc()
            sp.args["bytes"] = nbytes

    def _sync_state(self):
        """Bring the device's step state up to the mirrors before a
        dispatch reads it: the whole upload where there is none yet,
        else a patch of the dirty slots; then the first tokens that
        wait to be picked (their lanes patched just now)."""
        if self._dev_state is None:
            self._push_state()
        elif self._dirty_slots:
            self._patch_state()
        if self._first_pending:
            self._pick_first_tokens()

    def _prefill_paged(self, slot):
        """Paged admission prefill: ONE jitted dispatch gathers the
        adopted prefix blocks as attention context, runs the prompt's
        non-shared tail, and scatters the tail's K/V block-granular
        into the slot's fresh blocks — a prefix hit neither recomputes
        nor re-stores the shared span.  The prompt's full blocks are
        then registered in the prefix cache for later adopters."""
        import jax.numpy as jnp
        req = slot.request
        ctx, fresh, m = self._bind_kv_plan(slot)
        blocks = ctx + fresh
        tokens = req.context  # prompt, or the frozen resume snapshot
        s = len(tokens)
        n_ctx = len(ctx)
        s_tail = s - m
        n_tail = -(-s // self._bs) - n_ctx
        pf, _, _ = self.model.serving_program(
            "paged_prefill", self._pnames, self._params,
            self._lora_key(
                (s_tail, n_ctx, n_tail, self._bs, self._kv_dtype_str,
                 tuple(self._pnames), self._bnames_all)),
            s_tail, n_ctx, n_tail, self._bs, self._nh, self._hd,
            self._kv_dtype)
        last0, self.k_pools, self.v_pools = pf(
            self._p_list(), self._b_list(), self.k_pools, self.v_pools,
            tokens[None, m:],
            jnp.asarray(np.asarray(ctx, np.int32)),
            jnp.asarray(np.asarray(fresh[:n_tail], np.int32)),
            *self._lora_args_slot(req))
        self._dev_note(pf.kind, last0, n=s_tail, req=req.id)
        if self.prefix_cache is not None and not req._adapter_id:
            self.prefix_cache.insert(tokens, blocks[:s // self._bs])
        self._m_prefill_tokens.inc(s_tail)
        slot.pos = s
        slot.prefilled = s
        self._queue_first_token(slot, last0)

    def _prefill(self, slot):
        """Admission prefill: one jitted whole-prompt forward (shared
        with ``generate(compiled=...)`` via _compiled_prefill_fn, so the
        math is the compiled path's bit-for-bit),
        padded to the pool's L and written into the slot's cache rows."""
        self._bind_sample_state(slot)
        if self._paged:
            return self._prefill_paged(slot)
        req = slot.request
        tokens = req.context  # prompt, or the frozen resume snapshot
        s = len(tokens)
        L = self.max_seq_len
        pf, _, _ = self.model.serving_program(
            "prefill", self._pnames, self._params,
            self._lora_key(
                (1, s, L, self._kv_dtype_str, tuple(self._pnames),
                 self._bnames_all)),
            1, s, L, self._nh, self._hd, self._kv_dtype)
        last0, k_bufs, v_bufs = pf(self._p_list(), self._b_list(),
                                   tokens[None, :],
                                   *self._lora_args_slot(req))
        self._dev_note(pf.kind, last0, n=s, req=req.id)
        i = slot.index
        if self._insert_fn is None:
            import jax

            def ins(k_pools, v_pools, k_news, v_news, idx):
                # one dispatch writes the slot row into every layer;
                # donated pools update in place instead of 2*n_layers
                # whole-pool copies per admission
                new_k = [jax.lax.dynamic_update_slice(
                    kp, kn.astype(kp.dtype), (idx, 0, 0, 0))
                    for kp, kn in zip(k_pools, k_news)]
                new_v = [jax.lax.dynamic_update_slice(
                    vp, vn.astype(vp.dtype), (idx, 0, 0, 0))
                    for vp, vn in zip(v_pools, v_news)]
                return new_k, new_v

            self._insert_fn = jax.jit(ins, donate_argnums=(0, 1))
        import jax.numpy as jnp
        self.k_pools, self.v_pools = self._insert_fn(
            self.k_pools, self.v_pools, k_bufs, v_bufs,
            jnp.asarray(i, jnp.int32))
        self._m_prefill_tokens.inc(s)
        slot.pos = s
        slot.prefilled = s
        self._queue_first_token(slot, last0)

    # -- budgeted chunked prefill (prefill_chunk=...) ------------------
    def _begin_chunked(self, slot):
        """Chunked admission: bind the paged block plan (the adopted
        prefix span counts as already-prefilled tokens) and park the
        slot PREFILLING — no prompt compute happens at admission;
        ``_prefill_chunked`` spends the tick budget.  The decode
        dispatch's (discarded) compute for a half-prefilled slot is
        parked at the NEXT chunk's start row: its garbage K/V write
        lands on a row that chunk overwrites before any query can see
        it (in paged mode that row always sits in the slot's own fresh
        blocks — the adopted shared blocks all lie before
        ``prefilled``)."""
        i = slot.index
        self._bind_sample_state(slot)
        if self._paged:
            _, _, m = self._bind_kv_plan(slot)
            slot.prefilled = m
        else:
            slot.prefilled = 0
        slot.pos = slot.prefilled
        self._pos[i] = slot.prefilled
        self._cur_tok[i] = 0
        self._flags[i] = 0
        if self._step is not None \
                and slot.prefilled >= self._prefill_target(slot.request):
            # every whole group of the context was adopted: no chunk
            self._open_step(slot)

    def _prefill_target(self, req):
        """Context tokens a prefill covers: all of them, or the whole
        groups of a model whose positions come in groups
        (``StepSpec.align``; the rest opens its first step)."""
        s = len(req.context)
        return s - s % self._step.align if self._step is not None else s

    def _open_step(self, slot):
        """A lane whose prefill yields no token starts stepping: what
        the prefill left of the context stands first in the lane's
        rows (``StepSpec.open``), and the slot counts as prefilled, so
        it is DECODING from the next snapshot on."""
        req, i = slot.request, slot.index
        target = self._prefill_target(req)
        self._cur_tok[i], self._flags[i] = self._step.open(
            req.context[target:])
        slot.pos = target
        slot.prefilled = len(req.context)
        self._pos[i] = target
        self._dirty_slots.add(i)

    def _run_chunk(self, slot, n):
        """One chunk dispatch: compute K/V (and, on the final chunk,
        the first-token logits) for prompt positions
        ``[prefilled, prefilled + n)``.  Returns True while chunks are
        left.  The final chunk's first token is queued, not read
        (``_queue_first_token``); where the first step makes it
        (``StepSpec``) the lane opens its first step."""
        import jax.numpy as jnp
        req = slot.request
        i = slot.index
        tokens = req.context  # prompt, or the frozen resume snapshot
        s = self._prefill_target(req)
        p0 = slot.prefilled
        C = self._chunk
        ids = np.zeros((1, C), np.int32)  # right-padded final chunk
        ids[0, :n] = tokens[p0:p0 + n]
        with self.tracer.span(
                "prefill.chunk", cpu=self._phase_cpu, req=req.id, pos=p0, n=n,
                layout="paged" if self._paged else "contiguous"):
            if self._paged:
                fn, _, _ = self.model.serving_program(
                    "paged_chunk_prefill", self._pnames, self._params,
                    self._lora_key(
                        (C, self._kv_managed + self.dp, self._bs, self._bps,
                         self._kv_dtype_str, tuple(self._pnames),
                         self._bnames_all)))
                last0, self.k_pools, self.v_pools, *stats = fn(
                    self._p_list(), self._b_list(), self.k_pools,
                    self.v_pools, ids,
                    jnp.asarray(self._block_tables[i]),
                    jnp.asarray(p0, jnp.int32),
                    jnp.asarray(n, jnp.int32),
                    jnp.asarray(int(self._slot_scratch[i]), jnp.int32),
                    *self._lora_args_slot(req))
            else:
                fn, _, _ = self.model.serving_program(
                    "chunk_prefill", self._pnames, self._params,
                    self._lora_key(
                        (C, self.num_slots, self.max_seq_len,
                         self._kv_dtype_str, tuple(self._pnames),
                         self._bnames_all)),
                    C, self.max_seq_len, self._nh, self._hd,
                    self._kv_dtype)
                last0, self.k_pools, self.v_pools = fn(
                    self._p_list(), self._b_list(), self.k_pools,
                    self.v_pools, ids, jnp.asarray(i, jnp.int32),
                    jnp.asarray(p0, jnp.int32),
                    jnp.asarray(n, jnp.int32),
                    *self._lora_args_slot(req))
                stats = []
            # a chunk's counters wait for the download of the decode
            # queued behind it (the device runs in order: they are
            # ready by then)
            self._stats_pending += [(self.tick_no, h) for h in stats]
            self._dev_note(fn.kind, last0, n=n, req=req.id, stats=stats)
        slot.prefilled = p0 + n
        slot.pos = slot.prefilled
        self._m_chunks.inc()
        self._m_prefill_tokens.inc(n)
        self._dirty_slots.add(i)  # a patch moves the lane's cursor
        #   before the next fused tick reads it
        if slot.prefilled < s:
            # still PREFILLING: re-park the decode dispatch's garbage
            # write on the next chunk's start row
            self._pos[i] = slot.prefilled
            return True
        # final chunk: the context's full blocks become adoptable and
        # the last real position's logits sample the first token (TTFT
        # on a fresh admission; the NEXT stream token on a resume)
        if self._paged and self.prefix_cache is not None \
                and not req._adapter_id:
            self.prefix_cache.insert(tokens,
                                     self._slot_blocks[i][:s // self._bs])
        if self._step is not None:
            self._open_step(slot)
        else:
            self._queue_first_token(slot, last0)
        return False

    def _prefill_chunked(self, prefilling):
        """Spend at most ``tick_token_budget`` prompt tokens on prefill
        chunks: round-robin over the PREFILLING slots (admission order,
        so partially-prefilled prompts resume before fresh ones start),
        one chunk per slot per pass.  A slot whose final chunk ran is
        DECODING from the next snapshot on and joins this same tick's
        decode dispatch, exactly like monolithic prefill's
        emit-then-decode; its first token is read behind that
        dispatch (``_emit_first_tokens``)."""
        from collections import deque
        budget = self._tick_budget
        queue = deque(prefilling)
        while queue and budget > 0:
            slot = queue[0]
            req = slot.request
            n = min(self._chunk,
                    self._prefill_target(req) - slot.prefilled)
            if n > budget:
                break  # strict per-tick cap (budget >= chunk, so a
                #        tick's FIRST chunk always fits: progress is
                #        guaranteed, the cap only defers later chunks)
            queue.popleft()
            budget -= n
            if self._run_chunk(slot, n):
                queue.append(slot)
        # a prompt the budget left waiting keeps its lane where the
        # last chunk parked it: every decode moves a live lane on by a
        # row, and the garbage row it writes belongs on the NEXT
        # chunk's start row, which that chunk rewrites
        self._dirty_slots.update(s.index for s in queue)

    def _queue_first_token(self, slot, last0):
        """A prefill's (or the final chunk's) last-position logits are
        on their way: queue the request's first token instead of
        waiting for them.  The mirrors take the lane as it stands once
        that token is emitted (cursor on the context's end, the
        counter and the budget moved by one), so the patch before the
        next dispatch carries it, ``_pick_first_tokens`` writes the id
        the device picks into the lane's ``tok``, and the decode that
        follows is queued at once; ``_emit_first_tokens`` reads the id
        (4 bytes, not a ``[V]`` row) behind that dispatch."""
        req, i = slot.request, slot.index
        self._pos[i] = slot.pos
        self._sctr[i] = len(req.generated) + 1
        self._rem[i] = max(req.remaining - 1, 0)
        self._dirty_slots.add(i)
        self._first_pending.append((slot, req, last0))

    def _pick_first_tokens(self):
        """Queue the ``first_token`` program for every first token
        that waits for its pick: argmax for a greedy request, else the
        SAME lane filters and key derivation as the fused dispatches
        (``models.gpt.sample_rows``), read from the slot's own lanes —
        so token i of a request draws from fold(request_key, i)
        whether prefill, a one-token tick, or a verify-window lane
        emitted it, and a seed reproduces across engine restarts."""
        _, _, first = self._state_programs()
        pending, self._first_pending = self._first_pending, []
        for slot, req, last0 in pending:
            with self.tracer.span("state.patch", cpu=self._phase_cpu,
                                  first_token=slot.index):
                self._dev_state, ids = first[req.do_sample](
                    self._dev_state, last0, np.int32(slot.index))
            self._first_picked.append((slot, req, ids))

    def _emit_first_tokens(self):
        """Read and emit the first tokens queued this tick (called with
        the decode behind them already dispatched, or with nothing in
        flight on the synchronous paths, where they are picked here).
        The read waits for the prefill program and whatever was queued
        ahead of it, not for the decode behind it: ``prefill.d2h``,
        counted as time blocked on the device.  A first token that
        ends its request (EOS, ``max_new_tokens`` 1) parks the slot
        like any eviction; the decode lane it cost is dropped at
        consume.  Returns the tokens emitted."""
        if not (self._first_pending or self._first_picked):
            return 0
        with self.tracer.span("first_token", cpu=self._phase_cpu):
            if self._first_pending:
                self._sync_state()
            picked, self._first_picked = self._first_picked, []
            for slot, req, ids in picked:
                with _DeviceWait(self, self.tracer.span(
                        "prefill.d2h", cpu=self._phase_cpu, req=req.id)):
                    tok = int(np.asarray(ids)[0])
                self._emit(slot, tok)
                if slot.request is None:
                    for inf in self._ring:
                        inf.dropped.add(slot.index)
        return len(picked)

    def _emit(self, slot, tok):
        """Record one generated token; finish + evict on EOS or
        max_new_tokens, else arm the slot for the next tick."""
        req = slot.request
        now = time.monotonic()
        if req._sinks:
            # live streaming consumers: fan the token out under the
            # sink lock (exactly-once vs a concurrent attach replay);
            # spanned so trace_view --wall prices the fan-out
            with self.tracer.span("stream.emit", cat="serving",
                                  req=req.id):
                req._emit_token(int(tok))
        else:
            req._emit_token(int(tok))
        if req.first_token_at is None:
            req.first_token_at = now
            self._m_ttft.observe((now - req.submitted_at) * 1e3)
            self.tracer.instant(
                "req.first_token", cat="request", req=req.id,
                ttft_ms=round((now - req.submitted_at) * 1e3, 3))
        self._m_tokens.inc()
        self._m_rate.add(1, now)
        # context high-water mark: prompt + everything decoded so far
        # — the max context length this engine has actually served
        # (reported in /healthz + /debug/requests, copied into the
        # router's probe signals)
        ctx_len = len(req.prompt) + len(req.generated)
        if ctx_len > self._max_context_len:
            self._max_context_len = ctx_len
        finished = (len(req.generated) >= req.max_new_tokens or
                    (req.eos_token_id is not None
                     and int(tok) == int(req.eos_token_id)))
        if finished:
            n_after_first = len(req.generated) - 1
            if n_after_first > 0:
                self._m_tpot.observe(
                    (now - req.first_token_at) / n_after_first * 1e3)
            i = slot.index
            if self._step is not None and self.prefix_cache is not None \
                    and not req._adapter_id:
                # rows below pos are final (StepSpec.align): the whole
                # blocks of prompt + answer below it stay adoptable, so
                # the next turn prefills what the client added and no
                # more.  (The last block of a stream is never
                # committed, so no row the client has not seen is
                # keyed by ids it could send.)
                self.prefix_cache.insert(
                    self._history(req),
                    self._slot_blocks[i][:slot.pos // self._bs])
            self.scheduler.evict(slot)
            self._evicted_in_tick += 1
            self._release_slot_kv(i)
            # park the freed row (frozen pos/tok keeps the inactive
            # row's ignored compute in-bounds until the next prefill
            # overwrites the whole cache row) and dirty the device
            # mirrors
            self._park_state(i)
            self._m_done.inc()
            self.tracer.instant("req.finished", cat="request",
                                req=req.id,
                                tokens=len(req.generated))
            return
        i = slot.index
        if self._step is None:
            self._cur_tok[i, 0] = int(tok)
        self._pos[i] = slot.pos
        self._sctr[i] = len(req.generated)  # rng fold counter mirror
        self._rem[i] = req.max_new_tokens - len(req.generated)
        #   remaining-budget mirror: tracks the device lane exactly
        #   (both decrement once per emitted token), so steady state
        #   needs no re-upload

    def _draft_window(self, active):
        """Gather the speculative verify window: [num_slots, W] tokens
        whose lane 0 is each slot's current token and lanes 1..k are
        the proposer's drafts (pad lanes repeat the current token).
        Sets ``slot.spec_lanes`` per live slot.  Shared by the host
        verify tick and the fused device tick."""
        k = self._spec_k
        W = k + 1
        toks = np.zeros((self.num_slots, W), np.int32)
        toks[:, 0] = self._cur_tok[:, 0]
        for slot in active:
            i = slot.index
            req = slot.request
            # clamp to what the request can still consume: the window
            # emits at most (lanes + 1) tokens before max_new_tokens
            # evicts, so lanes past remaining-1 could never be
            # accepted — proposing them would waste proposer work and
            # permanently deflate the acceptance-rate gauge with
            # request-length effects that say nothing about draft
            # quality (the compiled window shape stays the full W;
            # the tail just rides as pad lanes)
            n_lanes = min(k, req.max_new_tokens - len(req.generated) - 1)
            toks[i, 1:] = toks[i, 0]  # pad lanes: repeat the current
            #   token — window FILLER, never proposals (their garbage
            #   K/V is rewritten before visibility like any rejected
            #   lane, and the accept loop below cannot consume them)
            n_drafted = 0
            if n_lanes > 0:
                history = np.concatenate(
                    [req.prompt, np.asarray(req.generated, np.int32)])
                try:
                    self._fault("spec_draft")
                    d = np.asarray(
                        self.proposer.propose(history, n_lanes),
                        np.int32).reshape(-1)[:n_lanes]
                except Exception as e:
                    # a proposer outage DEGRADES (zero drafts — the
                    # verify window still emits its bonus token, i.e.
                    # plain decode speed) instead of failing the tick
                    # and evicting every in-flight request
                    self._m_proposer_failures.inc()
                    self.tracer.instant(
                        "spec.proposer_failed", cat="serving",
                        req=req.id, error=repr(e))
                    d = np.zeros(0, np.int32)
                toks[i, 1:1 + len(d)] = d
                n_drafted = len(d)
            slot.spec_lanes = n_drafted  # in-flight REAL draft lanes —
            #   what the proposer returned, not what was asked: a
            #   shortfall's pad fill must not count as proposed nor be
            #   consumable as accepted.  (Counted into the proposed
            #   metric only after the dispatch returns: a failed
            #   verify must not deflate the lifetime acceptance-rate
            #   gauge with lanes never scored.)
        return toks

    @_spanned("dispatch", phase=True)
    def _dispatch_spec(self, active, tr):
        """DISPATCH one fused speculative draft-and-verify tick
        without consuming it: the verify dispatch picks every window
        lane's token on device, counts the accepted prefix, AND
        applies the device-side stop condition (EOS / remaining
        budget clamp the emitted window and freeze finished lanes),
        so the un-materialized handles carry picks [B, W] + counts +
        the packed done mask — never the [B, W, V] logits.  Drafting
        stays host-side and data-dependent on the PREVIOUS window's
        accepted tokens, which is why the async loop consumes before
        drafting in spec mode."""
        import jax.numpy as jnp
        W = self._spec_k + 1
        layout = "paged" if self._paged else "contiguous"
        with tr.span("spec.draft", batch=len(active), spec_k=W - 1):
            toks = self._draft_window(active)
        lanes = np.zeros(self.num_slots, np.int32)
        for slot in active:
            lanes[slot.index] = slot.spec_lanes
        self._sync_state()
        st = self._dev_state
        if self._fused_spec_fn is None:
            self._fused_spec_fn, _, _ = \
                self.model.serving_program(
                    "fused_spec_verify", self._pnames, self._params,
                    self._lora_key(
                        ("paged" if self._paged else "slot", W,
                         self.num_slots,
                         (self._kv_managed + self.dp, self._bs) if self._paged
                         else self.max_seq_len, self._kv_dtype_str,
                         tuple(self._pnames), self._bnames_all)),
                    paged=self._paged)
        args = [self._p_list(), self._b_list(), self.k_pools,
                self.v_pools]
        if self._paged:
            args.append(st["tables"])
        args += [jnp.asarray(toks), jnp.asarray(lanes), st["pos"],
                 st["temp"], st["topk"], st["topp"], st["slo"],
                 st["shi"], st["ctr"], st["eos"], st["rem"],
                 *self._lora_args_state(st)]
        self._fault("dispatch")
        with tr.span("decode.dispatch", cpu=self._phase_cpu, batch=len(active),
                     layout=layout, spec_w=W, fused=True,
                     rows=self._rows_walked(W)), \
                self._dequant_span(tr, len(active)):
            (picks, n_acc, n_emit, done, new_tok, new_pos, new_ctr,
             new_rem, self.k_pools, self.v_pools) = \
                self._fused_spec_fn(*args)
        self._dev_note(self._fused_spec_fn.kind, n_emit,
                       batch=len(active))
        st["tok"], st["pos"], st["ctr"], st["rem"] = \
            new_tok, new_pos, new_ctr, new_rem
        self._m_fused_ticks.inc()
        self._m_spec_windows.inc(len(active))
        return _InflightTick(
            self.tick_no, "spec", list(active),
            {"picks": picks, "n_acc": n_acc, "n_emit": n_emit,
             "done": done}, len(active), layout,
            {"pos": self._pos.tolist(), "rem": self._rem.tolist()},
            spec_lanes=[slot.spec_lanes for slot in active])

    def _emit_lane(self, slot, toks, n_emit_dev_i, done_i, tick,
                   advance=1):
        """The one emit loop of every consume path: ``toks`` are the
        tokens the device says this lane yields this tick, in stream
        order; each goes through ``_emit`` (the cursor first moving
        ``advance`` rows: 1 where a token is a cached row, 0 where the
        step program moves ``pos`` itself) until one finishes the
        request.  Host-vs-device stop-condition drift raises into step
        recovery — ONE implementation, so the paths' drift semantics
        cannot desynchronize.  ``n_emit_dev_i`` is the device's count
        (1 where the program yields exactly one token a live lane).
        Returns the tokens emitted."""
        i = slot.index
        n_em = 0
        for tok in toks:
            slot.pos += advance
            self._pos[i] = slot.pos
            self._emit(slot, int(tok))
            n_em += 1
            if slot.request is None:
                break
        if n_em != n_emit_dev_i or done_i != (slot.request is None):
            raise RuntimeError(
                f"async stop-condition drift: slot {i} host "
                f"emitted {n_em} (finished={slot.request is None}) "
                f"vs device n_emit={n_emit_dev_i} done={done_i} "
                f"at tick {tick}")
        return n_em

    def _emit_window_lane(self, slot, picks_row, acc_i, n_emit_dev_i,
                          done_i, tick):
        """Per-slot emit of the windowed consume paths
        (``_consume_spec`` and ``_consume_ragged``'s mode-0 lanes):
        consume the device-accepted lanes plus the bonus token
        (``_emit_lane``).  Lane j's pick was drawn on device from the
        same key/logits the one-token tick would use for this prefix,
        and ``acc_i`` counts only REAL draft lanes, so consuming lanes
        0..acc_i reproduces the host accept loop exactly; an accepted
        lane is counted even when its token finishes the request (EOS
        drafted by a matched lane), but only over lanes actually
        consumed.  Returns (emitted, accepted)."""
        n_em = self._emit_lane(slot, picks_row[:acc_i + 1], n_emit_dev_i,
                               done_i, tick)
        slot.spec_lanes = 0
        return n_em, min(n_em, acc_i)

    def _consume_spec(self, inf, mats, done, tr):
        """Emit a materialized speculative tick: consume exactly the
        device-accepted lanes per slot (plus the bonus token), with
        the same acceptance accounting as the host verify loop.  The
        device-computed emitted-window length (``n_emit``) must match
        what the host loop consumed — a mismatch means the on-device
        stop condition diverged from ``_emit`` and raises into the
        step-failure recovery path."""
        picks = mats["picks"]
        n_acc = mats["n_acc"]
        n_emit_dev = mats["n_emit"]
        emitted = 0
        total_acc = 0
        # `with`, not manual enter/exit: an _emit failure mid-loop must
        # still record the span for the flight-recorder dump
        with tr.span("decode.emit", cpu=self._phase_cpu, batch=inf.batch,
                     layout=inf.layout) as emit_sp:
            for slot, req, lanes_i in zip(inf.slots, inf.reqs,
                                          inf.spec_lanes):
                i = slot.index
                if slot.request is not req:
                    if not done[i] and i not in inf.dropped:
                        raise RuntimeError(
                            f"async stop-condition drift: slot {i} "
                            f"was evicted on the host but tick "
                            f"{inf.tick}'s device lane is not done")
                    continue
                self._m_spec_proposed.inc(lanes_i)
                n_em, n_cnt = self._emit_window_lane(
                    slot, picks[i], int(n_acc[i]),
                    int(n_emit_dev[i]), bool(done[i]), inf.tick)
                self._m_spec_accepted.inc(n_cnt)
                total_acc += n_cnt
                emitted += n_em
            emit_sp.args.update(emitted=emitted, accepted=total_acc)
        proposed = self._m_spec_proposed.value
        if proposed:
            self._m_spec_rate.set(
                self._m_spec_accepted.value / proposed)
        self._m_spec_tpt.set(emitted / inf.batch)
        return emitted

    def _fused_spec_tick(self, active):
        """Synchronous fused speculative tick (async_depth=1 path):
        dispatch + immediate consume — today's tick shape."""
        inf = self._dispatch_spec(active, self.tracer)
        return self._consume(inf, self.tracer)

    @_spanned("dispatch", phase=True)
    def _dispatch_decode(self, active, tr):
        """DISPATCH one fused decode+sample tick without
        consuming it: the step state lives on
        device between ticks (admissions / evictions / chunks patch
        their own slot's lanes in behind the ticks in flight, see
        ``_sync_state``), sampling AND the stop
        condition run inside the dispatch, and the returned
        ``_InflightTick`` holds the un-materialized [B] ids + packed
        done-mask handles — jax async dispatch means this returns as
        soon as the program is enqueued, so the host can plan the
        next tick (or emit the previous one) while the device
        computes."""
        self._sync_state()
        st = self._dev_state
        if self._fused_fn is None:
            self._fused_fn, _, _ = self.model.serving_program(
                "fused_decode", self._pnames, self._params,
                self._lora_key(
                    ("paged" if self._paged else "slot", self.num_slots,
                     (self._kv_managed + self.dp, self._bs) if self._paged
                     else self.max_seq_len, self._kv_dtype_str,
                     tuple(self._pnames), self._bnames_all)),
                paged=self._paged)
        args = [self._p_list(), self._b_list(), self.k_pools,
                self.v_pools]
        if self._paged:
            args.append(st["tables"])
        args += [st["tok"], st["pos"], st["temp"], st["topk"],
                 st["topp"], st["slo"], st["shi"], st["ctr"],
                 st["eos"], st["rem"], *self._lora_args_state(st)]
        step = self._step
        span_args = {}
        if step is not None:
            args.append(st["flags"])
            span_args["width"] = step.rows
        layout = "paged" if self._paged else "contiguous"
        self._fault("dispatch")
        with tr.span("decode.dispatch", cpu=self._phase_cpu, batch=len(active),
                     layout=layout, fused=True,
                     rows=self._rows_walked(step.rows if step else 1),
                     **span_args), \
                self._dequant_span(tr, len(active)):
            (ids, done, new_tok, new_pos, new_ctr, new_rem,
             self.k_pools, self.v_pools, *stats) = self._fused_fn(*args)
        if step is not None:
            st["flags"] = stats.pop()
        self._dev_note(self._fused_fn.kind, ids, batch=len(active),
                       stats=stats, **span_args)
        st["tok"], st["pos"], st["ctr"], st["rem"] = \
            new_tok, new_pos, new_ctr, new_rem
        self._m_fused_ticks.inc()
        arrays = {"ids": ids, "done": done}
        if stats:
            arrays["stats"] = stats[0]  # rides the ids' download
        return _InflightTick(
            self.tick_no, "decode", list(active),
            arrays, len(active), layout,
            {"pos": self._pos.tolist(), "rem": self._rem.tolist()})

    def _consume_decode(self, inf, mats, done, tr):
        """Emit a materialized decode tick's tokens (the consume
        side: pure host work on already-downloaded arrays, so at
        async_depth > 1 it runs while the NEXT tick computes).  Lanes
        whose request was evicted by an earlier tick's consume are
        skipped via the ``slot.request is req`` identity check — the
        device froze them (done bit), and the slot may already carry
        a new request.  Host-vs-device stop-condition drift raises,
        turning a would-be silent corruption into a recovered step
        failure."""
        ids = mats["ids"]
        emitted = 0
        with tr.span("decode.emit", cpu=self._phase_cpu, batch=inf.batch,
                     layout=inf.layout) as emit_sp:
            for slot, req in zip(inf.slots, inf.reqs):
                i = slot.index
                if slot.request is not req:
                    if not done[i] and i not in inf.dropped:
                        raise RuntimeError(
                            f"async stop-condition drift: slot {i} "
                            f"was evicted on the host but tick "
                            f"{inf.tick}'s device lane is not done")
                    continue
                if self._step is None:
                    emitted += self._emit_lane(
                        slot, ids[i:i + 1], 1, bool(done[i]), inf.tick)
                    continue
                # a lane's report (StepSpec): its rows after the step,
                # then which of them to send and its new cursor and
                # flags, which the mirrors follow
                W = self._step.rows
                first, count, pos, flags = (int(v) for v in ids[i, W:])
                slot.pos = pos
                emitted += self._emit_lane(
                    slot, ids[i, first:first + count], count,
                    bool(done[i]), inf.tick, advance=0)
                if slot.request is req:
                    self._cur_tok[i] = ids[i, :W]
                    self._pos[i], self._flags[i] = pos, flags
            emit_sp.args["emitted"] = emitted
        return emitted

    # -- ragged paged attention dispatch (attn_impl="ragged") ----------
    def _plan_ragged_chunks(self, prefilling):
        """Select this tick's prefill-chunk lanes for the unified
        ragged dispatch: admission order (partially-prefilled prompts
        resume first — ``snapshot()`` already sorts by seq), ONE
        window lane per slot of up to ``min(_wmax, budget left)``
        tokens, strictly capped by ``tick_token_budget`` like
        ``_prefill_chunked``.  The lane width is capped by the
        compiled window ``_wmax`` (= max(prefill_chunk, spec_k+1)),
        not by ``prefill_chunk``: widths are runtime data, so a
        spec-widened window prefills faster than the nominal chunk at
        zero extra cost.  One structural difference from the XLA
        path: a slot advances at most one window per tick (the XLA
        path can spend the whole budget re-dispatching one slot's
        chunks back to back), so per-slot prefill throughput is
        ``_wmax`` tokens/tick — under ``attn_impl="ragged"`` size
        ``prefill_chunk`` to the per-tick prompt throughput you want
        (the budget then mainly arbitrates ACROSS slots).  Returns
        [(slot, n_tokens, is_final_chunk)]."""
        plan = []
        budget = self._tick_budget
        for slot in prefilling:
            req = slot.request
            n = min(self._wmax, budget,
                    len(req.context) - slot.prefilled)
            if n <= 0:
                continue
            plan.append((slot, n,
                         slot.prefilled + n >= len(req.context)))
            budget -= n
            if budget <= 0:
                break
        return plan

    @_spanned("dispatch", phase=True)
    def _dispatch_ragged(self, active, plan, tr):
        """DISPATCH one unified RAGGED window tick without consuming
        it: decoding slots ride as mode-0 lanes (width 1, or the k+1
        verify window with host-proposed drafts), budgeted prefill
        chunks as mode-1/2 lanes (width = chunk tokens) — ONE call of
        the ONE compiled ``ragged_window`` program, whatever the mix.
        Chunk lanes advance the prefill cursor AT DISPATCH (their
        tokens are known up front — unlike spec drafts there is no
        data dependence on the in-flight window), so a depth-2 blind
        dispatch can plan the next chunk, and a final chunk's first
        token rides home in the device picks, where the XLA path
        queues a program a chunk and reads a first token of its
        own."""
        import jax.numpy as jnp
        W = self._wmax
        B = self.num_slots
        spec_w = (self._spec_k + 1) if self._spec_k is not None else 1
        toks = np.zeros((B, W), np.int32)
        width = np.zeros(B, np.int32)
        mode = np.zeros(B, np.int32)
        lanes = np.zeros(B, np.int32)
        if self._spec_k is not None and active:
            with tr.span("spec.draft", batch=len(active),
                         spec_k=self._spec_k):
                toks[:, :spec_w] = self._draft_window(active)
        for slot in active:
            width[slot.index] = spec_w
            if self._spec_k is not None:
                lanes[slot.index] = slot.spec_lanes
        chunk_toks = 0
        for slot, n, final in plan:
            req = slot.request
            i = slot.index
            p0 = slot.prefilled
            toks[i, :n] = req.context[p0:p0 + n]
            width[i] = n
            mode[i] = 2 if final else 1
            chunk_toks += n
        # BEFORE the chunk lanes' mirror advance below: an upload or a
        # patch must carry the PRE-dispatch cursors (the program
        # itself advances them by width)
        self._sync_state()
        # kv blocks the kernel walks this tick (computed on the
        # PRE-dispatch cursors, before the chunk lanes' mirror
        # advance): the streaming loop stops at each lane's causal
        # horizon ceil((pos + width) / block_size) — the per-tick
        # block-walk cost the kv_blocks_walked_per_tick gauge makes
        # attributable
        walked = 0
        for s in (list(active) + [sl for sl, _, _ in plan]):
            i = s.index
            live = int(self._pos[i]) + max(int(width[i]), 1)
            walked += min(self._bps, (live - 1) // self._bs + 1)
        self._m_kv_blocks_walked.set(walked)
        for slot, n, final in plan:
            i = slot.index
            # dispatch-time bookkeeping (kept consistent with the
            # device cursor the program advances; the mirrors equal
            # the post-consume state, so a rebuild's upload stays
            # exact)
            slot.prefilled += n
            slot.pos = slot.prefilled
            self._pos[i] = slot.prefilled
            self._m_chunks.inc()
            self._m_prefill_tokens.inc(n)
        st = self._dev_state
        if self._ragged_fn is None:
            # emit_w: sample only the emit-reachable lanes (spec_k+1,
            # or 1 without speculation) — a chunk-widened window's
            # high lanes can never emit, so their picks would be
            # computed and discarded every tick
            self._ragged_fn, _, _ = \
                self.model.serving_program(
                    "ragged_window", self._pnames, self._params,
                    self._lora_key(
                        (self.num_slots, W, spec_w,
                         self._kv_managed + self.dp, self._bs,
                         self._kv_dtype_str, tuple(self._pnames),
                         self._bnames_all)),
                    emit_w=spec_w,
                    sharded=self.mp * self.dp > 1)
        self._fault("dispatch")
        with tr.span("decode.ragged_stream", cpu=self._phase_cpu,
                     batch=len(active) + len(plan),
                     layout="paged", w=W, chunks=len(plan),
                     chunk_tokens=chunk_toks, fused=True,
                     kv_blocks_walked=walked), \
                self._dequant_span(tr, len(active) + len(plan)):
            (picks, n_acc, n_emit, done, new_tok, new_pos, new_ctr,
             new_rem, self.k_pools, self.v_pools) = self._ragged_fn(
                self._p_list(), self._b_list(), self.k_pools,
                self.v_pools, st["tables"], st["scratch"],
                jnp.asarray(toks),
                jnp.asarray(width), jnp.asarray(mode),
                jnp.asarray(lanes), st["tok"], st["pos"], st["temp"],
                st["topk"], st["topp"], st["slo"], st["shi"],
                st["ctr"], st["eos"], st["rem"],
                *self._lora_args_state(st))
        self._dev_note(
            self._ragged_fn.kind, n_emit, batch=len(active),
            n=chunk_toks,
            req=plan[0][0].request.id if len(plan) == 1 else None)
        st["tok"], st["pos"], st["ctr"], st["rem"] = \
            new_tok, new_pos, new_ctr, new_rem
        self._m_fused_ticks.inc()
        if self._spec_k is not None and active:
            self._m_spec_windows.inc(len(active))
        slots = list(active) + [s for s, _, _ in plan]
        return _InflightTick(
            self.tick_no, "ragged", slots,
            {"picks": picks, "n_acc": n_acc, "n_emit": n_emit,
             "done": done}, len(slots), "paged",
            {"pos": self._pos.tolist(), "rem": self._rem.tolist()},
            meta_lanes=[(int(mode[s.index]), int(width[s.index]),
                         int(lanes[s.index])) for s in slots])

    def _consume_ragged(self, inf, mats, done, tr):
        """Emit a materialized ragged tick, per lane MODE: chunk lanes
        (mode 1) already advanced at dispatch — nothing to emit; a
        final chunk (mode 2) registers the prompt's full blocks in the
        prefix cache and emits the device-sampled first token (picks
        lane 0 — drawn with the unshifted counter key, the stream's
        next draw); decode / spec lanes (mode 0) run the same
        accepted-prefix emit loop as ``_consume_spec``, a pure decode
        lane being its zero-draft degenerate case.  Host-vs-device
        drift in any mode raises into step recovery."""
        picks = mats["picks"]
        n_acc = mats["n_acc"]
        n_emit_dev = mats["n_emit"]
        emitted = 0
        total_acc = 0
        emitted_spec = 0
        n_spec = 0
        with tr.span("decode.emit", cpu=self._phase_cpu, batch=inf.batch,
                     layout=inf.layout) as emit_sp:
            for slot, req, (mode_i, width_i, lanes_i) in zip(
                    inf.slots, inf.reqs, inf.meta_lanes):
                i = slot.index
                if slot.request is not req:
                    if not done[i] and i not in inf.dropped:
                        raise RuntimeError(
                            f"async stop-condition drift: slot {i} "
                            f"was evicted on the host but tick "
                            f"{inf.tick}'s device lane is not done")
                    continue
                if mode_i == 1:
                    if int(n_emit_dev[i]):
                        raise RuntimeError(
                            f"ragged drift: chunk lane {i} emitted "
                            f"{int(n_emit_dev[i])} on device at tick "
                            f"{inf.tick}")
                    continue
                if mode_i == 2:
                    ctxt = req.context
                    if self.prefix_cache is not None \
                            and not req._adapter_id:
                        self.prefix_cache.insert(
                            ctxt,
                            self._slot_blocks[i][:len(ctxt)
                                                 // self._bs])
                    self._emit(slot, int(picks[i, 0]))
                    emitted += 1
                    if int(n_emit_dev[i]) != 1 or \
                            bool(done[i]) != (slot.request is None):
                        raise RuntimeError(
                            f"ragged drift: final-chunk lane {i} "
                            f"device n_emit={int(n_emit_dev[i])} "
                            f"done={bool(done[i])} vs host finished="
                            f"{slot.request is None} at tick "
                            f"{inf.tick}")
                    continue
                # mode 0: decode / spec window — the same emit loop
                # as _consume_spec (zero draft lanes = plain decode)
                if self._spec_k is not None:
                    self._m_spec_proposed.inc(lanes_i)
                    n_spec += 1
                n_em, n_cnt = self._emit_window_lane(
                    slot, picks[i], int(n_acc[i]),
                    int(n_emit_dev[i]), bool(done[i]), inf.tick)
                if self._spec_k is not None:
                    self._m_spec_accepted.inc(n_cnt)
                    total_acc += n_cnt
                emitted_spec += n_em
                emitted += n_em
            emit_sp.args.update(emitted=emitted, accepted=total_acc)
        if self._spec_k is not None and n_spec:
            proposed = self._m_spec_proposed.value
            if proposed:
                self._m_spec_rate.set(
                    self._m_spec_accepted.value / proposed)
            self._m_spec_tpt.set(emitted_spec / n_spec)
        return emitted

    @_spanned("consume", phase=True)
    def _consume(self, inf, tr):
        """Materialize and emit one in-flight tick.  The blocking
        ``np.asarray`` on the ids + done mask is the async loop's ONLY
        sync point — traced as ``decode.d2h_wait`` (``decode.d2h`` at
        async_depth=1, today's synchronous name) so the wait is
        attributed to the download, not smeared into dispatch.  When
        a newer tick is still in flight, the emit work is wrapped in
        a ``host.overlap`` span and counted into
        ``serving.tick_overlap_ms`` — the host time the pipeline hid
        behind device compute."""
        wait_name = ("decode.d2h_wait" if self.async_depth > 1
                     else "decode.d2h")
        # the injectable wedge: a scheduled d2h_hang blocks here (the
        # engine's real sync point) until the watchdog converts it
        # into a WatchdogTimeout raise -> step-failure recovery
        self._fault("d2h_hang")
        if self.mp * self.dp > 1:
            # sharded tick: the [B] ids / picks are OUTPUTS of a
            # vocab-parallel head (replicated over 'mp' by its psum +
            # all-gather) and row-sharded over 'dp' — the device
            # finishes the cross-shard collectives before the handles
            # are ready.  Block on compute completion FIRST under its
            # own span so collective time is attributed to
            # decode.allgather, and the d2h span below measures the
            # (tiny, unchanged-contract) host copy alone.
            with _DeviceWait(self, tr.span(
                    "decode.allgather", tick=inf.tick,
                    shards=self.mp * self.dp, mp=self.mp, dp=self.dp)):
                for v in inf.arrays.values():
                    v.block_until_ready()
        t0 = time.monotonic()
        with _DeviceWait(self, tr.span(wait_name,
                                       tick=inf.tick)) as d2h_sp:
            mats = {k: np.asarray(v) for k, v in inf.arrays.items()}
            nbytes = sum(int(a.nbytes) for a in mats.values())
            d2h_sp.args["bytes"] = nbytes
        self._m_d2h_wait.observe((time.monotonic() - t0) * 1e3)
        self._m_d2h.set(nbytes)
        done = np.unpackbits(mats["done"],
                             count=self.num_slots).astype(bool)
        self._count_stats(inf.tick, mats.get("stats"))
        in_flight = bool(self._ring)
        t1 = time.monotonic()
        ov = (tr.span("host.overlap", tick=inf.tick) if in_flight
              else nullcontext())
        with ov:
            if inf.kind == "spec":
                emitted = self._consume_spec(inf, mats, done, tr)
            elif inf.kind == "ragged":
                emitted = self._consume_ragged(inf, mats, done, tr)
            else:
                emitted = self._consume_decode(inf, mats, done, tr)
        if in_flight:
            self._overlap_acc += time.monotonic() - t1
        # drop the consumed tick's device arrays HERE, under the span,
        # not a statement later with the last reference to ``inf``:
        # freeing them calls into the runtime, which gives the
        # interpreter up, and the tick's thread then queues for it
        # behind every handler thread that has a frame to write
        # (1.8 ms of a 15 ms tick under 13 streams: PERF.md section 5)
        inf.arrays = None
        return emitted

    def _count_stats(self, tick, vector=None):
        """Add the counter vector of the step program dispatched in
        ``tick``, and those of the chunk programs dispatched before it
        (complete by now: one device, in order), into the model's
        counters.  A chunk program queued BEHIND that step keeps its
        vector for a later tick's: reading it here would stand for a
        program the consumed tick never waited for."""
        ready = [h for t, h in self._stats_pending if t <= tick]
        self._stats_pending = [
            (t, h) for t, h in self._stats_pending if t > tick]
        for v in ([vector] if vector is not None else []) \
                + [np.asarray(h) for h in ready]:
            for m, n in zip(self._m_program, v):
                m.inc(int(n))

    def _note_dispatch_gap(self, n_active):
        """Pre-dispatch bookkeeping shared by the sync, async, and
        ragged tick paths (stall histogram + decode-batch gauge):
        ONE implementation, so the stall accounting cannot diverge
        between attn_impl modes or pipeline depths."""
        if self._last_decode_end is not None:
            self._m_stall.observe(
                (time.monotonic() - self._last_decode_end) * 1e3)
        self._m_decode_batch.set(n_active)

    def _drain_ring(self, tr, why):
        """Consume every in-flight tick, oldest first, under a
        ``ring.drain`` span that says ``why`` (``_RING_DRAINS``: the
        true syncs, where the host needs the tokens in flight before
        it can go on; a dirty slot is none, ``_patch_state``).
        Counted into ``serving.ring_drains.<why>``.  Returns tokens
        emitted."""
        emitted = 0
        self._m_ring_drains[why].inc()
        with tr.span("ring.drain", cpu=self._phase_cpu, why=why,
                     ticks=len(self._ring)):
            while self._ring:
                emitted += self._consume(self._ring.pop(0), tr)
        return emitted

    def _fused_decode_tick(self, active):
        """Synchronous fused decode tick (async_depth=1, the ``_tick``
        path): dispatch + immediate consume."""
        inf = self._dispatch_decode(active, self.tracer)
        return self._consume(inf, self.tracer)

    def step(self):
        """One engine tick: admit -> prefill -> slot-batched decode.
        Returns the number of tokens emitted this tick.

        A tick that raises (transient XLA error, bad dispatch) first
        RECOVERS the engine — in-flight requests are failed loudly
        (their waiters unblock) and the donated K/V pools are rebuilt
        (a dispatch that died after consuming them leaves them deleted)
        — then re-raises, so every driver (run_until_idle, the
        background loop) sees a working engine afterwards."""
        # O(1) no-op while subscribed; re-subscribes a synchronous
        # driver that keeps ticking after a stop()
        self._register_compile_listener()
        if self.watchdog_s is not None and self._watchdog is None:
            from .faults import TickWatchdog
            self._watchdog = TickWatchdog(self, self.watchdog_s).start()
        try:
            return self._step_inner()
        except Exception as e:
            # flight recorder FIRST: the dump must capture the slot /
            # request states as they were at the failure, not after
            # the evictions below tear them down
            self._record_flight(e)
            # busy_slots, not active_slots: a chunked tick that dies
            # mid-prompt leaves half-PREFILLED slots whose waiters must
            # unblock just like the decoding ones
            for slot in self.scheduler.busy_slots():
                req = self.scheduler.evict(slot, RuntimeError(
                    f"engine step failed: {e!r}"))
                if req is not None:
                    self._m_done.inc()  # terminal, like timeouts: keep
                    #   in-flight = total - completed consistent
                    self.tracer.instant("req.evicted", cat="request",
                                        req=req.id,
                                        reason="step_failure")
            self._reset_pools()
            self._last_decode_end = None
            self._m_occ.set(0)
            raise

    def _step_inner(self):
        self.tick_no += 1
        tr = self.tracer
        # watchdog heartbeat: stamped for the tick's whole duration;
        # a stale stamp is how the watchdog detects a wedged tick
        self._watchdog_fired = False
        self._tick_started_at = time.monotonic()
        try:
            self._fault("host_slow")
            self._blocked_s = self._blocked_cpu_s = 0.0
            with tr.span("tick", cat="tick",
                         tick=self.tick_no) as tick_sp:
                t0 = time.perf_counter()
                c0 = time.thread_time()
                if self.async_depth > 1:
                    emitted = self._tick_async(tr, tick_sp)
                else:
                    emitted = self._tick(tr, tick_sp)
                c1 = time.thread_time()
                # the tick's host share: its duration less the time
                # it was blocked on the device (d2h syncs, collectives);
                # of that, what this thread spent on a CPU, and the
                # rest: runnable or blocked, but not by the device.
                # Where the kernel accounts CPU time by its timer
                # (10 ms steps on the benchmark's machine), a whole
                # period lands on the tick the timer found running:
                # what a tick's host_ms cannot hold is carried to the
                # next ticks, so cpu_ms <= host_ms in every tick and
                # the sums over a window stay true
                host_ms = round(max(
                    time.perf_counter() - t0 - self._blocked_s, 0.0)
                    * 1e3, 3)
                cpu_seen = self._cpu_carry_ms + max(
                    c1 - c0 - self._blocked_cpu_s, 0.0) * 1e3
                cpu_ms = round(min(cpu_seen, host_ms), 3)
                self._cpu_carry_ms = max(cpu_seen - cpu_ms, 0.0)
                wait_ms = round(host_ms - cpu_ms, 3)
                tick_sp.args.update(host_ms=host_ms, cpu_ms=cpu_ms,
                                    wait_ms=wait_ms)
                blocked_cpu_ms = round(self._blocked_cpu_s * 1e3, 3)
                if blocked_cpu_ms:
                    tick_sp.args["blocked_cpu_ms"] = blocked_cpu_ms
            self._m_tick_host.inc(host_ms)
            self._m_tick_cpu.inc(cpu_ms)
            self._m_tick_wait.inc(wait_ms)
            self._m_proc_cpu.set(round(time.process_time() * 1e3, 3))
        finally:
            self._tick_started_at = None
        if emitted:
            now = time.monotonic()
            with self._ovl_lock:
                self._rate_win.append((now, emitted))
            rate = self.drain_rate()
            if rate is not None:
                self._m_drain_tps.set(round(rate, 1))
        return emitted

    def _tick_async(self, tr, tick_sp):
        """One PIPELINED engine tick (async_depth > 1): plan/admit in
        the gap while the previous tick computes, dispatch tick N+1,
        then consume tick N's already-materializing ids — so the
        inter-tick host work (admission, chunk planning, the emit
        loop) hides behind device compute instead of serializing with
        it.  Structural events (admission, eviction, chunk progress,
        a final chunk's first token) mark their slot dirty and reach
        the device as per-slot patches queued in dispatch order
        (``_patch_state``): nothing in flight is consumed for them,
        and parity with the synchronous tick is exact because the
        device runs its queue in order."""
        self._overlap_acc = 0.0
        now = time.monotonic()
        emitted = 0
        # cross-replica migration orders first: an export drains the
        # ring and frees its slot for this very tick's admission, an
        # import's request enters the queue before the admit phase
        emitted += self._service_migrations(tr)
        # ...then the offload drain: last tick's demote gathers have
        # had a dispatch of device time — copy out behind it
        self._service_offload(tr)
        # -- planning / admission: host work in the gap --------------
        in_flight = bool(self._ring)
        t_plan = time.monotonic()
        self._gate_declined = False
        ov = (tr.span("host.overlap", phase="plan") if in_flight
              else nullcontext())
        with ov:
            with tr.span("admit", cpu=self._phase_cpu) as admit_sp:
                timed_out = self.queue.expire(now)
                admitted = []
                if not self._draining and self.scheduler.admissible():
                    admitted, admit_timed_out = self.scheduler.admit(
                        now, gate=self._kv_gate if self._paged
                        else None)
                    timed_out = timed_out + admit_timed_out
                admit_sp.args.update(admitted=len(admitted),
                                     timed_out=len(timed_out))
        if in_flight:
            self._overlap_acc += time.monotonic() - t_plan
        # priority preemption (outside the overlap span: it may have
        # to consume the in-flight ring — a real sync, not hidden
        # host work)
        p_admitted, p_timed, p_emitted = self._preempt_round(now, tr)
        emitted += p_emitted
        admitted = self._post_admit(admitted + p_admitted,
                                    timed_out + p_timed, tr)
        # -- prefill / chunk planning (mutates only the admitted
        #    slots' lanes; their patch is queued at the dispatch) ----
        if self._chunk is None:
            for slot in admitted:
                rid = slot.request.id
                with tr.span("prefill", req=rid,
                             prompt=int(len(slot.request.prompt))):
                    self._prefill(slot)
        else:
            with tr.span("chunk.plan", cpu=self._phase_cpu) as plan_sp:
                for slot in admitted:
                    self._begin_chunked(slot)
                _, _, prefilling = self.scheduler.snapshot()
                plan_sp.args["prefilling"] = len(prefilling)
                if prefilling and not self._ragged:
                    # ragged mode: chunks ride as lanes of the unified
                    # dispatch below; the XLA path's per-chunk
                    # programs are queued here, behind the decode in
                    # flight
                    self._prefill_chunked(prefilling)
        # -- spec barrier: drafting is data-dependent on the previous
        #    window's accepted tokens, so spec mode always consumes
        #    before the dispatch snapshot — but only HERE, after the
        #    planning/prefill phase above ran in the gap, so spec
        #    ticks still overlap their plan work with the in-flight
        #    verify's device compute --------------------------------
        if self._spec_k is not None:
            if self._ring:
                emitted += self._drain_ring(tr, "spec")
            # ...and on the first token of this tick's prefills
            emitted += self._emit_first_tokens()
        # (no dirty barrier: a slot an admission, an eviction or a
        # chunk touched is patched on the device, behind the ring,
        # when the dispatch below syncs the state)
        occ, active, prefilling = self.scheduler.snapshot()
        ragged = self._ragged
        if active and self._ring and self._spec_k is None and \
                self._step is None and \
                not (ragged and prefilling) and \
                all(self._rem[s.index] <= len(self._ring)
                    for s in active):
            # bursty-tail cutoff: the rem mirrors say every active
            # slot exhausts its budget within the ticks ALREADY in
            # flight, so one more dispatch would compute only frozen
            # lanes — consume instead (EOS can still finish a lane
            # earlier than its budget; that case just falls through
            # to the done-mask path).  Pending ragged chunk lanes
            # veto the cutoff: their dispatch still does real work.
            emitted += self._drain_ring(tr, "tail")
            occ, active, prefilling = self.scheduler.snapshot()
        n_before = self._evicted_in_tick
        plan = []
        if ragged and self._chunk is not None and prefilling:
            with tr.span("chunk.plan", cpu=self._phase_cpu,
                         prefilling=len(prefilling)):
                plan = self._plan_ragged_chunks(prefilling)
        # -- dispatch tick N+1 ---------------------------------------
        if active or plan:
            self._note_dispatch_gap(len(active))
            if ragged:
                inf = self._dispatch_ragged(active, plan, tr)
            else:
                inf = (self._dispatch_spec(active, tr)
                       if self._spec_k is not None
                       else self._dispatch_decode(active, tr))
            self._ring.append(inf)
            self._last_decode_end = time.monotonic()
        else:
            self._m_decode_batch.set(0)
            self._last_decode_end = None
        # -- consume tick N (the emit loop overlaps N+1's compute);
        #    with nothing dispatched, drain the tail completely ------
        keep = (self.async_depth - 1) if (active or plan) else 0
        while len(self._ring) > keep:
            emitted += self._consume(self._ring.pop(0), tr)
        # -- ...then the first tokens of this tick's prefills: picked
        #    on the device ahead of the decode just queued, read once
        #    their program ends, not once that decode does ----------
        emitted += self._emit_first_tokens()
        occ -= self._evicted_in_tick - n_before
        if self._ring and occ == 0:
            # every slot freed while the newest dispatch was in
            # flight: its lanes are all frozen (device-side stop), so
            # drain the tail — an idle engine must hold no futures
            emitted += self._drain_ring(tr, "idle")
        self._m_queue.set(self.queue.depth())
        self._m_occ.set(occ)
        ov_ms = self._overlap_acc * 1e3
        self._m_overlap.observe(ov_ms)
        tick_sp.args.update(batch=len(active), emitted=emitted,
                            occupancy=occ, queue=self.queue.depth(),
                            overlap_ms=round(ov_ms, 3),
                            in_flight=len(self._ring))
        if self._paged:
            self._m_kv_blocks.set(self.block_pool.in_use())
            tick_sp.args["kv_blocks_in_use"] = self.block_pool.in_use()
        return emitted

    def _tick(self, tr, tick_sp):
        now = time.monotonic()
        emitted = 0
        # cross-replica migration orders first (see _tick_async)
        emitted += self._service_migrations(tr)
        self._service_offload(tr)  # tick-boundary demote drain
        self._gate_declined = False
        # deadline sweep first: with a full pool nothing gets popped,
        # but queued requests must still time out on schedule
        with tr.span("admit", cpu=self._phase_cpu) as admit_sp:
            timed_out = self.queue.expire(now)
            admitted = []
            if not self._draining:
                admitted, admit_timed_out = self.scheduler.admit(
                    now, gate=self._kv_gate if self._paged else None)
                timed_out = timed_out + admit_timed_out
            admit_sp.args.update(admitted=len(admitted),
                                 timed_out=len(timed_out))
        # priority preemption: evict the lowest-priority running slot
        # when the best queued request outranks it and admission is
        # blocked (no async ring at depth 1, so no drain involved)
        p_admitted, p_timed, p_emitted = self._preempt_round(now, tr)
        emitted += p_emitted
        admitted = self._post_admit(admitted + p_admitted,
                                    timed_out + p_timed, tr)
        plan = []   # ragged mode: this tick's prefill-chunk lanes
        if self._chunk is None:
            for slot in admitted:
                # read the id up front: an EOS-on-first-token prefill
                # evicts and clears slot.request before the span ends
                rid = slot.request.id
                with tr.span("prefill", req=rid,
                             prompt=int(len(slot.request.prompt))):
                    self._prefill(slot)
        else:
            with tr.span("chunk.plan", cpu=self._phase_cpu) as plan_sp:
                for slot in admitted:
                    self._begin_chunked(slot)
                _, _, prefilling = self.scheduler.snapshot()
                plan_sp.args["prefilling"] = len(prefilling)
                if prefilling and not self._ragged:
                    self._prefill_chunked(prefilling)
        # nothing is in flight to read the first tokens behind: the
        # same patches and picks as the pipelined tick's, consumed at
        # once, so a first token that ends its request frees the slot
        # before the snapshot
        emitted += self._emit_first_tokens()
        # final-chunk slots decode in this same tick, like monolithic
        # emit-then-decode
        occ, active, prefilling = self.scheduler.snapshot()
        if self._ragged and self._chunk is not None:
            # chunks ride as window lanes of the unified dispatch, not
            # through the per-chunk loop
            with tr.span("chunk.plan", cpu=self._phase_cpu,
                         prefilling=len(prefilling)):
                plan = self._plan_ragged_chunks(prefilling)
        if self._ragged:
            if active or plan:
                self._note_dispatch_gap(len(active))
                n_before = self._evicted_in_tick
                inf = self._dispatch_ragged(active, plan, tr)
                emitted += self._consume(inf, tr)
                occ -= self._evicted_in_tick - n_before
                self._last_decode_end = time.monotonic()
            else:
                self._m_decode_batch.set(0)
                self._last_decode_end = None
        elif active:
            self._note_dispatch_gap(len(active))
            n_before = self._evicted_in_tick
            emitted += (self._fused_spec_tick(active)
                        if self._spec_k is not None
                        else self._fused_decode_tick(active))
            occ -= self._evicted_in_tick - n_before
            self._last_decode_end = time.monotonic()
        else:
            self._m_decode_batch.set(0)
            self._last_decode_end = None
        self._m_queue.set(self.queue.depth())
        self._m_occ.set(occ)
        tick_sp.args.update(batch=len(active), emitted=emitted,
                            occupancy=occ, queue=self.queue.depth())
        if self._paged:
            self._m_kv_blocks.set(self.block_pool.in_use())
            tick_sp.args["kv_blocks_in_use"] = self.block_pool.in_use()
        return emitted

    def run_until_idle(self, max_steps=100000):
        """Drive ticks until queue and slots are empty (test/batch
        convenience); returns total tokens emitted."""
        total = 0
        for _ in range(max_steps):
            if self.scheduler.idle() and not self._migrate_actionable():
                self._flush_offload()  # last tick's demotes land
                return total
            total += self.step()
        raise RuntimeError(
            f"engine still busy after {max_steps} steps "
            f"(occupancy={self.scheduler.occupancy()}, "
            f"queue={self.queue.depth()})")

    # -- background loop -------------------------------------------------
    def start(self):
        """Run the tick loop on a daemon thread (the HTTP endpoint's
        mode); idle ticks sleep briefly instead of spinning.  Safe to
        call after a timed-out stop(): the new loop joins the old one
        before its first tick, so two loops never step concurrently."""
        self._register_compile_listener()  # restart after a stop()
        prev = self._thread
        if prev is not None and prev.is_alive() \
                and not self._stop.is_set():
            return prev  # loop already running
        if prev is not None and not prev.is_alive():
            prev = None
        # a restart supersedes a pending shutdown drain: the old loop
        # must not fail requests submitted to the restarted engine
        # (the flag is the owning loop's stop event, so a stale loop
        # comparing against its own event can never match after this)
        self._drain_on_exit = None
        self._draining = False  # a restarted engine admits again
        # each loop carries its OWN stop event: a stop-pending loop
        # keeps honoring the event it was born with while the new loop
        # runs against the fresh one
        stop_evt = self._stop = threading.Event()

        def loop():
            if prev is not None:
                prev.join()  # serialize: never two loops in step()
            try:
                while not stop_evt.is_set():
                    if self.scheduler.idle() \
                            and not self._migrate_actionable():
                        self._m_rate.refresh()  # decay tokens/sec to 0
                        # event-driven wake instead of a 2 ms poll: an
                        # idle engine burns no CPU and a submit() is
                        # admitted immediately, not a poll later.  The
                        # clear-then-recheck order closes the race: a
                        # submit landing between the idle check and
                        # the clear is caught by the recheck, one
                        # landing after it re-sets the event.  The
                        # timeout is only the tokens/sec decay + stop
                        # heartbeat, not an admission latency bound.
                        self._flush_offload()  # going idle: land the
                        #   final tick's demote gathers now
                        self._wake.clear()
                        if self.scheduler.idle() \
                                and not self._migrate_actionable() \
                                and not stop_evt.is_set():
                            self._wake.wait(timeout=0.5)
                        continue
                    try:
                        self.step()  # step() already recovered state
                    except Exception:  # keep the loop alive
                        time.sleep(0.05)  # no hot spin on repeat failure
            finally:
                # a stop() whose join might time out delegates the
                # drain here (the loop's last act); the identity check
                # means only THIS loop's stop() can trigger it — a
                # restart invalidates stale delegations
                if self._drain_on_exit is stop_evt:
                    self._drain_on_exit = None
                    self._drain()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="paddle_tpu-serving-engine")
        self._thread.start()
        return self._thread

    def _drain(self):
        """Fail every queued and in-flight request (shutdown path)."""
        self._flush_offload()  # land pending demotes — the host tier
        #   outlives this loop and warms the next start()
        # drop un-consumed dispatches and unread first tokens: their
        # requests fail below, and the next start() serves with clean
        # cursors (every eviction parks its lanes and marks its slot
        # for a patch)
        self._ring = []
        self._first_pending, self._first_picked = [], []
        with self._mig_lock:
            demands, self._migrate_demands = self._migrate_demands, []
        for d in demands:
            d.fail(RuntimeError("engine stopped"))
        self._m_done.inc(len(self.queue.drain()))
        for slot in self.scheduler.busy_slots():
            req = self.scheduler.evict(
                slot, RuntimeError("engine stopped"))
            self._release_slot_kv(slot.index)
            self._park_state(slot.index)  # a later start() serves with
            #   clean device cursors
            if req is not None:
                self._m_done.inc()
                self.tracer.instant("req.evicted", cat="request",
                                    req=req.id, reason="shutdown")
        self._m_queue.set(0)
        self._m_occ.set(0)

    def stop(self, drain=True, join_timeout=30.0, drain_timeout=None):
        """Stop the background loop.

        ``drain=True`` (default) is a GRACEFUL DRAIN: submission
        closes (``submit`` sheds with QueueFull) and no queued
        request is admitted, but the loop keeps ticking until every
        IN-FLIGHT stream finishes — their waiters receive complete
        outputs instead of an "engine stopped" error.  The wait is
        bounded by ``drain_timeout`` (default: ``join_timeout``);
        whatever is still running past the bound, plus every
        queued-but-never-admitted request, is failed by the final
        hard drain — shutdown always terminates.  ``drain=False``
        halts the loop in place without failing anything (requests
        stay pending for a later ``start()``)."""
        evt = self._stop
        t = self._thread
        if drain and t is not None and t.is_alive() \
                and not evt.is_set():
            # graceful phase: the live loop finishes the in-flight
            # streams while admissions are held off
            self._draining = True
            self._wake.set()
            limit = (join_timeout if drain_timeout is None
                     else drain_timeout)
            deadline = time.monotonic() + max(float(limit), 0.0)
            while time.monotonic() < deadline \
                    and self.scheduler.busy_slots():
                time.sleep(0.002)
        if drain:
            # delegate BEFORE set+join: a loop that exits inside the
            # join window must still see the delegation (it drains in
            # its finally; double-drain below is an idempotent no-op)
            self._drain_on_exit = evt
        evt.set()
        self._wake.set()  # unblock an idle loop's event wait now
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None  # a later step()/start() re-arms
        if t is not None:
            t.join(timeout=join_timeout)
            if t.is_alive():
                # mid-dispatch (e.g. a long first compile): draining
                # under the live loop would race it, so the loop drains
                # on exit instead; the handle stays so a later start()
                # serializes behind it — and the compile listener stays
                # subscribed, because that in-flight dispatch may be
                # the very compile worth recording.  Clear the drain
                # flag NOW: the stop event already keeps this loop
                # from admitting again, and a later synchronous
                # driver (step() after stop() is supported) must not
                # find admissions permanently disabled
                self._draining = False
                return
            self._thread = None
        # only AFTER the loop is confirmed down: a stopped engine must
        # not keep counting sibling engines' compiles, but compiles
        # completing inside the join window above still count.
        # start() — or a synchronous step() — re-subscribes.
        self._unregister_compile_listener()
        self._stop_watcher(join_timeout)
        if drain:
            self._drain_on_exit = None
            self._drain()
        self._draining = False  # a later start() serves normally

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
