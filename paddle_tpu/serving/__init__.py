"""paddle_tpu.serving — continuous-batching inference engine.

The serving workload class (ROADMAP: "serve heavy traffic from millions
of users"): an in-process ``Engine`` runs ONE jitted one-token decode
step over a fixed pool of batch slots, a ``Scheduler`` admits queued
requests into free slots (prefill on admission, eviction on EOS /
max_new_tokens), a ``RequestQueue`` enforces per-request deadlines, and
``serving.httpd`` exposes the whole thing over stdlib HTTP for smoke
serving.  ``serving.kvcache`` pages the K/V pools into fixed-size
refcounted blocks (``Engine(kv_block_size=...)``): identical prompt
prefixes share physical blocks and a token-trie ``PrefixCache`` lets
admission skip prefill for previously-seen spans, with LRU eviction
under pool pressure.  ``Engine(prefill_chunk=...,
tick_token_budget=...)`` adds budgeted CHUNKED prefill: prompts split
into fixed-size chunks interleaved with decode so a long prompt can
no longer stall token emission for the active slots (decode latency
is bounded by the per-tick token budget, not the longest queued
prompt).  ``Engine(spec_k=..., proposer=...)`` turns the decode tick
into SPECULATIVE draft-and-verify (``serving.spec``): a proposer —
``PromptLookupProposer`` (n-gram match on the slot's own history, no
extra model) or ``DraftModelProposer`` (a smaller GPT) — guesses k
tokens per slot, ONE jitted verify dispatch scores all k+1 positions,
and the engine keeps the longest argmax-matching prefix plus the
bonus token: 1..k+1 tokens per dispatch, greedy outputs still
token-identical to the non-speculative engine.
Sampling is FUSED into the jitted dispatches: per-slot
temperature/top_k/top_p as traced lanes, rng keys derived on device
from the request seed + emitted-token counter, device-resident step
cursors — a steady-state tick uploads nothing and downloads only the
sampled ids (+ accept counts under speculation), never the per-tick
logits matrix.  ``Engine(weight_dtype="int8")`` /
``Engine(kv_dtype="int8")`` add QUANTIZED serving (``serving.quant``):
weight-only int8 codes ride the compiled hot paths as traced buffers,
and the paged K/V pools store int8 codes with per-block per-head f32
scales (``QuantKV``) so the same ``kv_budget_mb`` holds ~2x the
logical blocks vs bf16 (~4x vs f32) — quantized blocks stay
first-class through prefix sharing, preemption, recovery, and the
migration wire (a ``kv_dtype``-mismatched peer raises
``KVDtypeMismatch`` instead of adopting garbage).  Metrics (queue depth, slot occupancy, tokens/sec,
TTFT/TPOT, KV blocks in use, prefix hits/evictions, prefill chunks,
decode stall, spec proposed/accepted/acceptance-rate/tokens-per-tick,
d2h bytes per tick, fused-sample ticks, compiles)
land in paddle_tpu.monitor and render via ``render_prometheus()``.
Every engine also runs a tick-level span tracer (monitor/tracing.py:
bounded per-thread rings, phase spans + request lifecycle instants +
compile events) with chrome-trace export (``Engine.chrome_trace()``,
``GET /debug/trace``), a live request view (``GET /debug/requests``),
and an automatic flight-recorder dump on step failure
(``Engine(flight_dir=...)``).  THE HOST BY THREAD: the ``tick`` span
splits its ``host_ms`` (the tick less its waits for the device) into
``cpu_ms`` (Python its thread ran) and ``wait_ms`` (it stood still:
the interpreter lock, a sink lock, the scheduler), its phases
(``admit``, ``chunk.plan``, ``prefill.chunk``, ``prefill.d2h``,
``state.push``, ``state.patch``, ``first_token``, ``ring.drain``,
``dispatch``, ``decode.dispatch``,
``consume``, ``decode.emit``) carry ``cpu_ms`` where a read of that
clock is cheap or ``trace_annotations`` is on, the HTTP edge's
``http.ingest`` and ``http.stream`` (one a streamed response) theirs,
and counters keep the sums with tracing on or off:
``serving.tick_host_ms`` / ``tick_cpu_ms`` / ``tick_wait_ms``,
``serving.http_cpu_ms`` / ``http_frames`` / ``http_bytes_out``,
``serving.dev_watch_cpu_ms`` and the gauge ``process.cpu_ms``
(``tools/trace_view.py --wall`` prints both).  OVERLOAD PROTECTION:
``submit(priority=..., tenant=...)`` gives requests priority classes
(higher preempts lower MID-STREAM under slot/KV pressure — the
victim's blocks return to the prefix cache and its stream resumes
token-identically on re-admission) and per-tenant weighted-fair
queue service with token-bucket rate limits
(``Engine(tenants={...})``); deadline-aware shedding rejects
requests whose deadline the measured drain rate already cannot meet
(``DeadlineShed`` with an honest computed Retry-After);
``stop(drain=True)`` drains gracefully (in-flight streams finish,
bounded by a timeout); and ``serving.faults`` provides the
deterministic chaos harness (seeded fault schedule over
dispatch/d2h/pool/host sites + a tick watchdog that converts wedged
dispatches into flight-recorded recoveries).
``Engine(adapters={name: LoRAAdapter(...)})`` adds MULTI-ADAPTER
serving (``serving.lora``): every adapter's low-rank factors live in
two fixed-shape device banks gathered by a per-slot ``adapter_id``
INSIDE the compiled hot paths — one program serves every adapter,
hot-load/unload is pure data movement (zero recompiles), and
in-flight requests pin their adapter against unload.
``serving.stream`` adds live TOKEN STREAMING: a ``TokenStream``
attaches to a request with exactly-once replay-then-subscribe
semantics, httpd/routerd answer ``{"stream": true}`` as SSE, and the
router's ``generate(on_token=...)`` splices failover/migration
continuations into one seamless stream.
``Engine(kv_host_mb=...)`` adds the HIERARCHICAL KV OFFLOAD tier
(``serving.offload``): blocks the prefix trie evicts under pool
pressure demote into a content-addressed host-RAM ``HostBlockStore``
(async device gathers materialized at tick boundaries, LRU within a
byte budget) instead of vanishing, and admission consults the store
after the device trie — a host hit restores the payload into fresh
device blocks and skips prefill for the span exactly like a device
prefix hit, token-identical to a never-evicted run; int8 KV payloads
carry codes+scales, and the router's prefix warming ships a peer's
host tier before recomputing.
"""
from .request import (  # noqa: F401
    Request, RequestQueue, RequestTimeout, QueueFull, Rejected,
    RateLimited, DeadlineShed, TenantPolicy, TokenBucket)
from .scheduler import Scheduler, Slot  # noqa: F401
from .kvcache import (  # noqa: F401
    BlockPool, KVDtypeMismatch, NoFreeBlocks, PrefixCache)
from .quant import QuantKV, relayout_weights_int8  # noqa: F401
from .spec import (  # noqa: F401
    Proposer, PromptLookupProposer, DraftModelProposer)
from .faults import (  # noqa: F401
    FaultInjector, InjectedFault, NetDisconnect, NetFault, NetRefused,
    NetTimeout, TickWatchdog, WatchdogTimeout)
from .lora import (  # noqa: F401
    AdapterInUse, AdapterRegistry, LoRAAdapter, RegistryFull,
    UnknownAdapter)
from .stream import (  # noqa: F401
    StreamClosed, StreamEvent, TokenStream, parse_sse, sse_format)
from .offload import HostBlockStore, prefix_key  # noqa: F401
from .engine import Engine  # noqa: F401
from .httpd import EngineServer, serve  # noqa: F401
from .router import (  # noqa: F401
    CircuitBreaker, HttpReplicaClient, InProcessReplica,
    NoReplicasAvailable, Replica, ReplicaAbandoned, ReplicaHTTPError,
    ReplicaUnavailable, RequestFailed, Router, RouterError,
    RouterPolicy, UnknownModel, affinity_key)
from .routerd import RouterServer  # noqa: F401
from .supervisor import (  # noqa: F401
    FleetSupervisor, ProcessReplica, SupervisorPolicy,
    supervise_fleet)

__all__ = [
    "Request", "RequestQueue", "RequestTimeout", "QueueFull",
    "Rejected", "RateLimited", "DeadlineShed", "TenantPolicy",
    "TokenBucket",
    "Scheduler", "Slot", "Engine", "EngineServer", "serve",
    "BlockPool", "PrefixCache", "NoFreeBlocks",
    "KVDtypeMismatch", "QuantKV", "relayout_weights_int8",
    "HostBlockStore", "prefix_key",
    "Proposer", "PromptLookupProposer", "DraftModelProposer",
    "FaultInjector", "InjectedFault", "TickWatchdog",
    "WatchdogTimeout",
    "NetFault", "NetRefused", "NetTimeout", "NetDisconnect",
    "LoRAAdapter", "AdapterRegistry", "AdapterInUse", "RegistryFull",
    "UnknownAdapter",
    "TokenStream", "StreamEvent", "StreamClosed", "sse_format",
    "parse_sse",
    "Router", "RouterPolicy", "RouterServer", "RouterError",
    "UnknownModel",
    "NoReplicasAvailable", "RequestFailed", "Replica",
    "ReplicaAbandoned", "ReplicaHTTPError", "ReplicaUnavailable",
    "CircuitBreaker", "HttpReplicaClient", "InProcessReplica",
    "affinity_key",
    "FleetSupervisor", "SupervisorPolicy", "ProcessReplica",
    "supervise_fleet",
]
