"""Resilient multi-replica routing: kill a replica, lose nothing.

``serving_engine.py`` scales ONE engine up; this demo scales OUT: a
``serving.Router`` spreads traffic over two local engine replicas
(``InProcessReplica`` — the same transport the tier-1 tests
use), probing health, routing by PREFIX AFFINITY (the first
kv_block_size-aligned span of the prompt is rendezvous-hashed, so
every request sharing the system prompt lands on the replica whose
prefix cache holds its blocks), and surviving failures:

1. steady state — all shared-prefix traffic lands on one replica,
   whose prefix cache serves the system prompt's KV blocks;
2. that replica is KILLED mid-workload — the next request pays one
   refused hop and fails over to the survivor (token-identical to an
   uninterrupted run: greedy failover re-dispatches with context),
   consecutive failures TRIP the replica's circuit breaker, and the
   health prober walks the corpse through degraded -> dead;
3. the replica comes BACK — a clean probe moves the cooled breaker to
   half-open, the next request is the trial that closes it, and
   affinity routing resumes where it left off.

The failover timeline (``router.route_log()`` — picks, failovers,
breaker transitions, probe verdicts; a pure function of the seed and
the fault schedule) is printed at the end, plus the router's metrics.

Run: python examples/serving_router.py
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
from paddle_tpu.serving import (Engine, InProcessReplica, Router,
                                RouterPolicy)
from paddle_tpu.serving.router import affinity_key


def main():
    paddle.seed(0)
    model = GPTModel.from_config("tiny", dropout=0.0)
    model.eval()
    vocab = int(model.embeddings.word_embeddings.weight.shape[0])
    rng = np.random.RandomState(0)
    sysp = rng.randint(0, vocab, (16,)).tolist()   # shared 2-block head
    n_new = 4

    def mk_prompt(i):
        return sysp + rng.randint(0, vocab, (2 + i % 3,)).tolist()

    # two local replicas: same model (same seeded weights), private
    # engines + registries — exactly what 2 processes would run
    engines = {n: Engine(model, num_slots=2, max_seq_len=64,
                         kv_block_size=8,
                         registry=monitor.StatRegistry())
               for n in ("alpha", "beta")}
    reps = {n: InProcessReplica(n, engines[n]) for n in engines}
    reg = monitor.StatRegistry()
    router = Router(reps, policy=RouterPolicy(
        seed=0, retry_max=3, breaker_threshold=2,
        breaker_cooldown_s=0.0, backoff_base_s=0.005),
        kv_block_size=8, registry=reg)
    for e in engines.values():
        e.start()
    t_start = time.perf_counter()

    def stamp():
        return (time.perf_counter() - t_start) * 1e3

    def show(out):
        print(f"  [{stamp():8.1f} ms] req {out['req']:2d} -> "
              f"{out['replica']}  (attempts {out['attempts']})")

    try:
        router.probe_once()
        target = router._affinity_target(
            affinity_key(sysp, router.block_size()),
            router._reps()).name
        survivor = next(n for n in reps if n != target)

        # -- 1. steady state: affinity concentrates the prefix ---------
        print(f"steady state — shared system prompt's affinity target "
              f"is '{target}':")
        for i in range(4):
            show(router.generate(mk_prompt(i), max_new_tokens=n_new))
        cached = int(engines[target].registry.get(
            "serving.prefix_hit_tokens").value)
        print(f"  affinity hits "
              f"{int(reg.get('router.affinity_hits_total').value)}/"
              f"{int(reg.get('router.picks_total').value)}; "
              f"'{target}' served {cached} prompt tokens from its "
              f"prefix cache")

        # -- 2. kill the affinity target mid-workload ------------------
        print(f"\nKILLING '{target}' — traffic continues:")
        reps[target].kill()
        p = mk_prompt(4)
        ref = model.generate(
            paddle.to_tensor(np.asarray([p], np.int32)),
            max_new_tokens=n_new).numpy()[0]
        out = router.generate(list(p), max_new_tokens=n_new)
        assert out["ids"] == [int(x) for x in ref], \
            "failover must stay token-identical to generate()"
        show(out)
        print(f"  ^ paid one refused hop on '{target}', failed over "
              f"to '{out['replica']}', token-identical to an "
              f"uninterrupted generate()")
        show(router.generate(mk_prompt(5), max_new_tokens=n_new))
        print(f"  breaker['{target}'] = "
              f"{router._replicas[target].breaker.state} after "
              f"{router.policy.breaker_threshold} consecutive "
              f"failures — picks now skip it without trying")
        for _ in range(router.policy.dead_after):
            router.probe_once()      # degraded -> ... -> dead
        print(f"  prober verdict: {target} = "
              f"{router._replicas[target].state}")
        for i in range(6, 8):
            show(router.generate(mk_prompt(i), max_new_tokens=n_new))

        # -- 3. the replica returns: probe-driven breaker recovery -----
        print(f"\nREVIVING '{target}':")
        reps[target].revive()
        router.probe_once()          # clean probe: healthy again, and
        #   the cooled-open breaker moves to HALF_OPEN
        print(f"  probe: {target} = {router._replicas[target].state}, "
              f"breaker = {router._replicas[target].breaker.state}")
        out = router.generate(mk_prompt(8), max_new_tokens=n_new)
        show(out)
        print(f"  ^ the half-open trial; breaker = "
              f"{router._replicas[target].breaker.state} — affinity "
              f"routing resumed")
    finally:
        for e in engines.values():
            e.stop(drain=False)

    print("\nfailover timeline (router.route_log() — deterministic "
          "for this seed):")
    for ev in router.route_log():
        print(f"   {ev}")

    print("\nrouter metrics:")
    for name in ("router.requests_total", "router.served_total",
                 "router.retries_total", "router.failovers_total",
                 "router.affinity_hits_total",
                 "router.breaker_trips_total"):
        print(f"  {name} = {int(reg.get(name).value)}")
    print(f"  (spans: route.pick / route.retry / probe — "
          f"router.chrome_trace(), or tools/timeline.py --router "
          f"http://host:port against a live routerd to merge the "
          f"router's trace with every replica's)")

    served = [ev for ev in router.route_log() if ev[0] == "serve"]
    assert len(served) == int(reg.get("router.served_total").value)
    print(f"\nall {len(served)} requests delivered exactly once "
          f"despite the kill.")


if __name__ == "__main__":
    main()
