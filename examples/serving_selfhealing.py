"""Self-healing serving fleet: supervisor tier + SIGTERM drain.

Two failure stories, one goal — the fleet heals itself and no request
ever pays for it:

1. SUPERVISOR LIFECYCLE (fake replica handles, deterministic): the
   ``FleetSupervisor`` sweep detects a death, restarts with seeded
   exponential backoff, and quarantines a crash-looper behind the
   supervisor-level breaker (N restarts inside the window).  Every
   transition lands in ``restart_log`` — wall-clock free, so the same
   seed replays the same story.  An operator ``release`` lifts the
   quarantine.
2. SIGTERM DRAIN (two real in-process engines on the migration wire):
   a "replica" with live mid-decode streams is told to retire.
   ``EngineServer.drain_to_peers`` flips ``/readyz`` to draining,
   ``migrate_out``s every live stream to a healthy peer, and the
   blocked clients get their COMPLETE responses — token-identical to
   an undrained oracle, zero tokens lost, zero tokens twice.  The
   handoffs are first-class ``drain.migrate`` spans, rendered the way
   ``tools/trace_view.py --wall`` breaks them out.

The real-process twin (spawned fleet + kill storm) lives in
``tests/test_supervisor.py`` (slow lane).

Run: python examples/serving_selfhealing.py
"""
import json
import os
import sys
import threading
import time
import urllib.request

# allow running as `python examples/<script>.py` from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import (Engine, EngineServer, FleetSupervisor,
                                SupervisorPolicy)


def _load_trace_view():
    """tools/ is scripts, not a package — load trace_view by path."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_view.py")
    spec = importlib.util.spec_from_file_location("trace_view", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class DemoHandle:
    """Scriptable replica handle (the supervisor contract: alive /
    exit_code / kill / spawn / probe_live) — process-free, so the
    lifecycle demo is instant and fully deterministic."""

    def __init__(self, name, crashloop=False):
        self.name = name
        self.crashloop = crashloop   # every respawn dies on boot
        self._alive = True
        self._rc = None
        self.spawns = 0

    def alive(self):
        return self._alive

    def exit_code(self):
        return self._rc

    def kill(self):
        self._alive, self._rc = False, -9

    def die(self, rc=-9):
        self._alive, self._rc = False, rc

    def spawn(self, incarnation):
        self.spawns += 1
        if self.crashloop:
            self._alive, self._rc = False, 23   # exit-on-boot
        else:
            self._alive, self._rc = True, None

    def probe_live(self, timeout_s):
        if not self._alive:
            raise OSError("connection refused")
        return {"live": True}


def main():
    # -- 1. the supervisor lifecycle, deterministically ----------------
    print("1. supervisor: death -> seeded backoff -> restart; "
          "crash-loop -> quarantine -> release")
    handles = [DemoHandle("steady"), DemoHandle("looper",
                                                crashloop=True)]
    pol = SupervisorPolicy(backoff_base_s=1.0, backoff_cap_s=8.0,
                           backoff_jitter=0.5, boot_grace_s=0.0,
                           crashloop_window_s=100.0,
                           crashloop_threshold=3, seed=7)
    sup = FleetSupervisor({h.name: h for h in handles}, policy=pol,
                          registry=monitor.StatRegistry())
    # one ordinary death: restarted after one seeded backoff delay
    handles[0].die()
    now = 0.0
    sup.poll_once(now=now)                     # death observed
    st = sup.status()["replicas"]["steady"]
    while st["state"] != "up":
        now += 0.25
        sup.poll_once(now=now)
        st = sup.status()["replicas"]["steady"]
    print(f"   'steady' died once -> back up at t={now:.2f}s "
          f"(jittered backoff, seed={pol.seed}; same seed, same delay)")
    # the crash-looper: every respawn exits on boot until quarantined
    handles[1].die(23)
    while "looper" not in sup.quarantined():
        now += 0.25
        sup.poll_once(now=now)
    print(f"   'looper' exit(23) on every boot -> QUARANTINED after "
          f"{handles[1].spawns} futile restart(s) "
          f"(threshold={pol.crashloop_threshold} in "
          f"{pol.crashloop_window_s:.0f}s)")
    print(f"   supervisor.restarts_total = "
          f"{int(sup.registry.get('supervisor.restarts_total').value)}"
          f", quarantined = {sup.quarantined()}")
    handles[1].crashloop = False               # "the operator fixed it"
    sup.release("looper")
    now += 0.25
    sup.poll_once(now=now)
    print(f"   release('looper') -> state "
          f"{sup.status()['replicas']['looper']['state']} "
          f"(window reset; the breaker re-arms)")
    for ev in sup.restart_log:
        print(f"     log {ev}")

    # -- 2. SIGTERM drain: retire a replica without losing a token ----
    print("\n2. SIGTERM drain: live mid-decode streams migrate to a "
          "peer, token-identical")
    paddle.seed(0)
    model = GPTModel.from_config("tiny", dropout=0.0)
    model.eval()
    vocab = int(model.embeddings.word_embeddings.weight.shape[0])
    rng = np.random.RandomState(0)
    n_new = 32
    prompts = [rng.randint(0, vocab, (16,)).tolist() for _ in range(2)]

    def mk_engine():
        return Engine(model, num_slots=4, max_seq_len=64,
                      kv_block_size=8,
                      registry=monitor.StatRegistry())

    refs = []
    oracle = mk_engine()
    for p in prompts:
        r = oracle.submit(p, max_new_tokens=n_new)
        oracle.run_until_idle()
        refs.append(r.result(timeout=5).tolist())

    src, dst = mk_engine(), mk_engine()
    with EngineServer(dst) as peer, \
            EngineServer(src, peers=[peer.address],
                         incarnation=1) as victim:
        results = [None] * len(prompts)

        def client(k):
            req = urllib.request.Request(
                victim.address + "/generate",
                data=json.dumps({"prompt": prompts[k],
                                 "max_new_tokens": n_new}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120.0) as resp:
                results[k] = json.loads(resp.read())

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(len(prompts))]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline \
                and len(src.live_request_ids()) < len(prompts):
            time.sleep(0.005)
        # what main() does on SIGTERM — called directly here so the
        # demo works without spawning a process to signal
        acct = victim.drain_to_peers()
        for t in threads:
            t.join(timeout=120.0)
        print(f"   drain: migrated={acct['migrated']} "
              f"fallback={acct['fallback']} "
              f"lost_tokens={acct['lost_tokens']}")
        for k, out in enumerate(results):
            assert out["ids"] == refs[k], "stream diverged"
        migrated = sum(1 for out in results if out.get("migrated"))
        print(f"   {len(prompts)} blocked clients: every response "
              f"complete and token-identical to the undrained oracle "
              f"({migrated} assembled on the peer)")
        with urllib.request.urlopen(victim.address + "/healthz",
                                    timeout=5.0) as r:
            info = json.loads(r.read())
        print(f"   victim /healthz: draining={info['draining']} "
              f"incarnation={info['incarnation']} "
              f"drain_migrations_total="
              f"{info['drain_migrations_total']}")
        trace = src.tracer.chrome_trace()

    tv = _load_trace_view()
    w = tv.wall_summary(trace["traceEvents"])
    print("\ndrain handoffs in the victim's trace "
          "(tools/trace_view.py --wall):")
    print(f"   drain.migrate {w['drain_migrate_ms']:.3f} ms over "
          f"{w['drain_migrations']} stream(s)")
    print("\nthe fleet heals itself; no request ever notices.")


if __name__ == "__main__":
    main()
