"""Multi-host launcher.

Reference parity: ``python -m paddle.distributed.launch``
(``fleet/launch.py:334``) which spawns one process per GPU and wires the
PADDLE_* env contract, with abort-on-failure monitoring
(``launch_utils.py:526``).

TPU-native design: ONE process per host drives all local chips (SPMD), so
the launcher's job collapses to: set the env contract, call
``jax.distributed.initialize`` (which replaces the TCP ncclUniqueId
bootstrap), and exec the training script.  For single-host multi-chip there
is nothing to spawn at all.  Usage:

    python -m paddle_tpu.distributed.launch --nnodes N --node_rank I \
        --master ADDR:PORT train.py [args...]

``--nproc_per_node`` > 1 additionally spawns that many *local* worker
processes (CPU meshes, multi-client simulations, and the reference's
multi-process test idiom — test_dist_base.py:668) and monitors them with
the reference's abort-all watch loop: the first nonzero child exit
terminates every other worker and the launcher exits with that code.
"""
from __future__ import annotations

import argparse
import os
import runpy
import signal
import socket
import subprocess
import sys
import time


def _reserve_port():
    """Bind an OS-assigned port and KEEP the socket open so no
    concurrent process can grab it while the launcher prepares the
    job.  The caller closes it at the last moment before spawning (the
    coordinator bind lives in a child, and two sockets cannot hold one
    port, so a residual close-to-child-bind window remains — narrowed,
    not closed; concurrent multi-launch jobs should pass an explicit
    --master).  SO_REUSEADDR lets the child's bind succeed immediately
    despite the just-closed probe.  Returns the bound socket (port via
    ``sock.getsockname()[1]``)."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s


def _free_port():
    """Probe-and-close port pick — RACY by construction (another
    process can take the port before the caller binds).  Kept for
    callers that tolerate the race; the launcher itself reserves via
    ``_reserve_port`` and holds the socket until workers start.
    Concurrent multi-launch jobs should pass an explicit --master."""
    s = _reserve_port()
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(base=None, platform=None, device_count=None):
    """Per-worker env contract for spawned processes: propagate the
    parent's JAX platform selection explicitly (children of a CPU-mesh
    simulation must not auto-pick a TPU the parent deliberately
    avoided) and force a virtual host-device pool when the worker
    needs an N-device mesh on CPU.  ``device_count`` APPENDS the
    ``--xla_force_host_platform_device_count`` flag unless the flags
    already carry one — an explicit operator setting wins."""
    env = dict(base if base is not None else os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if device_count:
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{int(device_count)}").strip()
    return env


class ServingFleet:
    """Handle over a spawned N-process serving fleet (one
    ``serving.httpd`` replica per process, each replica itself
    mesh-sharded when ``mp > 1``).  ``urls`` index-aligns with
    ``procs``; ``stop()`` terminates everything (idempotent).

    A fleet spawned by ``spawn_serving_fleet`` also remembers each
    replica's spawn command, env, and log path, so ``respawn(i)`` can
    bring a dead replica back ON THE SAME URL — the supervisor tier's
    restart primitive (httpd's HTTPServer binds with SO_REUSEADDR, so
    the port is immediately rebindable after the old process dies)."""

    def __init__(self, procs, urls, logs, cmds=None, env=None,
                 log_paths=None):
        self.procs = procs
        self.urls = urls
        # index-aligned with procs when per-replica logs exist (None
        # entries once a kill() released them); empty otherwise
        self._logs = list(logs)
        self._cmds = list(cmds) if cmds is not None else None
        self._env = dict(env) if env is not None else None
        self._log_paths = (list(log_paths) if log_paths is not None
                           else [None] * len(procs))

    def alive_count(self):
        """Replicas whose process is currently up (poll() is None) —
        the supervisor's capacity view."""
        return sum(1 for p in self.procs if p.poll() is None)

    def kill(self, i, sig=signal.SIGKILL):
        """Hard-kill replica ``i`` (failover tests / chaos): the
        router sees a refused socket, not a graceful drain.  The
        child is REAPED here (waited on) and its log handle closed
        immediately — a chaos storm that kills half the fleet must
        not accumulate zombies or leaked file descriptors while the
        surviving replicas keep serving.  A SIGSTOP-wedged child is
        killable too: SIGKILL terminates even stopped processes."""
        p = self.procs[i]
        if p.poll() is None:
            try:
                p.send_signal(sig)
            except ProcessLookupError:
                pass
        p.wait()
        if i < len(self._logs) and self._logs[i] is not None:
            self._logs[i].close()
            self._logs[i] = None

    def respawn(self, i, incarnation=None, extra_args=()):
        """Restart replica ``i`` on its ORIGINAL port/URL with a fresh
        process.  The old process must already be dead (``kill(i)``
        it first if not — respawning over a live child would orphan
        it).  ``incarnation`` replaces (or appends) the child's
        ``--incarnation`` flag so the new process advertises its
        identity on ``/healthz`` and the router registry can tell a
        successor from its dead predecessor.  The log file reopens in
        APPEND mode at the same path, so one file tells the replica's
        whole multi-incarnation story.  Does NOT wait for readiness —
        the caller (supervisor) owns the boot-grace policy."""
        if self._cmds is None:
            raise RuntimeError(
                "this fleet was not built by spawn_serving_fleet: "
                "no recorded spawn command to respawn from")
        p = self.procs[i]
        if p.poll() is None:
            raise RuntimeError(
                f"replica {i} is still alive (pid {p.pid}); kill it "
                "before respawning")
        p.wait()  # reap (idempotent) — never leave a zombie behind
        cmd = list(self._cmds[i])
        if incarnation is not None:
            if "--incarnation" in cmd:
                k = cmd.index("--incarnation")
                cmd[k + 1] = str(int(incarnation))
            else:
                cmd += ["--incarnation", str(int(incarnation))]
            self._cmds[i] = list(cmd)
        cmd += list(extra_args)
        if i < len(self._logs) and self._logs[i] is not None:
            self._logs[i].close()
            self._logs[i] = None
        path = (self._log_paths[i]
                if i < len(self._log_paths) else None)
        if path:
            f = open(path, "a")
            while len(self._logs) <= i:
                self._logs.append(None)
            self._logs[i] = f
            self.procs[i] = subprocess.Popen(
                cmd, env=self._env, stdout=f,
                stderr=subprocess.STDOUT)
        else:
            self.procs[i] = subprocess.Popen(
                cmd, env=self._env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
        return self.urls[i]

    def stop(self, grace=5.0):
        """Escalating shutdown: SIGTERM every live child (a drain-
        aware replica flips ``/readyz`` to draining and migrates its
        live streams out), wait up to ``grace`` for voluntary exits,
        then SIGKILL whatever remains — including SIGSTOP-wedged
        children, which never see the SIGTERM (it stays pending while
        they are stopped) but die to SIGKILL regardless — and REAP
        every child unconditionally.  Log handles close in a finally:
        after a storm there must be no zombies and no leaked fds even
        if a wait() raises.  Idempotent."""
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.terminate()          # SIGTERM: drain deadline
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + grace
        try:
            for p in self.procs:
                while p.poll() is None \
                        and time.monotonic() < deadline:
                    time.sleep(0.05)
                if p.poll() is None:
                    try:
                        p.kill()           # escalation: SIGKILL
                    except ProcessLookupError:
                        pass
                p.wait()   # reap even the already-dead children
        finally:
            for f in self._logs:
                if f is not None:
                    f.close()
            self._logs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def spawn_serving_fleet(n, config="tiny", mp=1, dp=1, platform=None,
                        seed=0, num_slots=4, max_seq_len=64,
                        kv_block_size=None, spec_k=None,
                        prefill_chunk=None, roles=None, log_dir=None,
                        ready_timeout_s=120.0, peers=False,
                        extra_args=()):
    """Spawn an N-process serving replica fleet and wait until every
    replica answers ``/healthz`` — the real-process twin of the
    in-process router tests.  Each worker is
    ``python -m paddle_tpu.serving.httpd`` with:

    * a port reserved HERE via ``_reserve_port`` and held until the
      moment of spawn (the training launcher's hold-until-spawn
      pattern, reused) — so the returned URLs are race-free against
      concurrent launches, modulo the unavoidable close-to-child-bind
      window the training path documents;
    * the per-worker env contract from ``_worker_env``: the JAX
      platform propagated explicitly and, for ``mp * dp > 1`` on
      CPU, a forced virtual device pool sized to the replica's FULL
      (mp x dp) mesh — a worker must never silently serve a 1-device
      mesh because the parent's XLA_FLAGS did not reach it;
    * the SAME ``--seed``, so greedy failover across replicas is
      token-identical.

    ``roles`` optionally assigns each replica a serving role — an
    index-aligned list of ``mixed`` / ``prefill`` / ``decode`` passed
    through as ``--role`` (the disaggregated fleet shape; the router
    reads it back from each replica's ``/healthz``).

    ``peers=True`` passes every OTHER replica's URL as ``--peer`` to
    each child (all ports are reserved up front, so the full URL set
    is known before any spawn) — the SIGTERM drain wiring: a replica
    told to exit migrates its live decoding streams to a healthy peer
    instead of dropping them.

    ``platform`` defaults to the parent's own ``JAX_PLATFORMS`` (the
    test environment exports ``cpu``).  A chip belongs to one process
    at a time and this launcher knows only the virtual CPU pool: it
    cannot give each replica a chip of its own, so every worker would
    claim every chip and all but the first would fail or hang.  More
    than one replica on anything but an explicit ``cpu`` platform is
    therefore refused HERE, with a ValueError before any process
    starts, rather than discovered at ``ready_timeout_s``.  One
    replica (its mesh spanning the host's chips) may run on the chip,
    from a parent that has not touched JAX itself.  Replicas per chip
    is ROADMAP R6.

    Returns a ``ServingFleet``; raises RuntimeError (after killing
    the partial fleet) if any replica fails to become ready."""
    import urllib.request

    plat = platform or os.environ.get("JAX_PLATFORMS")
    if int(n) > 1 and plat != "cpu":
        raise ValueError(
            f"spawn_serving_fleet: {int(n)} replica processes on "
            f"platform {plat or '<JAX default>'!r} would each claim "
            "every chip of this host (one process per chip; the "
            "launcher cannot hand out chips — ROADMAP R6).  Pass "
            "platform='cpu' or export JAX_PLATFORMS=cpu for a CPU "
            "fleet, or spawn one replica")
    if roles is not None and len(roles) != int(n):
        raise ValueError(
            f"roles must have one entry per replica: got "
            f"{len(roles)} for n={n}")
    procs, urls, logs, cmds, log_paths = [], [], [], [], []
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    need = int(mp) * int(dp)
    env = _worker_env(platform=platform,
                      device_count=need if need > 1 else None)
    reserved = [_reserve_port() for _ in range(int(n))]
    all_urls = [f"http://127.0.0.1:{s.getsockname()[1]}"
                for s in reserved]
    try:
        for i, sock in enumerate(reserved):
            port = sock.getsockname()[1]
            cmd = [sys.executable, "-m", "paddle_tpu.serving.httpd",
                   "--config", str(config), "--mp", str(int(mp)),
                   "--dp", str(int(dp)),
                   "--port", str(port), "--seed", str(int(seed)),
                   "--num-slots", str(int(num_slots)),
                   "--max-seq-len", str(int(max_seq_len))]
            if kv_block_size is not None:
                cmd += ["--kv-block-size", str(int(kv_block_size))]
            if spec_k is not None:
                cmd += ["--spec-k", str(int(spec_k))]
            if prefill_chunk is not None:
                cmd += ["--prefill-chunk", str(int(prefill_chunk))]
            if roles is not None:
                cmd += ["--role", str(roles[i])]
            if peers:
                for j, peer_url in enumerate(all_urls):
                    if j != i:
                        cmd += ["--peer", peer_url]
            cmd += list(extra_args)
            cmds.append(list(cmd))
            # release the reservation at the last moment (httpd's
            # HTTPServer binds with SO_REUSEADDR, so the just-closed
            # probe never blocks the child's bind)
            sock.close()
            if log_dir:
                path = os.path.join(log_dir, f"replica.{i}.log")
                f = open(path, "w")
                logs.append(f)
                log_paths.append(path)
                procs.append(subprocess.Popen(
                    cmd, env=env, stdout=f,
                    stderr=subprocess.STDOUT))
            else:
                log_paths.append(None)
                procs.append(subprocess.Popen(
                    cmd, env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            urls.append(f"http://127.0.0.1:{port}")
    except BaseException:
        for s in reserved:
            try:
                s.close()
            except OSError:
                pass
        for p in procs:
            p.kill()
            p.wait()  # reap now; the parent may be long-lived
        for f in logs:
            f.close()
        raise
    fleet = ServingFleet(procs, urls, logs, cmds=cmds, env=env,
                         log_paths=log_paths)
    deadline = time.monotonic() + float(ready_timeout_s)
    pending = dict(enumerate(urls))
    while pending:
        for i, url in list(pending.items()):
            if procs[i].poll() is not None:
                fleet.stop()
                raise RuntimeError(
                    f"replica {i} ({url}) exited rc="
                    f"{procs[i].returncode} before becoming ready"
                    + (f"; see {log_dir}/replica.{i}.log"
                       if log_dir else ""))
            try:
                with urllib.request.urlopen(url + "/healthz",
                                            timeout=1.0):
                    pending.pop(i)
            except Exception:
                pass
        if pending:
            if time.monotonic() > deadline:
                fleet.stop()
                raise RuntimeError(
                    f"fleet not ready after {ready_timeout_s}s: "
                    f"replicas {sorted(pending)} never answered "
                    "/healthz")
            time.sleep(0.2)
    return fleet


def _spawn_and_watch(args):
    """Spawn ``nproc_per_node`` local workers and watch them
    (reference launch_utils.py:526 ``watch_local_trainers``): any child
    failure aborts the whole job; the launcher's exit code is the first
    failing child's."""
    world = args.nnodes * args.nproc_per_node
    reserved = None
    if args.master:
        master = args.master
    else:
        # hold the probed port until the workers are spawning — a
        # close-then-rebind window here meant a concurrent launch could
        # steal the master port (flaky multi-launch failures)
        reserved = _reserve_port()
        master = f"127.0.0.1:{reserved.getsockname()[1]}"
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)

    procs = []
    logs = []
    if reserved is not None:
        # release as late as possible (the port cannot stay held: rank
        # 0's coordinator bind happens inside the first child, and two
        # sockets cannot bind one port).  The interpreter-boot window
        # before that bind is unavoidable without an explicit --master;
        # SO_REUSEADDR on the probe keeps the child's bind instant
        reserved.close()
    for local in range(args.nproc_per_node):
        rank = args.node_rank * args.nproc_per_node + local
        # per-worker env contract: propagate the platform choice
        # explicitly and force the virtual device pool when the
        # worker runs an N-device CPU mesh — a child that silently
        # booted 1 CPU device used to fail mesh construction with an
        # unhelpful "requires N devices, have 1"
        env = _worker_env(
            device_count=getattr(args, "devices_per_proc", None))
        env["PADDLE_TRAINERS_NUM"] = str(world)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINER_ENDPOINTS"] = master
        env["PADDLE_LOCAL_RANK"] = str(local)
        # children re-enter this file in single-process mode (the
        # env contract above carries the topology)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--nnodes", str(world), "--node_rank", str(rank),
               "--master", master, args.script] + list(args.script_args)
        if args.log_dir:
            f = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "w")
            logs.append(f)
            procs.append(subprocess.Popen(cmd, env=env, stdout=f,
                                          stderr=subprocess.STDOUT))
        else:
            procs.append(subprocess.Popen(cmd, env=env))

    def _terminate_all(sig=signal.SIGTERM, grace=10.0):
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except ProcessLookupError:
                    pass
        deadline = time.monotonic() + grace
        for p in procs:
            while p.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if p.poll() is None:
                p.kill()
                p.wait()

    def _forward(signum, frame):
        _terminate_all()
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, _forward)
    signal.signal(signal.SIGINT, _forward)

    rc = 0
    try:
        while True:
            alive = False
            for p in procs:
                code = p.poll()
                if code is None:
                    alive = True
                elif code != 0:
                    # reference abort-all: one dead trainer kills the job
                    sys.stderr.write(
                        f"launch: local worker pid {p.pid} exited with "
                        f"code {code}; aborting all workers\n")
                    _terminate_all()
                    return code
            if not alive:
                return rc
            time.sleep(0.5)
    finally:
        for f in logs:
            f.close()


def launch_main(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.environ.get("PADDLE_TRAINERS_NUM",
                                                   "1")))
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_TRAINER_ID",
                                                   "0")))
    parser.add_argument("--master",
                        default=os.environ.get("MASTER_ADDR_PORT", ""))
    parser.add_argument("--nproc_per_node", type=int, default=1,
                        help="local worker processes (CPU meshes / "
                             "multi-client simulation); 1 = SPMD "
                             "single-process-per-host")
    parser.add_argument("--log_dir", default=None,
                        help="per-rank workerlog.N files (reference "
                             "launch_utils.py log naming)")
    parser.add_argument("--devices_per_proc", type=int, default=None,
                        help="force each worker's virtual host-device"
                             " pool to this size (CPU mesh "
                             "simulation: appends --xla_force_host_"
                             "platform_device_count per worker unless"
                             " XLA_FLAGS already carries one)")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.nproc_per_node > 1:
        sys.exit(_spawn_and_watch(args))

    os.environ["PADDLE_TRAINERS_NUM"] = str(args.nnodes)
    os.environ["PADDLE_TRAINER_ID"] = str(args.node_rank)
    if args.master:
        os.environ["PADDLE_TRAINER_ENDPOINTS"] = args.master

    if args.nnodes > 1:
        import jax
        jax.distributed.initialize(
            coordinator_address=args.master or None,
            num_processes=args.nnodes, process_id=args.node_rank)

    sys.argv = [args.script] + list(args.script_args)
    runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    launch_main()
