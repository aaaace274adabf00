"""A training cell: the program's ``TrainStep`` over its model, fed a
fresh host batch every step.  Set-up builds the one compiled step with
its state, drives it through its first steps (which the reference
follows afterwards) and hands the same object to the window."""
from __future__ import annotations

import contextlib
import gc
import json
import os
import time

import numpy as np

from . import common


class Batches:
    """Token ids from a Zipf(a) unigram over the vocabulary, a fresh
    [batch, seq + 1] block per call, from the seed."""

    def __init__(self, mix, vocab, seed):
        self.rng = np.random.default_rng([int(seed), 5])
        self.shape = (int(mix["batch"]), int(mix["seq_len"]) + 1)
        p = 1.0 / np.arange(1, vocab + 1) ** float(mix["ids"]["a"])
        self.cdf = np.cumsum(p / p.sum())

    def next(self):
        ids = np.searchsorted(self.cdf, self.rng.random(self.shape))
        ids = np.minimum(ids, len(self.cdf) - 1).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]


def _leaf_norms(tree_a, tree_b=None, scale=1.0):
    """{name: norm of (a - b) * scale} in float32, one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            (a[k].astype(jnp.float32)
             - (b[k].astype(jnp.float32) if b is not None else 0.0))
            * scale))) for k in a}
    return {k: float(v) for k, v in norms(tree_a, tree_b).items()}


def worst_leaf_gap(got, want):
    """Largest gap between two norms of one leaf, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def reference_numbers(cfg, mix, seed, n_steps, precision="highest"):
    """Losses of the first steps, per-leaf norms of the first gradient
    and of the parameters' change, from the plain reference."""
    import jax
    ref = common.load_reference(cfg)
    dims = cfg["dims"]
    feed = Batches(mix, dims["vocab_size"], seed)
    batches = [feed.next() for _ in range(n_steps)]
    w = common.seeded_weights(cfg, seed)
    p0 = ref.stack_params(w, dims)
    del w
    losses, g1, p = ref.train_steps(
        jax.tree_util.tree_map(lambda a: a + 0, p0), batches, dims,
        cfg["optimizer"], rows_per_block=int(cfg["check"]["rows_per_block"]),
        precision=precision, store=cfg["dtype"])
    names = ref.unstack_names(dims)

    def flat(tree):
        return {n: (tree[key] if layer is None else tree[key][layer])
                for n, key, layer in names}
    return {"losses": [float(x) for x in losses],
            "grad_norms": _leaf_norms(flat(g1)),
            "update_norms": _leaf_norms(flat(p), flat(p0))}


def compare(got, want, limits):
    """[(name, value, limit)] of the three numbers that follow the
    reference."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                want["losses"])]
    g, where = worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    common.say(f"first gradient: worst leaf {where}")
    u, where = worst_leaf_gap(got["update_norms"], want["update_norms"])
    common.say(f"parameters' change: worst leaf {where}")
    return [("loss_gap_max", max(gaps), limits["loss_gap_max"]),
            ("grad_norm_gap", g, limits["grad_norm_gap"]),
            ("update_norm_gap", u, limits["update_norm_gap"])]


def run(cell, cfg, mix_path, args, t_proc0):
    import jax
    with open(mix_path) as f:
        mix = common.merged(json.load(f), args.mix_override)
    dims, limits = cfg["dims"], cfg["limits"]
    n_check = int(mix["check_steps"])
    dev = common.device_info()
    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])

    if args.control:
        # the control: the reference in the precision below, put in the
        # program's place; no window
        common.say(f"CONTROL {args.control}: the reference at "
                   f"{cfg['controls'][args.control]['precision']}")
        got = reference_numbers(
            cfg, mix, args.seed, n_check,
            precision=cfg["controls"][args.control]["precision"])
        want = reference_numbers(cfg, mix, args.seed, n_check)
        correct, compared = common.judge(compare(got, want, limits))
        return {"correct": correct, "attempted": n_check,
                "failed": 0, "metrics": {}, "device": dict(
                    dev, memory_peak_bytes=common.memory_peak_bytes()),
                "compared": compared}

    from paddle_tpu import optimizer
    from paddle_tpu.parallel.train_step import TrainStep
    compiles = common.CompileCounter()
    program = common.load_program(cfg)
    model = program.build(cfg, args.seed)
    model.train()
    o = cfg["optimizer"]
    opt = optimizer.AdamW(learning_rate=o["learning_rate"],
                          beta1=o["beta1"], beta2=o["beta2"],
                          epsilon=o["epsilon"],
                          weight_decay=o["weight_decay"],
                          parameters=model.parameters())
    step = TrainStep(model, opt, loss_fn=None)
    feed = Batches(mix, dims["vocab_size"], args.seed)
    spans = []

    def one(annotate=False):
        """The window's own call and feed: a host batch, the step, a
        wait for its loss."""
        t0 = time.perf_counter()
        x, y = feed.next()
        t1 = time.perf_counter()
        with (jax.profiler.TraceAnnotation("train.step") if annotate
              else contextlib.nullcontext()):
            loss = float(step.step([x, y]).numpy())
        t2 = time.perf_counter()
        for name, a, b in (("batch.make", t0, t1), ("train.step", t1, t2)):
            spans.append({"name": name, "ph": "X", "ts": a * 1e6,
                          "dur": (b - a) * 1e6,
                          "args": {"profiled": int(annotate)}})
        return loss

    # -- set-up: the first steps, whose numbers the reference follows ----
    got = {"losses": [one()]}
    got["grad_norms"] = _leaf_norms(
        {k: s["moment1"] for k, s in step.opt_state.items()},
        scale=1.0 / (1.0 - o["beta1"]))
    got["losses"] += [one() for _ in range(n_check - 1)]
    got["update_norms"] = _leaf_norms(
        dict(step.params), common.seeded_weights(cfg, args.seed))
    common.say(f"first losses: {got['losses']}")
    spans.clear()

    # -- the window ---------------------------------------------------------
    p_len = min(float(cfg["check"].get("profile_s", 5.0)),
                args.seconds * 0.5)
    p_at = (args.seconds - p_len) / 2.0
    prof_dir = os.path.join(common.scratch_dir(), "profile")
    # t: [start, stop] of the profile, each read INSIDE the capture (after
    # ``start_profile`` returns, before ``stop_trace`` is called): the
    # capture contains it, and ``xplane.reduce`` widens the window it
    # reports to hold every device event the capture recorded
    prof = {"t": None, "steps": 0}
    c0 = compiles.count
    losses = []
    w0 = time.monotonic()
    setup_s = w0 - t_proc0
    while True:
        now = time.monotonic() - w0
        if now >= args.seconds:
            break
        if args.trace and prof["t"] is None and now >= p_at:
            common.start_profile(prof_dir)
            prof["t"] = [time.monotonic(), None]
        profiling = bool(prof["t"]) and prof["t"][1] is None
        losses.append(one(annotate=profiling))
        if profiling:
            prof["steps"] += 1
            if time.monotonic() - prof["t"][0] >= p_len:
                prof["t"][1] = time.monotonic()
                jax.profiler.stop_trace()
    elapsed = time.monotonic() - w0
    if prof["t"] and prof["t"][1] is None:
        prof["t"][1] = time.monotonic()
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - c0
    memory_peak = common.memory_peak_bytes()
    train_tok_s = len(losses) * tokens_per_step / elapsed

    # -- correctness: the reference follows the first steps ---------------
    del step, opt, model
    gc.collect()
    t_ref = time.monotonic()
    want = reference_numbers(cfg, mix, args.seed, n_check)
    common.say(f"reference losses: {want['losses']} "
               f"({time.monotonic() - t_ref:.1f}s)")
    bad = sum(not np.isfinite(x) for x in losses)
    correct, compared = common.judge(compare(got, want, limits) + [
        ("window_losses_not_finite", bad, 0),
        ("window_loss_last_minus_first", losses[-1] - losses[0], 0.0)])
    common.say(f"compiles in the window: jax backend compiles "
               f"+{compiles_in_window}")

    device = dict(dev, memory_peak_bytes=memory_peak)
    breakdown = None
    if not args.trace:
        metrics = common.end_to_end(cell, {"setup_s": setup_s,
                                           "train_tok_s": train_tok_s})
    else:
        from . import xplane
        p0, p1 = prof["t"]
        dev_trace = xplane.reduce(
            xplane.find_trace(prof_dir), {"train.step"}, window_s=p1 - p0)
        # the profiler's start and stop stall the loop for seconds: the
        # rate that model-FLOPs utilisation is taken from leaves them and
        # the profiled steps out
        clear = [e["dur"] for e in spans if not e["args"]["profiled"]]
        train_tok_s = (len(clear) // 2) * tokens_per_step \
            / (sum(clear) * 1e-6)
        src = {
            "spans": spans,
            "counters": {"delta": {
                "jax.backend_compiles": compiles_in_window}},
            "device": dev_trace,
            "work": {"steps": prof["steps"], "batch": int(mix["batch"]),
                     "seq_len": int(mix["seq_len"])},
            "ctx": {"cfg": cfg,
                    "peaks": common.peaks(dev, args.rehearse),
                    "chips": cell["chips"],
                    "train_tok_s": train_tok_s,
                    "flops_per_token": program.train_flops_per_token(
                        dims, int(mix["seq_len"])),
                    "window_s": elapsed,
                    "memory_peak_bytes": memory_peak},
        }
        metrics = common.layer_metrics(cell, src, args.dump_sources)
        device.update({k: dev_trace[k] for k in common.DEVICE_WINDOW})
        breakdown = {"device_ops": dev_trace["device_ops"],
                     "idle_gaps": dev_trace["idle_gaps"]}
        common.say(f"traced run: train_tok_s={train_tok_s:.1f} outside "
                   "the profiled interval")
    return {"correct": correct, "attempted": len(losses), "failed": bad,
            "metrics": metrics, "device": device, "breakdown": breakdown,
            "compared": compared}
