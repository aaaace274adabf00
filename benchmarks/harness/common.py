"""What every kind of cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the device, the compile cache, and the judgement of
``correct``."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys

from . import counts, reducers, weights

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def say(*parts):
    print(*parts, flush=True)


def merged(base, over):
    """``base`` with ``over`` laid on top, dictionaries merged in
    depth."""
    out = dict(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = v
    return out


def load_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest, workload, rehearse=False):
    """(cell, configuration, path of the traffic mix), each found by the
    name the manifest gives.  A rehearsal lays the ``rehearse`` block of
    the configuration over it (tiny sizes for the CPU)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = merged(cfg, cfg["rehearse"])
    mix_path = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    if not os.path.exists(mix_path):
        raise SystemExit(f"cell {workload}: no traffic mix {mix_path}")
    return cell, cfg, mix_path


def cell_metrics(manifest, workload, group):
    """Names of the ``group`` metrics (``end_to_end`` or ``per_layer``)
    this cell reports: those that list it, and those that list
    nothing."""
    return [m["name"] for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def end_to_end(cell, values):
    """{name: {"value", "unit"}} of the end-to-end metrics the manifest
    gives this cell, out of what the runner measured (``values`` maps a
    name to a number or None)."""
    manifest = load_manifest()
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    return {n: {"value": float(values[n]), "unit": units[n]}
            for n in cell_metrics(manifest, cell["name"], "end_to_end")
            if values.get(n) is not None}


def layer_metrics(cell, src, dump=None):
    """The cell's per-layer metrics, each read by its own file."""
    if dump:
        with open(dump, "w") as f:
            json.dump(src, f)
    manifest = load_manifest()
    files = reducers.load_metric_files(
        os.path.join(BENCH_DIR, "layer_metrics"))
    wanted = cell_metrics(manifest, cell["name"], "per_layer")
    missing = [n for n in wanted if n not in files]
    if missing:
        raise SystemExit(f"no file under layer_metrics/ for {missing}")
    return reducers.reduce_all(files, wanted, src)


def _load_beside(name):
    """The module ``configs/<name>.py``, loaded once a process."""
    modname = "bench_configs_" + name
    if modname not in sys.modules:
        path = os.path.join(BENCH_DIR, "configs", name + ".py")
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return sys.modules[modname]


def load_reference(cfg):
    """The plain reference that sits beside the configuration's file."""
    return _load_beside(cfg["reference"])


def load_program(cfg):
    """The program's side of the configuration, beside its file: how the
    program's model is built (``build``), which leaves the benchmark
    seeds (``leaf_specs``), and the work an ideal chip must do for it.
    The only files of the benchmark that import a model class."""
    return _load_beside(cfg["program"])


def seeded_weights(cfg, seed, names=None):
    """The configuration's seeded leaves ({name: array of its dtype};
    ``names`` picks a subset), as its program file lists them."""
    return weights.make_weights(
        seed, load_program(cfg).leaf_specs(cfg["dims"]), cfg["dtype"],
        names)


# -- device, cache, scratch ------------------------------------------------

def scratch_dir():
    """A fixed directory inside the checkout for what a run leaves
    behind (profiles); ``.gitignore`` lists it."""
    d = os.path.join(ROOT, ".bench_scratch")
    os.makedirs(d, exist_ok=True)
    return d


def clear_dir(d):
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d, exist_ok=True)


def start_profile(directory):
    """Start the device trace into an emptied ``directory``: device
    operations and ``TraceAnnotation`` spans, without the Python call
    tracer (hundreds of thousands of events a second, which slow the
    host that is being measured)."""
    import jax
    clear_dir(directory)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


# what a traced run's result line says of the device beside JAX's own
# account of it, out of ``xplane.reduce``: ``window_s`` is the larger of
# ``host_window_s`` and ``extent_s``
DEVICE_WINDOW = ("busy_s", "window_s", "host_window_s", "extent_s")


def enable_cache():
    """JAX's persistent compile cache at the program's fixed place (the
    variable if set, else ``.jax_cache/`` in the checkout), keeping every
    program however small or quick to compile."""
    import jax
    from paddle_tpu.core.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(dev, rehearse=False):
    """The device's published peaks.  A rehearsal has no device worth a
    peak: it borrows the v5e's row to exercise the arithmetic, and its
    result line says it is a rehearsal."""
    return counts.peaks_for("TPU v5 lite" if rehearse else dev["kind"])


def memory_peak_bytes():
    """Peak bytes in use on the fullest device, as the backend reports
    it (0 where it reports nothing, as on the CPU)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts JAX's own backend compilations (``jax.monitoring``), so a
    window can show that nothing compiled in it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


# -- correct -------------------------------------------------------------------

def judge(numbers):
    """Print each number compared beside its limit.  Returns (correct,
    compared): True when every one is inside (a limit of None never
    passes), and {name: {"value", "limit"}} for the result line."""
    ok, compared = True, {}
    for name, value, limit in numbers:
        inside = limit is not None and value == value and value <= limit
        ok = ok and inside
        # (a value that is no number would make the line no JSON)
        compared[name] = {"limit": limit, "value": (
            value if math.isfinite(value) else repr(value))}
        say(f"compared {name}: {value!r} limit {limit!r} "
            f"{'ok' if inside else 'NOT OK'}")
    return ok, compared
