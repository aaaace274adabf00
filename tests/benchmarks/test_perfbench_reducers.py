"""The reducers: each kind on a hand-made source, then every serving
metric's own file on a recorded engine trace, and the device-trace
reducer on traces recorded on a v5e.

A metric file may name the recording it is checked on (its ``recorded``
key: a dump under ``data/``, made once the engine had the spans it
reads); ``test_perfbench_recorded.py`` checks it there, and it has no
case on the older recording here."""
import json
import os
import random
import statistics

import pytest

from bench_paths import BENCH, DATA, config, manifest
from harness import common, counts, reducers, xplane

METRICS = reducers.load_metric_files(os.path.join(BENCH, "layer_metrics"))


def span(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def inst(name, ts, **args):
    return {"name": name, "ph": "i", "ts": ts, "args": args}


SRC = {
    "spans": [
        span("tick", 0, 1000, batch=0), span("tick", 2000, 3000, batch=2),
        span("tick", 6000, 5000, batch=4),
        span("decode.dispatch", 2100, 100, batch=2),
        span("decode.dispatch", 6100, 100, batch=4),
        span("prefill.chunk", 100, 500_000), span("prefill.chunk", 7000,
                                                  500_000),
        inst("req.queued", 1000, req=1), inst("req.queued", 2000, req=2),
        inst("req.admitted", 4000, req=1), inst("req.admitted", 9000, req=2),
        inst("req.admitted", 9500, req=3),      # never queued here
    ],
    "counters": {
        "delta": {"serving.prefix_hit_tokens": 300.0,
                  "serving.prefill_tokens": 100.0, "x.compiles": 2.0},
        "peak": {"serving.kv_blocks_in_use": 30.0},
        "last": {"serving.kv_blocks_total": 40.0},
        "profile_delta": {"serving.prefill_tokens": 0.0},
    },
    "client": {"late_ms": [1.0, 2.0, 3.0, 4.0, 50.0]},
    "device": {"busy_s": 2.0, "window_s": 5.0, "by_name": {
        "XLA Modules": {"jit_gpt_fused_decode(11)": 1.5,
                        "jit_gpt_fused_decode(12)": 0.1,
                        "jit_gpt_paged_chunk_prefill(7)": 0.3},
        "XLA Ops": {"fusion f32[32,256,16]": 0.9, "my_kernel": 0.25}}},
    "work": {"tokens_emitted": 64, "live_positions": 64 * 500,
             "prefill_tokens": 0.0, "num_slots": 32,
             "counters": {"serving.prefill_tokens": 0.0}},
    "ctx": {"memory_peak_bytes": 12.5e9},
}


@pytest.mark.parametrize("kind, params, want", [
    ("span_percentile", {"span": "tick", "q": 50,
                         "where": {"batch": {"min": 1}}}, 3.0),
    ("span_percentile", {"span": "tick", "q": 100}, 5.0),
    ("span_percentile", {"span": "absent", "q": 50}, None),
    ("span_arg_mean", {"span": "decode.dispatch", "arg": "batch"}, 3.0),
    ("span_rate", {"counter": "serving.prefill_tokens",
                   "span": "prefill.chunk"}, 100.0),
    ("lifecycle_gap", {"start": "req.queued", "end": "req.admitted",
                       "key": "req", "q": 90}, 7.0),
    ("lifecycle_gap", {"start": "req.queued", "end": "req.admitted",
                       "key": "req", "q": 50}, 3.0),
    ("counter_delta", {"counter": "x.compiles"}, 2.0),
    ("counter_delta", {"counter": "absent"}, None),
    ("counter_delta_ratio",
     {"num": ["serving.prefix_hit_tokens"],
      "den": ["serving.prefix_hit_tokens", "serving.prefill_tokens"]}, 75.0),
    ("gauge_peak", {"gauge": "serving.kv_blocks_in_use",
                    "over": "serving.kv_blocks_total"}, 75.0),
    ("client_percentile", {"series": "late_ms", "q": 95}, 50.0),
    ("client_percentile", {"series": "late_ms", "q": 50}, 3.0),
    ("memory_peak", {}, 12.5),
    ("xplane_idle", {}, 60.0),
    # two fingerprints of one program kind add up: 1.6 of 2.0 s busy
    ("trace_time_share", {"line": "XLA Modules",
                          "match": "^jit_gpt_fused_decode\\("}, 80.0),
    ("trace_time_share", {"line": "XLA Ops", "match": "^my_kernel$"}, 12.5),
    ("trace_time_share", {"line": "XLA Modules", "match": "^jit_absent"},
     None),
    ("trace_time_share", {"line": "No Such Line", "match": "."}, None),
])
def test_reducer_kinds(kind, params, want):
    got = reducers.KINDS[kind](SRC, **params)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_roofline_is_least_time_over_busy_time():
    cfg = config("gpt3-1.3b-serve")
    prog = common.load_program(cfg)
    peaks = counts.peaks_for("TPU v5 lite")
    src = dict(SRC, ctx={"cfg": cfg, "peaks": peaks})
    # two decode steps of 32 tokens: 2 x 2.62 GB + 32,000 positions x
    # 196,608 B = 11.5 GB at 819 GB/s = 14.1 ms, of 2 s busy
    got = reducers.roofline(src, "serve")
    want = (2 * prog.step_weight_bytes(cfg["dims"]) + 32000 * 196608) / 819e9
    assert got == pytest.approx(100 * want / 2.0)
    assert 0.69 < got < 0.72
    # the same 14.1 ms over the decode program's own 1.6 s
    assert reducers.trace_roofline(
        src, "XLA Modules", "^jit_gpt_fused_decode\\(",
        "decode_least_seconds") == pytest.approx(100 * want / 1.6)
    # a count that returns (seconds, bound) is read by its seconds
    assert reducers.trace_roofline(
        src, "XLA Modules", "^jit_gpt_fused_decode\\(",
        "serve_least_seconds") == pytest.approx(100 * want / 1.6)
    assert reducers.trace_roofline(
        src, "XLA Modules", "^jit_absent", "decode_least_seconds") is None
    assert reducers.trace_roofline(
        dict(src, work=None), "XLA Modules", "^jit_gpt_fused_decode",
        "decode_least_seconds") is None
    # training: 19.9 TFLOP a step, 10 steps in 2 s busy = 50.5% of peak
    src = dict(SRC, ctx={"cfg": config("gpt2-medium-train"), "peaks": peaks},
               work={"steps": 10, "batch": 8, "seq_len": 1024})
    assert reducers.roofline(src, "train") == pytest.approx(
        100 * 10 * 19.85e12 / 197e12 / 2.0, rel=0.01)
    assert reducers.roofline(dict(src, work={"steps": 0, "batch": 8,
                                             "seq_len": 1024}),
                             "train") is None
    assert reducers.rate_over_peak(
        {"ctx": {"peaks": peaks, "rate": 30000.0,
                 "f": 2.423e9}}, "rate", "f") == pytest.approx(36.9, abs=0.05)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    out = reducers.reduce_all(
        METRICS, ["idle_share.serve", "tick_p50_ms", "kv_used_peak"],
        {"spans": [], "counters": {}, "device": None, "ctx": {}})
    assert out == {}


# -- the recorded engine trace ---------------------------------------------

with open(os.path.join(DATA, "engine_sources_tiny.json")) as _f:
    RECORDED = json.load(_f)


def _recorded(name):
    m = METRICS[name]
    return reducers.KINDS[m["reducer"]](RECORDED, **m.get("params", {}))


def _x(name):
    return [e for e in RECORDED["spans"]
            if e["name"] == name and e["ph"] == "X"]


@pytest.mark.parametrize("name", sorted(
    n for n, m in METRICS.items()
    if m["moves"] != "train_tok_s" and m["source"] != "device_trace"
    and "recorded" not in m))
def test_serving_metric_files_on_the_recorded_trace(name):
    got = _recorded(name)
    assert got is not None, f"{name} found nothing to read"
    delta = RECORDED["counters"]["delta"]
    if name.endswith(".closed"):      # the same reader, another arrow
        base = METRICS.get(name[:-len(".closed")])
        if base is not None:
            assert (base["reducer"], base["params"]) == \
                (METRICS[name]["reducer"], METRICS[name]["params"])
            assert METRICS[name]["moves"] == "out_tok_s"
        name = name[:-len(".closed")]
    if name == "decode_batch_mean":
        b = [e["args"]["batch"] for e in _x("decode.dispatch")]
        assert got == pytest.approx(sum(b) / len(b))
    elif name == "tick_p50_ms":
        d = sorted(e["dur"] / 1e3 for e in _x("tick")
                   if e["args"].get("batch", 0) >= 1)
        assert d[0] <= got <= d[-1]
        assert got == pytest.approx(statistics.median_low(d))
    elif name == "prefix_hit_share":
        hit, miss = (delta["serving.prefix_hit_tokens"],
                     delta["serving.prefill_tokens"])
        assert got == pytest.approx(100 * hit / (hit + miss))
    elif name == "prefix_evictions":
        assert got == delta["serving.prefix_evictions"] >= 0
    elif name == "queue_wait_p90_ms":
        q = {e["args"]["req"]: e["ts"] for e in RECORDED["spans"]
             if e["name"] == "req.queued"}
        a = {e["args"]["req"]: e["ts"] for e in RECORDED["spans"]
             if e["name"] == "req.admitted"}
        waits = sorted((a[k] - q[k]) / 1e3 for k in q if k in a)
        assert len(waits) == len(q) > 10 and waits[0] >= 0
        assert got == pytest.approx(reducers.percentile(waits, 90))
    elif name == "kv_used_peak":
        assert 0 < got <= 100
    elif name == "compiles_in_window.serve":
        assert got == 0
    elif name == "gen_late_p95_ms":
        assert got == pytest.approx(
            reducers.percentile(RECORDED["client"]["late_ms"], 95))
    elif name == "ttft_p90_ms":
        assert got == pytest.approx(
            reducers.percentile(RECORDED["client"]["ttft_ms"], 90))
    elif name == "hbm_peak.serve":
        assert got == 0        # recorded on the CPU, which reports none
    else:
        pytest.fail(f"no expectation written for {name}")


# -- the recorded device trace -------------------------------------------------

TRACE = os.path.join(DATA, "mini_v5e.xplane.pb")


def test_device_trace_recorded_on_a_v5e():
    # three runs of one jitted program: per run a copy-start (14 ns), a
    # copy-done (3 ns) and one fusion (11,877 ns), read off the file by
    # hand; the union of each run's operations is 11,897 ns
    got = xplane.reduce(TRACE, {"train.step"}, window_s=0.03)
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(3 * 11_894e-9, rel=0.002)
    assert got["window_s"] == 0.03
    ops = dict(got["device_ops"])
    assert set(ops) == {"fusion bf16[]", "copy-start bf16[1024,1024]",
                        "copy-done bf16[1024,1024]"}
    assert ops["fusion bf16[]"] == pytest.approx(3 * 11_877e-9, rel=0.001)
    # two gaps between three runs
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        (68_940_258 - 45_940_931 - 2 * 11_900) * 1e-9, rel=0.01)
    idle = reducers.xplane_idle({"device": got})
    assert idle == pytest.approx(100 * (1 - got["busy_s"] / 0.03))


# the events' extent: first copy-start's start to the last fusion's end
EXTENT = 23_011_224e-9


@pytest.mark.parametrize("host, busy, extent, window", [
    # the caller timed more than the events span: its window stands, and
    # the idle time at the capture's edges still counts
    (0.03, None, None, 0.03),
    # the caller timed LESS than the events span (its clock is read
    # inside the capture): the window holds every event
    (0.01, None, None, EXTENT),
    # no caller's window: the extent, and nothing said of the host's
    (None, None, None, EXTENT),
    # PR 41's refused pair (sessions, seed 1085869964): the device busy
    # for 0.48 ms more than the host timed; the events spanned the busy
    # time and the 2.9 ms of gaps between them
    (5.017475489, 5.017954717, 5.017954717 + 0.0029, 5.020854717),
], ids=["host_wider", "host_shorter", "no_host_window", "pr41_pair"])
def test_the_window_holds_every_device_event(host, busy, extent, window):
    if busy is None:
        got = xplane.reduce(TRACE, {"train.step"}, window_s=host)
        assert got["extent_s"] == pytest.approx(EXTENT, rel=1e-9)
    else:
        got = dict(xplane.capture_window(host, extent), busy_s=busy)
    assert got["window_s"] == pytest.approx(window, rel=1e-9)
    assert got["host_window_s"] == host
    assert got["window_s"] == max(host or 0.0, got["extent_s"])
    assert 0 < got["busy_s"] <= got["extent_s"] <= got["window_s"]
    assert 0.0 <= reducers.xplane_idle({"device": got}) <= 100.0


def test_device_trace_by_name():
    # the same file by name.  ``XLA Modules``: three runs of the one
    # program, 11,900 + 11,901 + 11,901 ns; ``XLA Ops``: nine events
    # under three names (copy-start 14 + 14 + 13, copy-done 3 + 3 + 2,
    # fusion 11,877 + 11,877 + 11,878)
    got = xplane.reduce(TRACE, {"train.step"}, window_s=0.03)
    by = got["by_name"]
    assert by["XLA Modules"] == {
        "jit__lambda(6074760096634504725)": pytest.approx(35_702e-9)}
    assert by["XLA Ops"] == {
        "copy-start bf16[1024,1024]": pytest.approx(41e-9),
        "copy-done bf16[1024,1024]": pytest.approx(8e-9),
        "fusion bf16[]": pytest.approx(35_632e-9)}
    # device_ops is the ten longest of the same table
    assert dict(got["device_ops"]) == by["XLA Ops"]
    src = {"device": got}
    # busy is the union of the operations, 3 x 11,894 - 1 = 35,681 ns
    assert got["busy_s"] == pytest.approx(35_681e-9)
    assert reducers.trace_time_share(src, "XLA Ops", "^fusion ") == \
        pytest.approx(100 * 35_632 / 35_681)
    assert reducers.trace_time_share(src, "XLA Ops", "^copy-") == \
        pytest.approx(100 * 49 / 35_681)
    # a program's span holds the few ns between its operations too
    assert reducers.trace_time_share(
        src, "XLA Modules", "^jit__lambda\\(") == \
        pytest.approx(100 * 35_702 / 35_681)
    # arithmetic only (a toy program against a real model's count):
    # one decode step reads 2,623,250,432 B of weights, 3.203 ms at
    # 819 GB/s, over the program's 35.702 us
    cfg = config("gpt3-1.3b-serve")
    src.update(ctx={"cfg": cfg, "peaks": counts.peaks_for("TPU v5 lite")},
               work={"tokens_emitted": 32, "live_positions": 0,
                     "prefill_tokens": 0, "num_slots": 32, "counters": {}})
    assert reducers.trace_roofline(
        src, "XLA Modules", "^jit__lambda\\(", "decode_least_seconds") == \
        pytest.approx(100 * 2_623_250_432 / 819e9 / 35_702e-9)


def test_a_pallas_call_goes_under_its_kernels_name():
    # recorded on a v5e (PR 27): three runs of a jitted function that
    # calls pl.pallas_call(..., name="probe_kernel_scale") and reduces
    # its product; the kernel's events read "%probe_kernel_scale.1 =
    # f32[512,512]... custom-call(...)" and took 1,937 + 1,910 + 1,903
    # ns, the fusion 1,822 + 1,820 + 1,822, back to back within a run
    got = xplane.reduce(os.path.join(DATA, "mini_pallas_v5e.xplane.pb"))
    assert got["by_name"]["XLA Ops"] == {
        "probe_kernel_scale": pytest.approx(5_750e-9),
        "convolution_reduce_fusion f32[]": pytest.approx(5_464e-9)}
    assert got["by_name"]["XLA Modules"] == {
        "jit_f(2841148262588850476)": pytest.approx(11_238e-9)}
    assert got["busy_s"] == pytest.approx(11_214e-9)
    assert dict(got["device_ops"])["probe_kernel_scale f32[512,512]"] == \
        pytest.approx(5_750e-9)
    assert reducers.trace_time_share(
        {"device": got}, "XLA Ops", "^probe_kernel_scale$") == \
        pytest.approx(100 * 5_750 / 11_214)


@pytest.mark.parametrize("text, want", [
    ('%probe_kernel_scale.1 = f32[512,512]{1,0:T(8,128)S(1)} custom-call('
     'f32[512,512]{1,0} %x.1), custom_call_target="tpu_custom_call", '
     'operand_layout_constraints={f32[512,512]{1,0}}', "probe_kernel_scale"),
    ('%attn_v2 = (bf16[8,128]{1,0}, f32[8]{0}) custom-call(bf16[8,128] %q), '
     'custom_call_target="tpu_custom_call"', "attn_v2"),
    # another custom call, and every other operation: as short_op has it
    ('%custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %x), '
     'custom_call_target="Sharding"', "custom-call f32[8]"),
    ("%convert.266 = f32[65536,16,128]{2,1,0:T(8,128)} convert(bf16[1]{0} %f)",
     "convert f32[65536,16,128]"),
])
def test_key_of_an_operation_by_name(text, want):
    assert xplane.op_key(text) == want


def _label_one_by_one(gap, host):
    """The reference: every host span tried against every gap."""
    mid = (gap[0] + gap[1]) / 2.0
    best = None
    for s, e, name in host:
        if s <= mid < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "(no program span)"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_gaps_are_labelled_as_one_by_one(seed):
    rng = random.Random(seed)
    # nested, overlapping and equally wide spans, in no order
    host = []
    for i in range(300):
        s = rng.randrange(0, 10_000)
        host.append((s, s + rng.choice((5, 50, 50, 400, 3000)), f"s{i % 7}"))
    edges = sorted(rng.sample(range(0, 14_000), 400))
    gaps = list(zip(edges[::2], edges[1::2]))
    got = xplane.label_gaps(gaps, host)
    assert got == [_label_one_by_one(g, host) for g in gaps]
    assert "(no program span)" in got and len(set(got)) > 3
    assert xplane.label_gaps(gaps, []) == ["(no program span)"] * len(gaps)


@pytest.mark.parametrize("text, want", [
    ("%convert.266 = f32[65536,16,128]{2,1,0:T(8,128)} convert(bf16[1]{0} %fusion.4)",
     "convert f32[65536,16,128]"),
    ("%copy-start.1 = (bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%p)",
     "copy-start bf16[8192,2048]"),
    ("%fusion = bf16[]{:T(256)} fusion(bf16[1024,1024]{1,0} %x), kind=kLoop",
     "fusion bf16[]"),
    ("jit_pure(9165921196842725281)", "jit_pure(9165921196842725281)"),
])
def test_operation_names_add_up_across_layers(text, want):
    assert xplane.short_op(text) == want


def test_intervals_merge():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == \
        [[0, 3], [5, 8], [10, 11]]


def test_every_metric_of_the_manifest_has_its_file_and_one_arrow():
    man = manifest()
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        f = METRICS[m["name"]]
        assert (f["layer"], f["unit"], f["moves"], f["source"]) == \
            (m["layer"], m["unit"], m["moves"], m["source"])
        assert f["reducer"] in reducers.KINDS
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
