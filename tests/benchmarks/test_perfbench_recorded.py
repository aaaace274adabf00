"""The per-layer metrics that read the engine's device lane, the tick's
host share, the HTTP edge and the programs' load time: each file names
an existing reducer kind and reads its value from the dump it names
under ``data/`` (``--rehearse --trace 1 --dump-sources`` of the chat
cell, recorded with those spans); the manifest lists each in the cells
that hold something to read; and on the older recording, which stands
for a parent commit without the spans, each reads nothing and raises
nothing."""
import json
import os
import statistics

import pytest

from bench_paths import DATA, manifest
from harness import common, reducers
from recorded_metrics import own_recording

OWN = own_recording()
CHAT, SESSIONS = "gpt3-1.3b-serve.chat", "gpt3-1.3b-serve.sessions"
CELLS = {
    "dev_decode_p50_ms": {CHAT, SESSIONS},
    "dev_prefill_tok_s": {CHAT},
    "dev_prefill_tok_s.closed": {SESSIONS},
    "tick_host_mean_ms": {CHAT, SESSIONS},
    "prefill_phase_p90_ms": {CHAT},
    "prefill_phase_p90_ms.closed": {SESSIONS},
    "edge_ingest_p90_ms": {CHAT},
    "edge_ingest_p90_ms.closed": {SESSIONS},
    "edge_first_frame_p90_ms": {CHAT},
    "program_load_ms.serve": {CHAT, SESSIONS},
}
_DUMPS = {}


def dump(name):
    fn = OWN[name]["recorded"]
    if fn not in _DUMPS:
        with open(os.path.join(DATA, fn)) as f:
            _DUMPS[fn] = json.load(f)
    return _DUMPS[fn]


def x(src, name):
    return [e for e in src["spans"]
            if e["name"] == name and e["ph"] == "X"]


def gaps_ms(src, start, end):
    t0 = {e["args"]["req"]: e["ts"] for e in src["spans"]
          if e["name"] == start}
    return sorted((e["ts"] - t0[e["args"]["req"]]) / 1e3
                  for e in src["spans"]
                  if e["name"] == end and e["args"]["req"] in t0)


def test_the_ten_are_the_files_that_name_a_recording():
    assert set(OWN) == set(CELLS)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_metric_file_on_its_recording(name):
    m = OWN[name]
    assert m["reducer"] in reducers.KINDS
    src = dump(name)
    got = reducers.reduce_all(OWN, [name], src)[name]
    assert got["unit"] == m["unit"]
    got = got["value"]
    if name.endswith(".closed"):      # the same reader, another arrow
        base = OWN[name[:-len(".closed")]]
        assert (base["reducer"], base["params"]) == \
            (m["reducer"], m["params"])
        assert m["moves"] == "out_tok_s"
        name = name[:-len(".closed")]
    if name == "dev_decode_p50_ms":
        d = sorted(e["dur"] / 1e3 for e in x(src, "dev.decode")
                   if e["args"]["batch"] >= 1)
        assert len(d) > 10
        assert got == pytest.approx(statistics.median_low(d))
    elif name == "dev_prefill_tok_s":
        spans = x(src, "dev.prefill")
        tokens = src["counters"]["delta"]["serving.prefill_tokens"]
        assert all(e["args"]["n"] >= 1 for e in spans)
        assert got == pytest.approx(
            tokens / (sum(e["dur"] for e in spans) * 1e-6))
    elif name == "tick_host_mean_ms":
        t = [e for e in x(src, "tick") if e["args"]["batch"] >= 1]
        assert all(0 <= e["args"]["host_ms"] <= e["dur"] / 1e3 + 1e-3
                   for e in t)
        assert got == pytest.approx(
            statistics.fmean(e["args"]["host_ms"] for e in t))
    elif name == "prefill_phase_p90_ms":
        g = gaps_ms(src, "req.admitted", "req.first_token")
        assert len(g) > 5 and g[0] >= 0
        assert got == pytest.approx(reducers.percentile(g, 90))
    elif name == "edge_ingest_p90_ms":
        d = [e["dur"] / 1e3 for e in x(src, "http.ingest")]
        assert len(d) > 5
        assert got == pytest.approx(reducers.percentile(d, 90))
    elif name == "edge_first_frame_p90_ms":
        g = gaps_ms(src, "req.first_token", "http.first_frame")
        assert len(g) > 5 and g[0] >= 0
        assert got == pytest.approx(reducers.percentile(g, 90))
    elif name == "program_load_ms.serve":
        assert got == src["counters"]["peak"]["serving.compile_wall_ms"]
        assert got > 0
        # nothing compiled in the window: all of it was set-up
        assert src["counters"]["delta"]["serving.compile_wall_ms"] == 0
    else:
        pytest.fail(f"no expectation written for {name}")


@pytest.mark.parametrize("name", sorted(CELLS))
def test_manifest_lists_the_metric_in_its_cells(name):
    man = manifest()
    entry = {m["name"]: m for m in man["per_layer"]}[name]
    assert set(entry["workloads"]) == CELLS[name]
    for cell in (CHAT, SESSIONS):
        assert (name in common.cell_metrics(man, cell, "per_layer")) \
            == (cell in CELLS[name])
    # new entries stand at the end of the list, after the 22 of PR 23
    order = [m["name"] for m in man["per_layer"]]
    assert order.index(name) >= 22


def test_a_parent_without_the_spans_reports_none_of_them_and_raises_nothing():
    with open(os.path.join(DATA, "engine_sources_tiny.json")) as f:
        older = json.load(f)
    got = reducers.reduce_all(OWN, sorted(OWN), older)
    # the two instants of the prefill phase predate this change
    assert set(got) <= {"prefill_phase_p90_ms",
                        "prefill_phase_p90_ms.closed"}
