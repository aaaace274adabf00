"""A later PR adds a configuration, a traffic mix, a cell and a
per-layer metric as new files and new entries, and edits no file that is
there: the harness has to run them."""
import json
import os
import shutil

from bench_paths import ROOT, manifest, run_cell


def test_new_cell_mix_and_metric_are_data(tmp_path):
    man = manifest()
    for p in man["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    before = {}
    for d, _, files in os.walk(tmp_path):
        for fn in files:
            path = os.path.join(d, fn)
            with open(path, "rb") as f:
                before[path] = f.read()

    bench = tmp_path / "benchmarks"
    with open(bench / "configs" / "gpt3-1.3b-serve.json") as f:
        cfg = json.load(f)
    cfg["name"] = "gpt-extra-serve"
    cfg["rehearse"]["dims"]["num_layers"] = 3
    with open(bench / "configs" / "gpt-extra-serve.json", "w") as f:
        json.dump(cfg, f)
    with open(bench / "traffic" / "chat.json") as f:
        mix = json.load(f)
    mix["name"] = "chat-burst"
    mix["arrival_gaps"] = {"dist": "gamma", "mean": 1.0, "cv": 3.0}
    mix["rehearse"]["arrival_gaps"] = mix["arrival_gaps"]
    with open(bench / "traffic" / "chat-burst.json", "w") as f:
        json.dump(mix, f)
    metric = {"name": "emit_p50_ms", "layer": "admission and scheduling",
              "unit": "ms", "better": "lower", "source": "program_span",
              "moves": "itl_p95_ms", "reducer": "span_percentile",
              "params": {"span": "decode.emit", "q": 50}}
    with open(bench / "layer_metrics" / "emit_p50_ms.json", "w") as f:
        json.dump(metric, f)

    cell = "gpt-extra-serve.chat-burst"
    man["configs"].append({
        "name": "gpt-extra-serve", "source": "a test",
        "file": "benchmarks/configs/gpt-extra-serve.json", "reduced": [],
        "why": "a test"})
    man["workloads"].append({
        "name": cell, "config": "gpt-extra-serve", "traffic": "chat-burst",
        "chips": 1, "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "gpt3-1.3b-serve.chat" in m["workloads"]:
            m["workloads"].append(cell)
    man["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")}
        | {"workloads": [cell]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(man, f)

    rc, lines, err = run_cell(cell, "--rehearse", root=str(tmp_path),
                              trace=1, seed=31)
    assert rc == 0, err[-2000:]
    res = json.loads(lines[-1])
    assert res["rehearsal_correct"] is True
    assert res["metrics"]["emit_p50_ms"]["value"] > 0
    assert "decode_batch_mean" in res["metrics"]
    rc, lines, err = run_cell(cell, "--rehearse", root=str(tmp_path),
                              trace=0, seed=32)
    assert rc == 0, err[-2000:]
    assert set(json.loads(lines[-1])["metrics"]) == {
        "ttft_mean_ms", "itl_p95_ms", "out_tok_s", "setup_s"}
    # nothing that was there was edited
    for path, data in before.items():
        with open(path, "rb") as f:
            assert f.read() == data, path
