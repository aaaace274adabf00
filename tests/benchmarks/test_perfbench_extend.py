"""A later PR adds a configuration, a traffic mix, a cell and per-layer
metrics as new files and new entries, and edits no file that is there:
the harness has to run them.  Twice: a new mix and metric over the model
there is, and what a ``model_config`` PR brings, another architecture
(other leaves, other counts) with its reference and program file."""
import json
import os
import shutil

import pytest

from bench_paths import DATA, ROOT, manifest, run_cell

EXTEND = os.path.join(DATA, "extend")


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark with every file's bytes remembered; after
    the test, nothing that was there may have been edited."""
    for p in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    before = {}
    for d, _, files in os.walk(tmp_path):
        for fn in files:
            path = os.path.join(d, fn)
            with open(path, "rb") as f:
                before[path] = f.read()
    yield tmp_path
    for path, data in before.items():
        with open(path, "rb") as f:
            assert f.read() == data, path


def add_cell(man, config, traffic, like, cfg_file):
    """New entries only: the configuration, its cell, and the cell's
    name in every metric that lists the cell ``like``."""
    cell = f"{config}.{traffic}"
    man["configs"].append({
        "name": config, "source": "a test", "file": cfg_file,
        "reduced": [], "why": "a test"})
    man["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    return cell


def add_metric(tree, man, metric, cell):
    with open(tree / "benchmarks" / "layer_metrics"
              / (metric["name"] + ".json"), "w") as f:
        json.dump(metric, f)
    man["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")}
        | {"workloads": [cell]})


def test_new_cell_mix_and_metric_are_data(tree):
    man = manifest()
    bench = tree / "benchmarks"
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
    cell = add_cell(man, "gpt-extra-serve", "chat-burst",
                    "gpt3-1.3b-serve.chat",
                    "benchmarks/configs/gpt-extra-serve.json")
    add_metric(tree, man, {
        "name": "emit_p50_ms", "layer": "admission and scheduling",
        "unit": "ms", "better": "lower", "source": "program_span",
        "moves": "itl_p95_ms", "reducer": "span_percentile",
        "params": {"span": "decode.emit", "q": 50}}, cell)
    with open(tree / "BENCHMARK.json", "w") as f:
        json.dump(man, f)

    rc, lines, err = run_cell(cell, "--rehearse", root=str(tree),
                              trace=1, seed=31)
    assert rc == 0, err[-2000:]
    res = json.loads(lines[-1])
    assert res["rehearsal_correct"] is True
    assert res["metrics"]["emit_p50_ms"]["value"] > 0
    assert "decode_batch_mean" in res["metrics"]
    rc, lines, err = run_cell(cell, "--rehearse", root=str(tree),
                              trace=0, seed=32)
    assert rc == 0, err[-2000:]
    assert set(json.loads(lines[-1])["metrics"]) == {
        "ttft_mean_ms", "itl_p95_ms", "out_tok_s", "setup_s"}


def test_another_architecture_is_new_files(tree):
    """What a ``model_config`` PR does: a program file that builds a
    model with other leaves and other counts (expert blocks), its plain
    reference, a configuration that names both, a cell, and one metric
    file of each kind that reads the device trace by name."""
    man = manifest()
    bench = tree / "benchmarks"
    for fn in ("gptmoe_program.py", "gptmoe_reference.py"):
        shutil.copy(os.path.join(EXTEND, fn), bench / "configs" / fn)
    with open(bench / "configs" / "gpt2-medium-train.json") as f:
        cfg = json.load(f)
    cfg.update(name="gptmoe-train", program="gptmoe_program",
               reference="gptmoe_reference")
    cfg["dims"].update(moe_experts=4, moe_every=2)
    # the reference's load-balancing term is the whole batch's
    cfg["rehearse"]["check"]["rows_per_block"] = 4
    # sound runs on ten seeds read up to 2.0e-7, 1.5e-7 and 7.9e-6
    cfg["rehearse"]["limits"] = {"loss_gap_max": 2e-6,
                                 "grad_norm_gap": 1e-5,
                                 "update_norm_gap": 3.5e-5}
    with open(bench / "configs" / "gptmoe-train.json", "w") as f:
        json.dump(cfg, f)
    cell = add_cell(man, "gptmoe-train", "steps",
                    "gpt2-medium-train.steps",
                    "benchmarks/configs/gptmoe-train.json")
    by_name = {"layer": "kernels", "unit": "%", "better": "higher",
               "source": "device_trace", "moves": "train_tok_s"}
    add_metric(tree, man, dict(
        by_name, name="dev_share.moe_step", reducer="trace_time_share",
        params={"line": "XLA Modules", "match": "^jit_"}), cell)
    add_metric(tree, man, dict(
        by_name, name="roofline_share.moe_step", reducer="trace_roofline",
        params={"line": "XLA Modules", "match": "^jit_",
                "least": "train_least_seconds"}), cell)
    with open(tree / "BENCHMARK.json", "w") as f:
        json.dump(man, f)

    rc, lines, err = run_cell(cell, "--rehearse", root=str(tree),
                              trace=0, seed=41)
    assert rc == 0, err[-2000:]
    res = json.loads(lines[-1])
    assert res["rehearsal_correct"] is True, lines[-12:]
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    dump = tree / "sources.json"
    rc, lines, err = run_cell(cell, "--rehearse", "--dump-sources",
                              str(dump), root=str(tree), trace=1, seed=42)
    assert rc == 0, err[-2000:]
    res = json.loads(lines[-1])
    assert res["rehearsal_correct"] is True, lines[-12:]
    # the CPU has no device plane: the two readers by name find nothing
    # and are left out, as roofline_share.train is; mfu.train is this
    # architecture's count (two of four experts a token), not gpt2's
    assert {"mfu.train", "step_p50_ms"} <= set(res["metrics"]) \
        <= {m["name"] for m in man["per_layer"]
            if cell in m["workloads"]} - {
                "dev_share.moe_step", "roofline_share.moe_step",
                "roofline_share.train"}
    with open(dump) as f:
        ctx = json.load(f)["ctx"]
    d = ctx["cfg"]["dims"]
    dense = 4 * d["hidden_size"] ** 2 \
        + 2 * d["hidden_size"] * d["ffn_hidden_size"]
    moe = 4 * d["hidden_size"] ** 2 + d["hidden_size"] * 4 \
        + 2 * 2 * d["hidden_size"] * d["ffn_hidden_size"]
    assert ctx["flops_per_token"] == 6 * (
        dense + moe + d["hidden_size"] * d["vocab_size"]) \
        + 12 * 2 * d["hidden_size"] * 32
    os.remove(dump)
