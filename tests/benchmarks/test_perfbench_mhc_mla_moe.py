"""``xing4-29b-a4b-serve``: its file against the published config, the
counts of ``configs/mhc_mla_moe_program.py`` against hand-worked
numbers, the seeded model it builds, its control and its planted fault
at the rehearsal's size, and the names ``dev_share.mhc`` reads held to
a compile for the described v5e."""
import collections
import functools
import json
import os
import re
import subprocess
import sys
from unittest import mock

import numpy as np
import pytest

from bench_paths import BENCH, ROOT, config, manifest, run_cell
from harness import common, counts, reducers, xplane

NAME = "xing4-29b-a4b-serve"
CELL = NAME + ".long-doc-sessions"
KIMI = "kimi-vl-a3b-serve.doc-sessions"
cfg = config(NAME)
dims = cfg["dims"]
prog = common.load_program(cfg)
PEAKS = counts.peaks_for("TPU v5 lite")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the catalog row's ``config``, verbatim
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 7, "num_nextn_predict_layers": 0}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_are_untouched_but_for_the_reduced(key):
    want = REDUCED.get(key, PUBLISHED[key])
    assert cfg[key] == want and dims[key] == want


def test_only_depth_and_the_prediction_module_are_reduced():
    assert cfg["reduced"] == sorted(REDUCED)
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(dims) == set(PUBLISHED)
    entry, = [c for c in manifest()["configs"] if c["name"] == NAME]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    assert set(cfg["assumed"]) >= {
        "streams", "stream_norm", "sinkhorn", "weights", "rope_pairs",
        "max_seq_len"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Xing4.0-29B-A4B"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]


@pytest.mark.parametrize("what, got, want, rel", [
    # 7 layers x (512 + 64) x 2 bytes
    ("bytes a cached position",
     lambda: prog.row_bytes_per_position(dims), 8_064, 0),
    # 3 x 3,584 x 1,024 x 2 bytes
    ("bytes an expert", lambda: prog.expert_bytes(dims), 22_020_096, 0),
    # 3,584 x 768 + 768 x 6,144 + 3,584 x 576 + 512 x 8,192 + 4,096 x
    # 3,584 (with the two norms' 768 + 512 gains: the issue's
    # 28,411,136)
    ("attention matrices a layer",
     lambda: prog.attention_params(dims), 28_409_856, 0),
    # 14,336 gains + 24 x 14,336 + 3 + 24
    ("a sub-layer's mappings",
     lambda: prog.mapping_params(dims), 358_427, 0),
    # two dense layers of 128,225,590, five expert layers of
    # 745,017,718, embedding, final norm and head 939,527,680
    ("parameters held", lambda: prog.total_params(dims),
     4_921_067_450, 0),
    # attention 7 x 28.41 M, dense feed-forward 2 x 99.09 M, shared
    # experts 5 x 11.01 M, routers 5 x 0.229 M, mappings 14 x 0.358 M,
    # head 469.8 M: 928.03 M x 2 bytes
    ("fixed bytes a decode step",
     lambda: 2 * prog.fixed_step_params(dims), 1.856e9, 0.0005),
    # the fixed part and 5 layers x 4 experts x 11.01 M
    ("parameters a token is multiplied by",
     lambda: prog.active_params(dims), 1.14823e9, 0.0001),
    # 2 x 32 heads x (128 + 64 + 128)
    ("attention operations a pair",
     lambda: prog.attention_flops_per_pair(dims), 20_480, 0),
])
def test_hand_worked(what, got, want, rel):
    assert got() == pytest.approx(want, rel=rel or 1e-12), what


def test_the_whole_model_is_the_name_s_29b_a4b():
    """At the published depth with the prediction module left out: 2
    dense and 38 expert layers hold 29.5 B parameters, 3.9 B of them
    multiplied by a token (the embedding's row is looked up)."""
    full = dict(dims, num_hidden_layers=40)
    assert prog.total_params(full) == pytest.approx(29.5e9, rel=0.003)
    assert prog.active_params(full) == pytest.approx(3.93e9, rel=0.005)


def test_decode_least_seconds_follows_the_experts_hit():
    """11 live rows of 12,500 positions, 32 experts hit in each of 5
    layers: 1.856 GB x 11/32 of a step + 160 x 22.0 MB + 1.11 GB over
    819 GB/s; a chunk run in the interval is taken to have hit all 320
    and leaves the decode count."""
    work = {"tokens_emitted": 11, "num_slots": 32,
            "live_positions": 11 * 12_500, "prefill_tokens": 0,
            "counters": {"serving.moe_experts_hit": 160}}
    want = (2 * prog.fixed_step_params(dims) * 11 / 32
            + 160 * 22_020_096 + 11 * 12_500 * 8_064) / 819e9
    assert prog.decode_least_seconds(cfg, PEAKS, work) \
        == pytest.approx(want, rel=1e-12)
    assert 0.0064 < want < 0.0065
    more = dict(work, counters={"serving.moe_experts_hit": 160 + 320,
                                "serving.prefill_chunks": 1})
    assert prog.decode_least_seconds(cfg, PEAKS, more) \
        == pytest.approx(want, rel=1e-12)
    secs, bound = prog.serve_least_seconds(cfg, PEAKS, more)
    assert bound == "memory" and secs == pytest.approx(
        want + 320 * 22_020_096 / 819e9, rel=1e-12)
    secs, bound = prog.serve_least_seconds(
        cfg, PEAKS, dict(work, prefill_tokens=8192))
    assert bound == "compute" and secs == pytest.approx(
        (2 * prog.active_params(dims) * (8192 + 11)
         + 7 * 20_480 * 11 * 12_500) / 197e12, rel=1e-12)
    # the grouped products: 160 hits read 3.52 GB, 44 pairs are nothing
    gmm = {"counters": {"serving.moe_experts_hit": 160.0,
                        "serving.moe_routed_pairs": 44.0}}
    assert prog.gmm_least_seconds(cfg, PEAKS, gmm) \
        == pytest.approx(160 * 22_020_096 / 819e9, rel=1e-12)


def test_build_holds_the_seeded_leaves_and_nothing_else():
    tiny = common.merged(cfg, cfg["rehearse"])
    model = prog.build(tiny, 2**31 + 9)
    params = dict(model.named_parameters())
    specs = prog.leaf_specs(tiny["dims"])
    assert set(params) == {n for n, _, _ in specs}
    want = common.seeded_weights(tiny, 2**31 + 9)
    for name, shape, _ in specs:
        got = params[name]._data
        assert tuple(got.shape) == tuple(shape)
        assert str(got.dtype) == tiny["dtype"]
        assert np.array_equal(np.asarray(got), np.asarray(want[name]))
    assert not list(model.named_buffers())
    assert model.streams == 4 and model.blocks[0].attn.q_lora_rank == 24


def test_the_cell_s_traffic_is_the_issue_s():
    with open(common.BENCH_DIR + "/traffic/long-doc-sessions.json") as f:
        mix = json.load(f)
    assert mix["kind"] == "sessions" and mix["system_prompt_len"] == 512
    assert mix["population"] == 20
    assert mix["history_len"] == {"dist": "uniform", "min": 6144,
                                  "max": 14336}
    assert mix["user_len"] == {"dist": "lognormal", "median": 64,
                               "sigma": 0.6, "min": 16, "max": 256}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.6, "min": 48, "max": 512}
    assert mix["max_context"] == cfg["engine"]["max_seq_len"] == 16384
    assert mix["think_s"] == {"dist": "exponential", "mean": 2.0}
    assert mix["grace_s"] == 5
    assert cfg["engine"] == {"num_slots": 32, "max_seq_len": 16384,
                             "kv_block_size": 16, "kv_blocks": 22528,
                             "prefill_chunk": 256}
    man = manifest()
    cell, = [w for w in man["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "long-doc-sessions", 1)
    # on every list that has Kimi's cell, and on dev_share.mhc alone
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if KIMI in m.get("workloads", ()):
                assert CELL in m["workloads"], m["name"]
    own, = [m for m in man["per_layer"] if m["name"] == "dev_share.mhc"]
    assert own["workloads"] == [CELL]


def test_dev_share_mhc_reads_the_kernel_by_name():
    """3 us a sub-layer of ``mhc_maps`` in 14 ms of busy time; a
    program without the kernel (the parent) reports nothing and raises
    nothing."""
    files = reducers.load_metric_files(common.BENCH_DIR + "/layer_metrics")
    src = {"device": {"busy_s": 0.014, "by_name": {"XLA Ops": {
        "mhc_maps": 42e-6, "multiply_add_fusion f32[24,32]": 28e-6,
        "fusion bf16[32,3584]": 1e-3, "gmm": 5e-3}}}}
    got = reducers.reduce_all(files, ["dev_share.mhc"], src)
    assert got["dev_share.mhc"]["value"] == pytest.approx(
        100 * 70e-6 / 0.014)
    src["device"]["by_name"]["XLA Ops"] = {"gmm": 5e-3}
    assert reducers.reduce_all(files, ["dev_share.mhc"], src) == {}


# -- the names dev_share.mhc reads, held to the compiler --------------------

def _entry_instructions(text):
    """[(name as the trace's reducer keys it, scopes of the operations
    inside)] of every instruction the device runs for a compiled
    module: the entry computation's and its loops' bodies', a fusion
    counted with what it holds."""
    comps, cur, entry = {}, None, None
    for ln in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", ln)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif ln.startswith("}"):
            cur = None
        elif cur:
            comps[cur].append(ln.strip())

    def scope(ln):
        m = re.search(r'op_name="([^"]*)"', ln)
        return None if not m else ("mhc" if "/mhc." in m.group(1)
                                   else "other")

    def inside(comp, seen):
        # (a parameter, constant or bitcast is no work, and a
        # reduction's region names its parameters by the primitive
        # alone, with no scope)
        out = collections.Counter()
        for ln in comps.get(comp, ()):
            if re.search(r" (parameter|constant|bitcast)\(", ln):
                continue
            out[scope(ln)] += 1
            for callee in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                                     ln):
                if callee not in seen:
                    seen.add(callee)
                    out += inside(callee, seen)
        return out

    found = []

    def walk(comp):
        for ln in comps[comp]:
            if " = " not in ln or re.search(
                    r" (parameter|constant|get-tuple-element|tuple|"
                    r"bitcast)\(", ln):
                continue
            loop = re.search(r" while\(.*body=%?([\w.\-]+)", ln)
            if loop:
                walk(loop.group(1))
                continue
            held = collections.Counter()
            for callee in re.findall(r"calls=%?([\w.\-]+)", ln):
                held += inside(callee, set())
            if not held:
                held[scope(ln)] += 1
            found.append((xplane.op_key(ln.replace("ROOT ", "")), held))
    walk(entry)
    return found


def _mhc_match():
    with open(os.path.join(BENCH, "layer_metrics",
                           "dev_share.mhc.json")) as f:
        return json.load(f)["params"]["match"]


@functools.lru_cache(maxsize=None)
def _compiled_for_the_v5e(program):
    """The cell's own step program (its depth, slots, chunk, pool and
    table, at the published widths), compiled for the compile-only
    ``TPU v5 lite`` device with what a chip would run (the backend reads
    ``tpu`` while it lowers: the kernels compiled, not interpreted) ->
    (its instructions, its count of device loops)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models import mla_moe

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
    eng, i32 = cfg["engine"], jnp.int32
    layers, slots = dims["num_hidden_layers"], eng["num_slots"]
    with nn.LazyGuard():
        model = mla_moe.MLAMoEModel(dims)
    model.to(dtype="bfloat16")
    params = dict(model.named_parameters())
    names = sorted(params)
    pools = [sds(s) for s in model.serving_spec().kv.pool_shapes(
        (eng["kv_blocks"] + 1, eng["kv_block_size"]))] * layers
    blocks = eng["max_seq_len"] // eng["kv_block_size"]
    if program == "decode":
        def step(p_list, pools, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._fused_decode_tick_slots(
                    args[1], pools, args[0], *args[2:])
        f32, u32 = jnp.float32, jnp.uint32
        args = [sds((slots, blocks), i32), sds((slots, 1), i32)] + [
            sds((slots,), t) for t in (i32, f32, i32, f32, u32, u32, i32,
                                       i32, i32)]
    else:
        def step(p_list, pools, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._chunk_prefill_tick_paged(
                    args[0], pools, *args[1:])
        args = [sds((1, eng["prefill_chunk"]), i32), sds((blocks,), i32),
                sds((), i32), sds((), i32), sds((), i32)]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = jax.jit(step, donate_argnums=(1,)).lower(
                [sds(params[n].shape) for n in names], pools,
                *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    return _entry_instructions(text), text.count(" while(")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_dev_share_mhc_matches_only_the_mappings_on_the_v5e(program):
    """Both step programs as the cell runs them (7 layers, the engine's
    own options): EVERY instruction under a name the metric's ``match``
    finds holds operations of the scopes ``mhc.maps`` / ``mhc.mix`` and
    of no other scope, so a fusion of another scope that the compiler
    names alike fails here; the kernel ``mhc_maps`` runs once a
    sub-layer, and the twenty normalisations are inside it (no device
    loop beside the walk's and megablox's)."""
    found, loops = _compiled_for_the_v5e(program)
    layers = dims["num_hidden_layers"]
    match = _mhc_match()
    hit = [(name, held) for name, held in found if re.search(match, name)]
    assert sum(name == "mhc_maps" for name, _ in hit) == 2 * layers
    for name, held in hit:
        assert held["mhc"] and not held["other"], (name, held)
    # the walk a layer and, where the pairs fill several row tiles
    # (the chunk), megablox's group metadata (one a routed layer): the
    # mappings add no loop
    assert loops == layers + (layers - 2 if program == "chunk" else 0)


# what ``match`` finds in the two programs, spelt out
MHC_NAMES = {
    "mhc_maps", "convert_element_type f32[14336]",
    "convert_element_type f32[24]", "select_select_fusion f32[24]",
    # the decode program's
    "multiply_add_fusion f32[24,32]", "multiply_reduce_fusion f32[32]",
    "slice_bitcast_fusion f32[32]", "slice_bitcast_fusion f32[4,32,1,1]",
    "fusion bf16[32,1,3584]",
    # the chunk program's
    "multiply_add_fusion f32[24,256]", "multiply_reduce_fusion f32[256]",
    "slice_bitcast_fusion f32[4,1,256,1]", "fusion bf16[1,256,3584]",
    "fusion f32[1,1,256,1]", "slice f32[1,1,256,1]", "slice f32[1,256]",
    "add_add_fusion f32[256]"}


def test_dev_share_mhc_names_nothing_stale_and_half_of_the_mappings():
    """The ``match`` finds exactly ``MHC_NAMES`` in the two programs (a
    name the compiler no longer makes would read 0 unseen), and they
    hold half of the scopes' operations by count (3,463 of 6,851 in
    the two programs): the rest (``fusion
    bf16[32,3584]``, ``fusion f32[32]``, ``add_rsqrt_fusion f32[32]``
    and their chunk forms, and the chunk's ``fusion
    bf16[1,1,256,3584]``, one of whose 15 holds the shared expert's
    product) the compiler merged with a neighbour's work, and the metric
    leaves them out: a lower bound."""
    match = _mhc_match()
    found = [x for program in ("decode", "chunk")
             for x in _compiled_for_the_v5e(program)[0]]
    assert {name for name, _ in found if re.search(match, name)} \
        == MHC_NAMES
    of_mhc = sum(held["mhc"] for _, held in found)
    read = sum(held["mhc"] for name, held in found
               if re.search(match, name))
    assert read >= 0.45 * of_mhc, (read, of_mhc)


LONGER = json.dumps({
    "answer_len": {"dist": "uniform", "min": 24, "max": 32},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history_len": {"dist": "uniform", "min": 16, "max": 24}})
MORE = json.dumps({"check": {"tokens": 4000, "max_requests": 200}})


def compared(lines):
    return {ln.split()[1].rstrip(":"): ln.endswith(" ok")
            for ln in lines if ln.startswith("compared ")}


@pytest.mark.parametrize("seed", [1, 2])
def test_control_int8_weights_is_not_correct(seed):
    args = ("--rehearse", "--mix-override", LONGER, "--config-override",
            MORE)
    rc, lines, err = run_cell(CELL, *args, "--control", "int8", seed=seed)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] or not c["regret_mean"]


@pytest.mark.parametrize("seed", [1, 2])
def test_the_plain_residual_is_not_correct(seed):
    """``planted_fault_residual.py`` (the upper reading of the cell's
    limits on the chip comes from it): a model served with the plain
    residual in place of the streams' mappings is caught."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "benchmarks",
                                      "planted_fault_residual.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", "--rehearse", "--mix-override", LONGER,
         "--config-override", MORE],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any(ln.startswith("PLANTED FAULT") for ln in lines)
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert not c["regret_max"] and not c["regret_mean"]
    assert c["finished_with_wrong_length"] and c["engine_step_failures"]
