"""``lfm2-8b-a1b-serve``: its file against the published config, the
counts of ``configs/lfm2_moe_program.py`` against hand-worked numbers,
the seeded model it builds, its cell and traffic, its control and its
planted fault at the rehearsal's size, and the operations
``dev_share.short_conv`` reads held to a compile for the described
v5e."""
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

NAME = "lfm2-8b-a1b-serve"
CELL = NAME + ".tool-sessions"
TRINITY = "trinity-large-preview-serve.mixed-doc-sessions"
cfg = config(NAME)
dims = cfg["dims"]
prog = common.load_program(cfg)
PEAKS = counts.peaks_for("TPU v5 lite")
C, A = "conv", "full_attention"

# the catalog row's ``config``
# (/opt/skills/guides/model-configs/architectures.jsonl, line 34), verbatim
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [C, C] + [A, C, C, C] * 4 + [A, C, C] * 2,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 13, "num_dense_layers": 1,
           "layer_types": [C] + [A, C, C, C] * 3}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_are_untouched_but_for_the_reduced(key):
    want = REDUCED.get(key, PUBLISHED[key])
    assert cfg[key] == want and dims[key] == want


def test_what_is_reduced_is_listed_and_no_width_is():
    entry, = [c for c in manifest()["configs"] if c["name"] == NAME]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == set(REDUCED)
    assert entry["source"] == cfg["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/" \
           "config.json"
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"]
    assert row["config"] == PUBLISHED and row["source_url"] == cfg["source"]
    assert set(dims) - set(PUBLISHED) == {"seeded"}
    for key in ("num_hidden_layers", "num_dense_layers"):
        assert cfg["published"][key] == PUBLISHED[key]
    # published layer 0 and layers 2-13: three whole periods after the
    # one dense layer, every expert, the whole vocabulary
    assert dims["layer_types"] == [PUBLISHED["layer_types"][0]] \
        + PUBLISHED["layer_types"][2:14]
    assert dims["num_hidden_layers"] - dims["num_dense_layers"] >= 4
    assert dims["layer_types"][1:] == [A, C, C, C] * 3
    assert (prog.layers_of(dims, C), prog.attention_layers(dims)) == (10, 3)
    for word in ("two pipeline stages", "ONE block table", "tail"):
        assert word in cfg["deployment"]
    for key in ("tied_head", "conv_in_proj", "conv", "conv_state",
                "head_dim", "qk_norm", "rope_pairs", "norms", "router",
                "experts_in", "max_seq_len", "weights"):
        assert cfg["assumed"][key]


# the issue's own count, bf16: d 2,048, 32 query / 8 K/V heads of 64
CONV = 3 * 2048 * 2048 + 2048 * 2048 + 3 * 2048     # 16,783,360
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64    # 10,485,888
EXPERT = 3 * 2048 * 1792                            # 11,010,048
NORMS = 2 * 2048
DENSE = 3 * 2048 * 7168                             # 44,040,192
ROUTED = 32 * EXPERT + 2048 * 32 + 32               # 352,387,104
FIXED = (10 * 4 * 2048 * 2048 + 3 * (ATTN - 128) + DENSE
         + 12 * 2048 * 32 + 2048 * 65536)


@pytest.mark.parametrize("what, got, want", [
    ("a conv operator", prog.conv_params(dims), CONV),
    ("an attention layer", prog.attention_params(dims), ATTN),
    ("an expert", prog.expert_params(dims), EXPERT),
    ("an expert's bytes", prog.expert_bytes(dims), 22_020_096),
    ("the model as run", prog.total_params(dims),
     (CONV + NORMS + DENSE) + 9 * (CONV + NORMS + ROUTED)
     + 3 * (ATTN + NORMS + ROUTED) + 65536 * 2048 + 2048),
    ("the model as run, the issue's number", prog.total_params(dims),
     4_606_249_728),
    ("a step's fixed reads", prog.fixed_step_params(dims), FIXED),
    ("a row of one layer", prog.row_bytes(dims), 2048),
    ("a position", 3 * prog.row_bytes(dims), 6144),
    ("a tail of one layer", prog.tail_bytes(dims), 8192),
    ("a block of 32 rows", prog.block_bytes(dims, 32), 278_528),
    ("a block of 16 rows", prog.block_bytes(dims, 16), 180_224),
    ("a block of 64 rows", prog.block_bytes(dims, 64), 475_136),
    ("a pair's attention", prog.attention_flops_per_pair(dims),
     4 * 32 * 64),
])
def test_hand_worked(what, got, want):
    assert got == want, what


def test_the_published_model_is_the_name_s_8b_a1b():
    """The parametrisation is the published one: whole, 24 layers of
    which 2 dense, the head tied."""
    assert prog.total_params(PUBLISHED) == 8_339_930_560
    active = (18 * (CONV + NORMS) + 6 * (ATTN + NORMS) + 2 * DENSE
              + 22 * (4 * EXPERT + 2048 * 32 + 32) + 65536 * 2048 + 2048)
    assert active == pytest.approx(1.56e9, rel=5e-3)
    # the whole model does not fit a chip, every expert of a layer does
    assert 2 * prog.total_params(PUBLISHED) > 16e9
    assert 32 * prog.expert_bytes(PUBLISHED) < 1e9
    # the spec the program serves with counts the same block
    from paddle_tpu.models.programs import KVRowSpec
    spec = KVRowSpec(3, "bfloat16", (("kv", (1024,)),),
                     block_rows=(("conv", 10 * 4096),))
    assert spec.block_bytes(32) == prog.block_bytes(dims, 32)
    assert 14336 * spec.block_bytes(32) == 3_992_977_408


def test_least_seconds_follow_the_counters():
    """0.757 GB a step, 22.0 MB an expert hit by the decode program (a
    chunk run is taken to hit all 12 x 32), 2,048 B a row seen, 8,192 B
    a tail each way for every position a decode lane computes in a conv
    layer."""
    work = {"tokens_emitted": 640, "num_slots": 64, "prefill_tokens": 512,
            "live_positions": 10**9,        # not read: the rows are
            "counters": {"serving.moe_experts_hit": 2 * 384 + 3000,
                         "serving.prefill_chunks": 2,
                         "serving.moe_routed_pairs": 9000,
                         "serving.attn_rows_seen": 1_000_000,
                         "serving.attn_rows_seen_chunk": 300_000,
                         "serving.conv_positions": 10 * (512 + 700),
                         "serving.conv_starts_from_tail": 2,
                         "serving.conv_starts_from_zero": 0}}
    assert prog.decode_least_seconds(cfg, PEAKS, work) == pytest.approx(
        (10 * 2 * FIXED + 3000 * 22_020_096 + 1_000_000 * 2048
         + 2 * 7000 * 8192) / 819e9, rel=1e-12)
    assert 2 * FIXED == pytest.approx(0.7565e9, rel=1e-3)
    t, bound = prog.serve_least_seconds(cfg, PEAKS, work)
    assert bound == "memory" and t == pytest.approx(
        (10 * 2 * FIXED + 3768 * 22_020_096 + 1_300_000 * 2048
         + 2 * (7000 + 20) * 8192) / 819e9, rel=1e-12)
    assert prog.gmm_least_seconds(cfg, PEAKS, work) == pytest.approx(
        3768 * 22_020_096 / 819e9, rel=1e-12)
    work["counters"]["serving.moe_routed_pairs"] = 10**7
    assert prog.gmm_least_seconds(cfg, PEAKS, work) == pytest.approx(
        2 * EXPERT * 1e7 / 197e12, rel=1e-12)
    # a program without the counters (the parent) gives a number, not
    # an error
    assert prog.decode_least_seconds(cfg, PEAKS, dict(
        work, counters={})) == pytest.approx(10 * 2 * FIXED / 819e9)


def test_build_holds_the_seeded_leaves_and_nothing_else():
    tiny = common.merged(cfg, cfg["rehearse"])
    model = prog.build(tiny, 2**31 + 9)
    params = dict(model.named_parameters())
    specs = prog.leaf_specs(tiny["dims"])
    assert set(params) == {n for n, _, _ in specs}
    want = common.seeded_weights(tiny, 2**31 + 9)
    scaled = 0
    for name, shape, _ in specs:
        got = params[name]._data
        assert tuple(got.shape) == tuple(shape)
        assert str(got.dtype) == tiny["dtype"]
        scale = prog.leaf_scale(tiny["dims"], name)
        scaled += scale != 1.0
        assert np.array_equal(np.asarray(got),
                              np.asarray(want[name]) * scale)
    # the taps of the ten conv layers, the query gains of the three
    # attention layers and no other leaf
    assert scaled == 10 + 3
    # the query gains as in the cell; the taps by more at a width of 64
    # (the rehearsal's limits_note)
    assert dims["seeded"] == {"conv_weight_scale": 16, "q_norm_scale": 4}
    assert tiny["dims"]["seeded"] == {"conv_weight_scale": 512,
                                      "q_norm_scale": 4}
    assert not list(model.named_buffers())
    assert [b.kind for b in model.blocks] == dims["layer_types"]
    # no head matrix: the embedding is the head
    assert "lm_head.weight" not in params


def test_the_cell_s_traffic_is_the_issue_s():
    with open(common.BENCH_DIR + "/traffic/tool-sessions.json") as f:
        mix = json.load(f)
    assert mix["kind"] == "sessions" and mix["system_prompt_len"] == 4096
    assert mix["history_len"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.8, "min": 256, "max": 8192}
    assert mix["user_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 24, "max": 384}
    assert mix["think_s"] == {"dist": "exponential", "mean": 1.0}
    assert mix["max_context"] == 16384 and mix["grace_s"] == 5
    assert 32 <= mix["population"] <= 72
    eng = cfg["engine"]
    assert eng["num_slots"] == 64 and eng["prefill_chunk"] == 256
    assert eng["max_seq_len"] == 20480
    assert eng["prefill_chunk"] % eng["kv_block_size"] == 0
    # the pool: about 4 GB beside 9.21 GB of weights
    pool = eng["kv_blocks"] * prog.block_bytes(dims, eng["kv_block_size"])
    assert 3.9e9 < pool < 4.1e9
    man = manifest()
    cell, = [w for w in man["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "tool-sessions", 1)
    for word in ("13 of 24 layers", "host"):
        assert word in cell["why"]
    assert len(cell["why"]) <= 200
    # on every list that has Trinity's cell but dev_share.attn_walk's,
    # and on dev_share.short_conv alone
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if TRINITY in m.get("workloads", ()):
                assert (CELL in m["workloads"]) \
                    == (m["name"] != "dev_share.attn_walk"), m["name"]
    own, = [m for m in man["per_layer"]
            if m["name"] == "dev_share.short_conv"]
    assert own["workloads"] == [CELL] and own["layer"] == "model step"
    assert sum(CELL in m.get("workloads", ())
               for m in man["per_layer"]) == 16
    assert man["workloads"][-1] == cell and man["per_layer"][-1] == own


def test_dev_share_short_conv_reads_its_operations_by_name():
    """The named operations of both programs over busy time; a trace
    without one (the parent's, which cannot run the cell) reports
    nothing and raises nothing."""
    files = reducers.load_metric_files(common.BENCH_DIR + "/layer_metrics")
    names = sorted(SHORT_CONV_NAMES)
    src = {"device": {"busy_s": 4.0, "by_name": {"XLA Ops": {
        names[0]: 0.05, names[-1]: 0.15, "gmm": 1.0,
        "fusion bf16[64,1,6144]": 0.6}}}}
    got = reducers.reduce_all(files, ["dev_share.short_conv"], src)
    assert got["dev_share.short_conv"]["value"] == pytest.approx(5.0)
    src["device"]["by_name"]["XLA Ops"] = {"gmm": 1.0}
    assert reducers.reduce_all(files, ["dev_share.short_conv"], src) == {}


# -- the operations dev_share.short_conv reads, held to the compiler -------

def _entry_instructions(text):
    """[(name as the trace's reducer keys it, scopes of the operations
    inside)] of every instruction the device runs for a compiled
    module: the entry computation's and its loops' bodies', a fusion
    counted with what it holds; an operation written under
    ``conv.mix`` counts as ``conv``, any other as ``other``."""
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
        return None if not m else ("conv" if "/conv.mix/" in m.group(1)
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


def _match():
    with open(os.path.join(BENCH, "layer_metrics",
                           "dev_share.short_conv.json")) as f:
        return json.load(f)["params"]["match"]


@functools.lru_cache(maxsize=None)
def _compiled_for_the_v5e(program, **engine):
    """The cell's own step program (its depth, slots, chunk, pools,
    tails and table, at the published widths; ``engine`` overrides an
    option), compiled for the compile-only ``TPU v5 lite`` device with
    what a chip would run -> (its instructions, the compiled text)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.lfm2_moe import Lfm2MoeModel

    try:
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:      # no compiler for a described chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)
    eng, i32 = dict(cfg["engine"], **engine), jnp.int32
    slots = eng["num_slots"]
    with nn.LazyGuard():
        model = Lfm2MoeModel(dims)
    model.to(dtype="bfloat16")
    params = dict(model.named_parameters())
    names = sorted(params)
    kv = model.serving_spec().kv
    leading = (eng["kv_blocks"] + 1, eng["kv_block_size"])
    pools = [sds(s) for s in kv.pool_shapes(leading)] * kv.n_layers
    tails = [sds(s) for s in kv.block_pool_shapes(leading[0])]
    blocks = eng["max_seq_len"] // eng["kv_block_size"]
    if program == "decode":
        def step(p_list, pools, tails, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._fused_decode_tick_slots(
                    args[1], pools, tails, args[0], *args[2:])
        f32, u32 = jnp.float32, jnp.uint32
        args = [sds((slots, blocks), i32), sds((slots, 1), i32)] + [
            sds((slots,), t) for t in (i32, f32, i32, f32, u32, u32, i32,
                                       i32, i32)]
    else:
        def step(p_list, pools, tails, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return model._chunk_prefill_tick_paged(
                    args[0], pools, tails, *args[1:])
        args = [sds((1, eng["prefill_chunk"]), i32), sds((blocks,), i32),
                sds((), i32), sds((), i32), sds((), i32)]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = jax.jit(step, donate_argnums=(1, 2)).lower(
                [sds(params[n].shape) for n in names], pools, tails,
                *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    return _entry_instructions(text), text


# what ``match`` finds in the two programs, spelt out
SHORT_CONV_NAMES = {
    # both programs': a tail's row cut out and written back in place,
    # the taps as float32
    "fusion f32[2048,1]",
    "fusion bf16[1,40960]",
    "dynamic_update_slice bf16[14337,40960]",
    # the decode program's: a slice a lane of the tails' pool, the
    # gate, the new states side by side
    "slice_multiply_fusion bf16[64,1,2048]",
    "constant_dynamic-slice_fusion bf16[1,40960]",
    "concatenate bf16[64,40960]",
    "broadcast_select_fusion bf16[64,40960]",
    "fusion bf16[64,4096]",
    "reshape bf16[64,2,2048]",
    "fusion bf16[64,1,2048]",
    "copy bf16[64,1,2048]",
    "pad_maximum_fusion bf16[64,2,2048]",
    # the chunk program's: its one tail, the gate, [state; s] and the
    # nine blocks' tails out of it
    "broadcast_select_fusion bf16[1,40960]",
    "slice_reduce_fusion bf16[4096]",
    "reshape bf16[1,2,2048]",
    "slice_multiply_fusion bf16[1,256,2048]",
    "fusion bf16[258,2048]",
    "fusion bf16[18,2048]",
    "reshape bf16[9,4096]",
}


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_dev_share_short_conv_matches_only_the_mix_on_the_v5e(program):
    """Both step programs as the cell runs them (13 layers, the
    engine's own options): EVERY instruction under a name the metric's
    ``match`` finds holds operations of the scope ``conv.mix`` (the
    tails' gather, the gates, the taps, the tails' scatter) and of no
    other scope, so a fusion of another scope that the compiler names
    alike fails here."""
    found, _ = _compiled_for_the_v5e(program)
    match = _match()
    hit = [(name, held) for name, held in found if re.search(match, name)]
    assert hit
    for name, held in hit:
        assert held["conv"] and not held["other"], (name, held)


def test_dev_share_short_conv_names_nothing_stale():
    """The ``match`` finds exactly ``SHORT_CONV_NAMES`` in the two
    programs (a name the compiler no longer makes would read 0 unseen),
    and they hold two fifths of the scope's operations by count (558 of
    1,316 in the two programs): what the compiler merged into a
    neighbour's fusion (``fusion f32[64]`` / ``f32[256]``, the taps and
    the gate beside the products' own work) goes under that fusion's
    name and is left out: a lower bound."""
    match = _match()
    found = [x for program in ("decode", "chunk")
             for x in _compiled_for_the_v5e(program)[0]]
    assert {name for name, _ in found if re.search(match, name)} \
        == SHORT_CONV_NAMES
    of_conv = sum(held["conv"] for _, held in found)
    read = sum(held["conv"] for name, held in found
               if re.search(match, name))
    assert read >= 0.4 * of_conv, (read, of_conv)


LONGER = json.dumps({
    "answer_len": {"dist": "uniform", "min": 24, "max": 32},
    "think_s": {"dist": "exponential", "mean": 0.05},
    "history_len": {"dist": "uniform", "min": 16, "max": 40}})
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


def planted(seed, *more):
    """``planted_fault_tail.py`` at the rehearsal size: (the lines it
    printed, what was compared)."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "benchmarks",
                                      "planted_fault_tail.py"), *more,
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", "--rehearse", "--mix-override", LONGER,
         "--config-override", MORE],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any(ln.startswith("PLANTED FAULT") for ln in lines)
    assert json.loads(lines[-1])["rehearsal_correct"] is False
    c = compared(lines)
    assert c["finished_with_wrong_length"] and c["engine_step_failures"]
    return lines, c


@pytest.mark.parametrize("seed", [1, 2])
def test_chunks_that_start_from_zeros_are_not_correct(seed):
    """``planted_fault_tail.py`` (the upper reading of the cell's
    ``regret_mean`` on the chip comes from it): a model whose chunk
    programs ignore the tail they should continue from is caught (here a
    chunk is 16 positions and a turn's new tokens lie within a few
    positions of a chunk's edge)."""
    _, c = planted(seed)
    assert not c["regret_max"] and not c["regret_mean"]


def test_a_walk_through_a_neighbour_s_table_is_not_correct():
    """``planted_fault_tail.py --period`` (the upper reading of the
    cell's ``regret_max``): a decoding slot that now and then walks
    another conversation's rows is caught by the largest regret."""
    _, c = planted(1, "--period", "8")
    assert not c["regret_max"]
