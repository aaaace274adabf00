"""Ragged paged attention (Pallas) — kernel and engine-path tests.

The kernel (ops/ragged_paged_attn.py) runs under interpret mode on
CPU, so tier-1 exercises the REAL kernel logic token-for-token against
the XLA oracle: per-slot pos/width/block-tables as data, width-masked
scratch writes, and the one-program compile-matrix collapse the
``attn_impl="ragged"`` engine path claims.  Tests marked ``pallas``
involve the kernel; ``test_kernel_compiled_lowering_on_tpu`` asks the
real Mosaic compiler (compile-only, no chip needed) which shapes it
takes.

NUMERICS CONTRACT: ``attn_impl="ragged"`` is a flash-style
online-softmax loop over the slot's live blocks.  Online softmax
reorders float summation, so the kernel is ALLCLOSE to the oracle
(not bitwise); end-to-end, GREEDY streams are asserted
token-identical to the XLA arm across the full layout matrix and
seeded streams are asserted deterministic (same seed, same stream).

Tests marked ``longctx`` cover prompts spanning many KV blocks — the
streaming kernel's O(block_size x window) working-set claim; the
small-shape twins run in tier-1, the multi-thousand-token leg is
additionally marked slow.
"""
import contextlib
import math
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTModel
from paddle_tpu.serving import Engine


@pytest.fixture(scope="module")
def tiny_gpt():
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("registry", monitor.StatRegistry())
    kw.setdefault("kv_block_size", 8)
    return Engine(model, **kw)


def _prompts(n, lens=(5, 21, 3, 17, 7, 12)):
    rng = np.random.RandomState(7)
    return [rng.randint(0, 128, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


def _ref(model, prompt, n):
    return model.generate(paddle.to_tensor(prompt[None, :]),
                          max_new_tokens=n).numpy()[0]


def _serve_mixed(model, prompts, max_new=6, greedy_only=False, **kw):
    """Serve a greedy+seeded mix and return the token streams."""
    eng = _engine(model, **kw)
    reqs = []
    for i, p in enumerate(prompts):
        if i % 2 and not greedy_only:
            reqs.append(eng.submit(p, max_new_tokens=max_new,
                                   temperature=0.8, top_p=0.9,
                                   seed=77 + i))
        else:
            reqs.append(eng.submit(p, max_new_tokens=max_new))
    eng.run_until_idle()
    return [r.result(timeout=2).tolist() for r in reqs], eng


# -- kernel unit level ------------------------------------------------

def _kernel_oracle(q, k_flat, v_flat, tables, pos, width, bs):
    """The batched _slot_attn math over the gathered rows."""
    import jax
    import jax.numpy as jnp
    B, W, H, hd = q.shape
    nb = tables.shape[1]
    gidx = ((np.asarray(tables) * bs)[:, :, None]
            + np.arange(bs)[None, None, :]).reshape(B, -1)
    k_rows = np.asarray(k_flat)[gidx]
    v_rows = np.asarray(v_flat)[gidx]
    scores = jnp.einsum("bqhd,bkhd->bhqk",
                        jnp.asarray(q, jnp.float32),
                        jnp.asarray(k_rows, jnp.float32)) \
        * (1.0 / math.sqrt(hd))
    L = nb * bs
    visible = (np.arange(L)[None, None, :]
               <= (np.asarray(pos)[:, None]
                   + np.arange(W)[None, :])[:, :, None])
    scores = jnp.where(jnp.asarray(visible)[:, None, :, :], scores,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return np.asarray(jnp.einsum("bhqk,bkhd->bqhd", probs,
                                 jnp.asarray(v_rows, jnp.float32)))


@pytest.mark.pallas
def test_kernel_matches_oracle():
    """Per slot, for real lanes, against the XLA oracle math
    (``_slot_attn`` over the block-table gather): the kernel's online
    softmax is allclose (block-sequential accumulation reorders the
    float sums).  Width-masked lanes (and whole parked width-0 slots)
    are zeroed EXACTLY."""
    import jax.numpy as jnp
    from paddle_tpu.ops.ragged_paged_attn import ragged_paged_attention

    rng = np.random.RandomState(0)
    B, W, H, hd = 4, 5, 4, 8
    bs, nb, NB = 8, 6, 20
    q = jnp.asarray(rng.randn(B, W, H, hd).astype(np.float32))
    k_flat = jnp.asarray(rng.randn(NB * bs, H, hd).astype(np.float32))
    v_flat = jnp.asarray(rng.randn(NB * bs, H, hd).astype(np.float32))
    tables = jnp.asarray(rng.randint(0, NB, (B, nb)).astype(np.int32))
    pos = jnp.asarray(np.array([3, 10, 0, 30], np.int32))
    width = jnp.asarray(np.array([1, 5, 0, 3], np.int32))
    out = np.asarray(ragged_paged_attention(
        q, k_flat, v_flat, tables, pos, width, block_size=bs))
    ctx = _kernel_oracle(q, k_flat, v_flat, tables, pos, width, bs)
    for b in range(B):
        w = int(width[b])
        if w:
            np.testing.assert_allclose(out[b, :w], ctx[b, :w],
                                       rtol=2e-5, atol=2e-6)
        assert np.all(out[b, w:] == 0.0), \
            "width-masked lanes must be zeroed (width is kernel data)"


@pytest.mark.pallas
@pytest.mark.longctx
def test_kernel_stream_allclose_long_tables():
    """Long-context kernel twin (prompts >= 8x block_size): a table
    of MANY live blocks, decode + verify + chunk widths mixed, int8
    per-block scales included — the streaming body stays allclose to
    the oracle while walking only the live horizon."""
    import jax.numpy as jnp
    from paddle_tpu.ops.ragged_paged_attn import ragged_paged_attention

    rng = np.random.RandomState(1)
    B, W, H, hd = 3, 5, 4, 8
    bs, nb, NB = 8, 16, 48                    # up to 128 ctx tokens
    q = jnp.asarray(rng.randn(B, W, H, hd).astype(np.float32))
    k_flat = jnp.asarray(rng.randn(NB * bs, H, hd).astype(np.float32))
    v_flat = jnp.asarray(rng.randn(NB * bs, H, hd).astype(np.float32))
    tables = jnp.asarray(rng.randint(0, NB, (B, nb)).astype(np.int32))
    pos = jnp.asarray(np.array([100, 127 - 5, 64], np.int32))
    width = jnp.asarray(np.array([1, 5, 3], np.int32))
    out = np.asarray(ragged_paged_attention(
        q, k_flat, v_flat, tables, pos, width, block_size=bs))
    ctx = _kernel_oracle(q, k_flat, v_flat, tables, pos, width, bs)
    for b in range(B):
        w = int(width[b])
        np.testing.assert_allclose(out[b, :w], ctx[b, :w],
                                   rtol=2e-5, atol=2e-6)
        assert np.all(out[b, w:] == 0.0)
    # int8 codes + per-block scales: the kernel dequantizes each
    # streamed block, so it agrees with the oracle over the
    # dequantized rows to float-reassociation tolerance at long
    # context too
    ck = jnp.asarray(rng.randint(-127, 128, (NB * bs, H, hd))
                     .astype(np.int8))
    cv = jnp.asarray(rng.randint(-127, 128, (NB * bs, H, hd))
                     .astype(np.int8))
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (NB, H))
                     .astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (NB, H))
                     .astype(np.float32))
    sq = np.asarray(ragged_paged_attention(
        q, ck, cv, tables, pos, width, block_size=bs, k_scale=ks,
        v_scale=vs))

    def deq(codes, scale):
        rows = codes.astype(jnp.float32).reshape(NB, bs, H, hd)
        return (rows * scale[:, None, :, None]).reshape(NB * bs, H, hd)

    ctx = _kernel_oracle(q, deq(ck, ks), deq(cv, vs), tables, pos,
                         width, bs)
    for b in range(B):
        w = int(width[b])
        np.testing.assert_allclose(sq[b, :w], ctx[b, :w],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_kernel_compiled_lowering_on_tpu():
    """What Mosaic says about the kernel, settled WITHOUT a chip: the
    installed libtpu compiles for a compile-only ``TPU v5 lite``
    topology device (``compile_check``).  The body lowers
    at the shapes that matter — bf16 pools, H=16, hd=128, block 16,
    a chunk window, the per-shard head counts of mp=2/4, f32 and int8
    pools — and is refused below one lane tile of head_dim.
    Agreement of the compiled kernel with the XLA path is a chip matter: ``chip_smoke.py`` checks it there."""
    import jax.numpy as jnp
    from jax.experimental import topologies
    from paddle_tpu.ops.ragged_paged_attn import compile_check

    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    assert dev.device_kind == "TPU v5 lite"
    c0 = dict(num_slots=8, num_heads=16, head_dim=128, block_size=16,
              blocks_per_slot=128, num_blocks=1025, device=dev)
    for kw in (dict(window=128, dtype=jnp.bfloat16),
               dict(window=1, dtype=jnp.bfloat16, num_heads=8),
               dict(window=1, dtype=jnp.bfloat16, num_heads=4),
               dict(window=4, dtype=jnp.float32),
               dict(window=1, dtype=jnp.bfloat16, quant=True)):
        compile_check(**{**c0, **kw})
    with pytest.raises(Exception, match="aligned to tiling"):
        compile_check(**{**c0, "window": 1, "dtype": jnp.bfloat16,
                         "head_dim": 64})


_POOL_SHAPE = (14337, 16, 640)
_POOL = "bf16[14337,16,640]"


@contextlib.contextmanager
def _described_v5e():
    """``sds(shape, dtype=bf16)``: shapes placed on the compile-only
    ``TPU v5 lite`` device, to lower and compile with.  (A compile for
    a described device is written to the persistent cache but cannot
    be read back: the cache is kept out meanwhile.)"""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
            tuple(shape), dtype, sharding=one)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()


def _ends_in_the_square(text, seq):
    """Instructions of a compiled module's text whose result, or one
    member of its tuple, is an array that ends in ``seq, seq`` (what
    stands between ``=`` and the operands)."""
    square = re.compile(r"\[(?:\d+,)*%d,%d\]" % (seq, seq))
    return [ln for ln in text.splitlines() if " = " in ln
            and square.search(ln.split(" = ", 1)[1].split("(%", 1)[0])]


def _forward_backward(attend):
    import jax

    def fwd_bwd(q, k, v, do):
        out, pull = jax.vjp(attend, q, k, v)
        return (out,) + pull(do)
    return fwd_bwd


def test_training_attention_writes_no_sequence_square_on_the_v5e():
    """Forward plus backward of attention at the training cell's shape
    (``[8, 1024, 16, 64]`` bf16, causal, no mask), on the path the rule
    gives for platform ``tpu`` and compiled for the compile-only ``TPU
    v5 lite`` device: the blockwise kernels (forward, fused backward)
    and no instruction whose array ends in ``1024, 1024``.  The dense
    form of the same call, compiled the same way, holds over fifty: the
    guard reads what it claims to."""
    import jax
    from paddle_tpu.nn.functional import attention as att

    def blockwise(q, k, v):
        return att._blockwise_attention(q, k, v, None, 0.125, True)

    def dense(q, k, v):
        return att._reference_attention(q, k, v, None, None, True)

    assert att.attention_path("tpu", 1024, 1024, 64, False) == "blockwise"
    with _described_v5e() as sds:
        x = sds((8, 1024, 16, 64))
        text, dense_text = (
            jax.jit(_forward_backward(f)).lower(x, x, x, x).compile()
            .as_text() for f in (blockwise, dense))
    assert not _ends_in_the_square(text, 1024)
    assert len(_ends_in_the_square(dense_text, 1024)) > 50
    kernels = re.findall(r"%(splash_\w+?)(?:\.\d+)* = ", text)
    assert sorted(set(kernels)) == ["splash_mha_dkv_no_residuals",
                                    "splash_mha_fwd_residuals"]
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_attention_over_a_mesh_of_four_v5e_lowers_without_the_kernel(
        monkeypatch):
    """A program sharded over the four described ``TPU v5 lite`` chips
    (the batch over ``dp``, as ``TrainStep`` shards it) whose builder
    published its mesh: ``_sdpa`` on platform ``tpu`` keeps the dense
    form, and the module lowers with no Mosaic call.  The same program
    with nothing published and no process mesh takes the kernel and
    cannot be lowered: GSPMD does not partition a Mosaic kernel, which
    is why the rule counts devices."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.nn.functional import attention as att
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_global_mesh", None)
    mesh = mesh_mod.build_mesh(dp=4, devices=topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, PartitionSpec(
                                 mesh_mod.DATA_AXES)))

    def lower():
        # a fresh function each time: nothing of an earlier trace reused
        return jax.jit(_forward_backward(
            lambda q, k, v: att._sdpa.raw_fn(q, k, v, is_causal=True))
        ).lower(x, x, x, x).as_text()

    with mesh_mod.compiling_for(mesh):
        text = lower()
    assert "tpu_custom_call" not in text
    assert "mhlo.num_partitions = 4" in text
    with pytest.raises(NotImplementedError, match="Mosaic"):
        lower()


def _latent_step_text(sds, program, *args):
    """The compiled text of ``program(attn, *args)``, a method of the
    latent attention at the published widths (16 heads of 128 + 64 /
    128, rank 512, hidden 2,048, bf16); the pool, 9 layers of 14,337
    blocks of 16 rows as the row spec stores them, comes second and is
    donated."""
    import jax
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.mla_moe import MLAttention
    from paddle_tpu.serving.kvcache import KVRowSpec

    with nn.LazyGuard():
        attn = MLAttention(2048, 16, 128, 64, 128, 512, 8e5, 1e-5)
    attn.to(dtype="bfloat16")
    params = dict(attn.named_parameters())
    names = sorted(params)
    assert KVRowSpec(9, "bfloat16", (("latent", (attn.row,)),)
                     ).pool_shapes((14337, 16)) == [_POOL_SHAPE]

    def step(p_list, *args):
        with _swapped(params, dict(zip(names, p_list))):
            return program(attn, *args)

    return jax.jit(step, donate_argnums=(2,)).lower(
        [sds(params[n].shape) for n in names], *args).compile().as_text()


def _computation(text, name):
    """The lines of the computation ``name`` of a compiled module's
    text, its header to its closing brace."""
    start = re.search(r"^%?" + re.escape(name) + r" \(", text, re.M).start()
    return text[start:text.index("\n}", start)]


def _pool_copies(text):
    """Instructions that copy or transpose an array of the pool's
    shape."""
    copies = re.compile(r"= " + re.escape(_POOL)
                        + r"\S* (copy|transpose)\(")
    return [ln for ln in text.splitlines() if copies.search(ln)]


def test_latent_pool_is_updated_in_place_on_the_v5e():
    """The latent decode attention at the published widths (16 heads
    of 128 + 64 / 128, rank 512, 32 slots, 8,192 positions), compiled
    for the compile-only ``TPU v5 lite`` device: with the row spec's
    lane padding (576 -> 640) the pool is a row-major parameter that
    the step scatters into and walks, no instruction copies or
    transposes it, and no gather fetches more of it than one trip's
    group of (slot, chunk) items.
    (Unpadded, the TPU runtime lays a ``[blocks, 16, 576]`` array out
    with the BLOCK axis minor and every step transposed it there and
    back: 264 MB a layer; chip run, PR 28.)  The grouped expert
    product compiles at a decode step's and a chunk's shapes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.moe import grouped_matmul
    from paddle_tpu.models.mla_moe import MLAttention
    from paddle_tpu.models.programs import walk_chunk, walk_group

    with _described_v5e() as sds:
        text = _latent_step_text(
            sds, MLAttention.decode_slots_paged, sds((32, 1, 2048)),
            sds(_POOL_SHAPE), sds((32, 512), jnp.int32),
            sds((32,), jnp.int32))
        for m, k, n in ((192, 2048, 2816), (1536, 1408, 2048)):
            jax.jit(lambda x, w, g: grouped_matmul(x, w, g, "gmm")).lower(
                sds((m, k)), sds((64, k, n)),
                sds((64,), jnp.int32)).compile()
    assert _POOL in text
    assert not _pool_copies(text)
    # the walk is a work list (PR 30): no fetch of cached rows is wider
    # than one trip's group of items, 32 x 256 rows of the pool's width
    rows = walk_group(32) * walk_chunk(8192, 16)
    fetched = [int(np.prod([int(d) for d in dims.split(",")]))
               for dims in re.findall(
                   r"= bf16\[([\d,]+)\]\S* gather\(", text)]
    assert fetched and max(fetched) == rows * 640
    assert " while(" in text


def test_latent_chunk_rows_are_written_in_place_on_the_v5e():
    """The latent chunk attention at the published widths (a chunk of
    256 rows, a table of 512 blocks), compiled for the compile-only
    ``TPU v5 lite`` device: the one loop left is the slot's walk.  The
    chunk's rows reach the pool by 17 in-place updates of the pool as
    it lies, a block each; no instruction comes from a scatter (which
    this compiler makes a loop of 256 trips with the pool in its
    carry: the second ``while`` this test found at PR 32's parent) and
    none copies or transposes the pool."""
    import jax.numpy as jnp
    from paddle_tpu.models.mla_moe import MLAttention

    with _described_v5e() as sds:
        text = _latent_step_text(
            sds, MLAttention.prefill_chunk_paged, sds((1, 256, 2048)),
            sds(_POOL_SHAPE), sds((512,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32))
    lines = text.splitlines()
    assert sum(" while(" in ln for ln in lines) == 1
    assert not [ln for ln in lines
                if re.search(r" scatter\(|op_name=\"[^\"]*scatter", ln)]
    assert not _pool_copies(text)
    # the pool comes back through the updates: the first takes the
    # donated parameter, each next one the pool of the one before it,
    # the program returns the last
    chain = [m.groups() for m in (re.search(
        r"%(\S+) = " + re.escape(_POOL)
        + r"\S* dynamic-update-slice\(%([^,]+), ", ln) for ln in lines) if m]
    assert len(chain) == 17
    assert [src for _, src in chain[1:]] == [name for name, _ in chain[:-1]]
    assert [ln for ln in lines if re.search(
        r"%" + re.escape(chain[0][1]) + r" = " + re.escape(_POOL)
        + r"\S* parameter\(", ln)]
    assert [ln for ln in lines if "ROOT" in ln
            and "%" + chain[-1][0] + ")" in ln]
    assert "input_output_alias" in lines[0]


def test_block_rows_are_written_in_place_on_the_v5e():
    """The block-diffusion model's grouped-query attention at the
    published widths (32 query / 4 K/V heads of 128, hidden 2,048,
    blocks of 4 positions; ONE pool a layer of 10,241 blocks of 16
    rows of K and V flat, 1,024 wide), compiled for the compile-only
    ``TPU v5 lite`` device.  The pool lies in whole ``(8,128)(2,1)``
    tiles with nothing padded (as ``[.., 4, 128]`` rows it lay in
    quarter-filled ``T(4,128)`` tiles and its block gather ran at a
    third of the memory's rate: chip runs, PR 35 and 36).  The step
    over 32 slots writes each slot's 4 rows by ONE in-place update (32
    in all) and the chunk program its 256 rows by 17; the one loop of
    either is the walk, whose trip holds ONE gather of pool blocks (32
    items x 16 blocks of ``[16, 1024]`` in the step) that no reshape,
    copy or transpose relays out before the products read it; nothing
    comes from a scatter and nothing copies or transposes the pool.
    The grouped product compiles at the routed layer's two widths for
    a step's and a chunk's pairs under the tiles ``_gmm_tiling`` gives
    them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    from paddle_tpu.distributed.moe import grouped_matmul
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.sdar_moe import GQAttention, SDARMoEModel

    shape, pool = (10241, 16, 1024), "bf16[10241,16,1024]"
    with nn.LazyGuard():
        model = SDARMoEModel(dict(
            vocab_size=512, max_position_embeddings=4096,
            hidden_size=2048, moe_intermediate_size=768,
            num_hidden_layers=1, num_attention_heads=32,
            num_key_value_heads=4, head_dim=128, num_experts=128,
            num_experts_per_tok=8, rms_norm_eps=1e-6, rope_theta=1e6),
            mask_token_id=511)
    model.to(dtype="bfloat16")
    assert model.serving_spec().kv.pool_shapes((10241, 16)) == [shape]
    attn = model.blocks[0].attn
    params = dict(attn.named_parameters())
    names = sorted(params)

    def text(sds, program, *args):
        def step(p_list, *args):
            with _swapped(params, dict(zip(names, p_list))):
                return program(attn, *args)
        return jax.jit(step, donate_argnums=(2,)).lower(
            [sds(params[n].shape) for n in names], *args
        ).compile().as_text()

    with _described_v5e() as sds:
        i32 = jnp.int32
        step = text(sds, GQAttention.step_slots_paged, sds((32, 4, 2048)),
                    sds(shape), sds((32, 256), i32), sds((32,), i32),
                    sds((32,), i32))
        chunk = text(sds, GQAttention.prefill_chunk_paged,
                     sds((1, 256, 2048)), sds(shape), sds((256,), i32),
                     sds((), i32), sds((), i32), sds((), i32))
        for m, k, n in ((1024, 2048, 1536), (1024, 768, 2048),
                        (2048, 2048, 1536), (2048, 768, 2048)):
            jax.jit(lambda x, w, g: grouped_matmul(x, w, g, "gmm")).lower(
                sds((m, k)), sds((128, k, n)),
                sds((128,), jnp.int32)).compile()
    # (program, in-place updates, blocks one trip of the walk fetches)
    for program, updates, fetched in ((step, 32, 32 * 16),
                                      (chunk, 17, 16)):
        lines = program.splitlines()
        # the pool and the blocks fetched from it, wherever they
        # appear: row-major in whole tiles (S(1): an array kept on chip)
        assert pool + "{2,1,0:T(8,128)(2,1)}" in program
        assert set(re.findall(
            r"bf16\[(?:10241|%d),16,1024\]\{([^}]*)\}" % fetched,
            program)) <= {"2,1,0:T(8,128)(2,1)", "2,1,0:T(8,128)(2,1)S(1)"}
        assert sum(" while(" in ln for ln in lines) == 1
        assert not [ln for ln in lines if re.search(
            r" scatter\(|op_name=\"[^\"]*scatter", ln)]
        assert not [ln for ln in lines if re.search(
            r"= " + re.escape(pool) + r"\S* (copy|transpose)\(", ln)]
        assert len(re.findall(re.escape(pool)
                              + r"\S* dynamic-update-slice\(",
                              program)) == updates
        assert "input_output_alias" in lines[0]
        # the walk's trip: the loop's body holds one gather of pool
        # blocks, and the blocks it fetched reach the products as they
        # lie (a reshape, copy or transpose of them is a relayout of
        # 16.8 MB a trip: 11.3 ms a step against 3.7, chip run, PR 36)
        body = _computation(program, re.search(
            r" while\(.*body=%?([\w.\-]+)", program).group(1))
        blocks = r"bf16\[%d,16,1024\]" % fetched
        called = "\n".join(_computation(program, name) for name in
                           re.findall(r"calls=%?([\w.\-]+)", body))
        assert len(re.findall(
            r" gather\(.*slice_sizes=\{1,16,1024\}", body + called)) == 1
        assert len(re.findall(r"= " + blocks + r"\S* fusion\(",
                              body)) == 1
        assert not re.findall(
            r"= bf16\[[\d,]+\]\S* (?:reshape|copy|transpose)\(", body)


def test_gpt_decode_walks_a_work_list_on_the_v5e():
    """GPT's paged decode attention at the widths ``gpt3-1.3b-serve``
    runs (16 heads of 128, hidden 2,048, 32 slots, tables of 128 blocks
    of 16 rows, K and V pools of 3,073 blocks), compiled for the
    compile-only ``TPU v5 lite`` device: ONE loop a layer, the walk,
    whose trip count the compiler does not know (it is read from
    ``pos``: ``ceil(items / group)``); no gather fetches more cached
    rows than one trip's group of (slot, chunk) items (8 of them here:
    K and V are 4,096 numbers a position, four times the width a trip
    of 32 items is sized for); no float32 value is as large as a pool,
    the largest being one trip's rows; nothing copies or transposes a
    pool.  (To the longest window the loop ran ``max`` and not ``sum /
    group`` trips, 83-86% of both GPT cells' device time; ledger,
    PR 42.)"""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.gpt import GPTAttention
    from paddle_tpu.models.programs import walk_chunk, walk_group

    with nn.LazyGuard():
        attn = GPTAttention(2048, 16, dropout=0.0)
    attn.to(dtype="bfloat16")
    params = dict(attn.named_parameters())
    names = sorted(params)
    pool_shape = (3073, 16, 16, 128)

    def step(p_list, x, k_pool, v_pool, tables, pos):
        with _swapped(params, dict(zip(names, p_list))):
            out, k_pool, v_pool = attn.decode_slots_paged(
                paddle.Tensor(x), k_pool, v_pool, tables, pos)
        return out._data, k_pool, v_pool

    with _described_v5e() as sds:
        text = jax.jit(step, donate_argnums=(2, 3)).lower(
            [sds(params[n].shape) for n in names], sds((32, 1, 2048)),
            sds(pool_shape), sds(pool_shape), sds((32, 128), jnp.int32),
            sds((32,), jnp.int32)).compile().as_text()
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert len(loops) == 1 and "known_trip_count" not in loops[0]
    # a trip takes its items' queries by their slots: the work list
    body = _computation(text, re.search(
        r" while\(.*body=%?([\w.\-]+)", loops[0]).group(1))
    called = "\n".join(_computation(text, name) for name in
                       re.findall(r"calls=%?([\w.\-]+)", body))
    group = walk_group(32, 2 * 16 * 128)
    assert group == 8        # K and V are 4,096 numbers a position
    assert re.findall(r"= bf16\[%d,16,128\]\S* gather\(" % group,
                      body + called)

    def sizes(pattern):
        return [int(np.prod([int(d) for d in dims.split(",")]))
                for dims in re.findall(pattern, text)]
    rows = group * walk_chunk(2048, 16)
    fetched = sizes(r"= bf16\[([\d,]+)\]\S* gather\(")
    assert fetched and max(fetched) == rows * 16 * 128
    f32 = sizes(r"= f32\[([\d,]+)\]")
    assert f32 and max(f32) == rows * 16 * 128 < np.prod(pool_shape)
    assert not re.findall(r"= bf16\[3073,16,16,128\]\S* (?:copy|transpose)\(",
                          text)


# -- the decode attention's core: the kernel behind _slot_attn --------

@pytest.fixture
def one_device(monkeypatch):
    """No process-wide mesh while the test runs: an earlier test of the
    same worker may have left one (``Engine(mesh=...)``, ``fleet.init``
    publish theirs), and a program over a mesh keeps the walk."""
    from paddle_tpu.distributed import mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "_global_mesh", None)


def _core_attn(heads=2):
    """A ``GPTAttention`` at heads of 128 (the kernel's tile) with
    seeded float32 weights."""
    from paddle_tpu.models.gpt import GPTAttention
    paddle.seed(3)
    return GPTAttention(heads * 128, heads, dropout=0.0)


def _core_case(pages, window, tail, bs=8, slots=8, rng=None):
    """Pools, tables and positions whose windows end at every edge a
    step of ``pages`` pages has: inside a step, at a step's last row
    and at the next step's first, inside the first block, a slot two
    steps and a half long; parked lanes between the live ones; the
    last slot live (``tail`` "live": its step has no successor to
    prefetch for) or parked."""
    rng = rng or np.random.RandomState(pages * 10 + window)
    R = pages * bs
    # a table of no whole steps, longer than the one chunk (256 rows)
    # that either form reads whole
    nb = max(3 * pages + 2, 34)
    NB = slots * nb + 1
    ends = [0, R - window, 0, R - window + 1, 5 - min(window, 4),
            R + R // 2, 0, 2 * R + R // 2]
    if tail == "parked":
        ends[-1], ends[2] = 0, 2 * R + R // 2
    pos = np.asarray(ends, np.int32)
    assert pos.max() + window <= nb * bs and pos.min() >= 0
    tables = np.zeros((slots, nb), np.int32)
    order = rng.permutation(np.arange(1, NB))
    for b in range(slots):
        if pos[b]:
            n = -(-(pos[b] + window) // bs)
            tables[b, :n] = order[b * nb:b * nb + n]
    pool = (NB, bs, 2, 128)
    return (rng.randn(slots, window, 256).astype(np.float32),
            rng.randn(*pool).astype(np.float32),
            rng.randn(*pool).astype(np.float32), tables, pos)


def _slot_attn_as(monkeypatch, platform, attn, x, k_pool, v_pool,
                  tables, pos):
    """``_slot_attn``'s output when the step programs are traced for
    ``platform`` (the kernel itself runs interpreted on this CPU)."""
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    monkeypatch.setattr(gpt, "_backend", lambda: platform)
    qa, _, _ = attn._qkv_step(paddle.Tensor(jnp.asarray(x)))
    return np.asarray(attn._slot_attn(
        qa, jnp.asarray(k_pool), jnp.asarray(v_pool),
        None if tables is None else jnp.asarray(tables),
        jnp.asarray(pos))._data)


@pytest.mark.pallas
@pytest.mark.parametrize("tail", ["live", "parked"])
@pytest.mark.parametrize("window", [1, 3])
@pytest.mark.parametrize("pages", [8, 16, 32])
def test_slot_attn_kernel_matches_the_walk(one_device, monkeypatch, pages, window,
                                           tail):
    """The kernel behind ``_slot_attn`` against the walk it replaces on
    a TPU, on the edges its body adds: a slot whose rows end inside a
    step of pages, at a step's last row and at the next step's first,
    a slot of one block, parked lanes between live ones, a last live
    slot whose prefetch has no successor, the decode window (1) and
    the verify window (k + 1), 8 / 16 / 32 pages a step.  Both are an
    online softmax over the same rows; a parked lane (position 0)
    gives the projection of zeros in both."""
    from paddle_tpu.ops import ragged_paged_attn as rpa
    attn = _core_attn()
    case = _core_case(pages, window, tail)
    monkeypatch.setattr(rpa, "_STEP_ROWS", pages * 8)
    assert _traces_the_kernel(monkeypatch, "tpu", attn, *case)
    walk = _slot_attn_as(monkeypatch, "cpu", attn, *case)
    kernel = _slot_attn_as(monkeypatch, "tpu", attn, *case)
    np.testing.assert_allclose(kernel, walk, rtol=2e-5, atol=2e-5)
    parked = case[4] == 0
    assert parked.any() and not parked.all()
    assert np.array_equal(kernel[parked], walk[parked])


@pytest.mark.pallas
@pytest.mark.parametrize("W", [1, 2])
def test_slot_attn_kernel_contracts_bfloat16_rows_as_stored(W):
    """bf16 queries over bf16 pools, a decode row (W 1) and a window
    (W 2), never upcast outside VMEM: the rows go to the matrix unit
    as they are (two heads a 32-bit word, cut apart by a shift and a
    mask), the sums and the weights stay float32 (the weights as three
    bf16 terms over ONE pass of the rows), so the kernel stands as
    close to the float32 oracle over the same bf16 values as float32
    arithmetic does: far inside one bf16 step (2 ** -8) of the
    context."""
    import jax.numpy as jnp
    from paddle_tpu.ops.ragged_paged_attn import ragged_paged_attention

    rng = np.random.RandomState(5)
    B, H, hd, bs, nb, NB = 3, 4, 128, 8, 9, 40

    def bf16(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)
                           ).astype(jnp.bfloat16)
    q, k, v = bf16(B, W, H, hd), bf16(NB * bs, H, hd), bf16(NB * bs, H, hd)
    tables = jnp.asarray(rng.randint(0, NB, (B, nb)).astype(np.int32))
    pos = jnp.asarray(np.array([70, 0, 33], np.int32))
    width = jnp.asarray(np.array([W, 0, 1], np.int32))
    out = ragged_paged_attention(q, k, v, tables, pos, width,
                                 block_size=bs, pages=4)
    assert out.dtype == jnp.bfloat16
    ctx = _kernel_oracle(q, k, v, tables, pos, width, bs)
    for b in (0, 2):
        w = int(width[b])
        # the output is rounded to bf16 once: half a step of 2 ** -8
        np.testing.assert_allclose(
            np.asarray(out[b, :w].astype(jnp.float32)), ctx[b, :w],
            rtol=2 ** -8, atol=2 ** -9)
    assert not np.asarray(out[1].astype(jnp.float32)).any()


_CORE = dict(paged=True, quant=False, head_dim=128, mesh=False,
             table_rows=2048, block_size=16)


@pytest.mark.parametrize("platform,change,form", [
    ("tpu", {}, "kernel"),
    ("cpu", {}, "walk"),
    ("gpu", {}, "walk"),
    ("tpu", {"quant": True}, "walk"),
    ("tpu", {"head_dim": 64}, "walk"),
    ("tpu", {"paged": False, "block_size": None}, "walk"),
    ("tpu", {"mesh": True}, "walk"),
    ("tpu", {"table_rows": 256}, "walk"),
    ("tpu", {"head_dim": 256, "block_size": 32}, "kernel"),
])
def test_slot_attn_core_rule(platform, change, form):
    """The ONE place that chooses between the kernel and the walk, from
    platform, layout, pool dtype, head size, mesh and table length,
    with a reason ``/healthz`` can show."""
    from paddle_tpu.models.gpt import slot_attn_core
    got, why = slot_attn_core(platform, **{**_CORE, **change})
    assert got == form and why


def _traces_the_kernel(monkeypatch, platform, attn, *case):
    """Whether ``_slot_attn``, traced for ``platform``, holds a Pallas
    call."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    monkeypatch.setattr(gpt, "_backend", lambda: platform)
    x, k_pool, v_pool, tables, pos = case
    if tables is not None:
        tables = jnp.asarray(tables)

    def run(x, k_pool, v_pool, pos):
        qa, _, _ = attn._qkv_step(paddle.Tensor(x))
        return attn._slot_attn(qa, k_pool, v_pool, tables, pos)._data
    return "pallas_call" in str(jax.make_jaxpr(run)(
        jnp.asarray(x), k_pool, v_pool, jnp.asarray(pos)))


@pytest.mark.pallas
def test_slot_attn_takes_the_form_the_rule_names(one_device, monkeypatch):
    """``_slot_attn`` itself, traced and not run: the kernel for paged
    float pools at heads of 128 on a TPU platform string; the walk on
    the CPU, for ``QuantKV`` pools, at heads of 64, for a contiguous
    cache, for the einsum form whose weights are sharded, under a
    process-wide mesh, and for a table of one chunk."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.gpt import GPTAttention
    from paddle_tpu.serving.quant import QuantKV

    attn = _core_attn()
    x, k_pool, v_pool, tables, pos = _core_case(8, 1, "live")
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)
    case = (x, k_pool, v_pool, tables, pos)
    assert _traces_the_kernel(monkeypatch, "tpu", attn, *case)
    assert not _traces_the_kernel(monkeypatch, "cpu", attn, *case)
    # int8 pools: codes and one scale a block and head
    quant = [QuantKV(jnp.zeros(p.shape, jnp.int8),
                     jnp.ones(p.shape[:1] + p.shape[2:3], jnp.float32))
             for p in (k_pool, v_pool)]
    assert not _traces_the_kernel(monkeypatch, "tpu", attn, x, *quant,
                                  tables, pos)
    # heads of 64: the same pools' numbers as four heads
    paddle.seed(3)
    narrow = GPTAttention(256, 4, dropout=0.0)
    assert not _traces_the_kernel(
        monkeypatch, "tpu", narrow, x,
        k_pool.reshape(k_pool.shape[:2] + (4, 64)),
        v_pool.reshape(v_pool.shape[:2] + (4, 64)), tables, pos)
    # a contiguous cache: [B, L, H, hd] buffers and no tables
    bufs = [jnp.zeros((8, 512, 2, 128), jnp.float32)] * 2
    assert not _traces_the_kernel(monkeypatch, "tpu", attn, x, *bufs,
                                  None, pos)
    # a table of one chunk (256 rows)
    assert not _traces_the_kernel(monkeypatch, "tpu", attn, x, k_pool,
                                  v_pool, tables[:, :32],
                                  np.minimum(pos, 200))
    # a process-wide mesh (``Engine(mesh=...)`` publishes one)
    devices = np.asarray(jax.devices()[:2]).reshape(2, 1)
    monkeypatch.setattr(mesh_mod, "_global_mesh",
                        jax.sharding.Mesh(devices, ("mp", "dp")))
    assert not _traces_the_kernel(monkeypatch, "tpu", attn, *case)
    monkeypatch.setattr(mesh_mod, "_global_mesh", None)
    # the einsum form whose weights carry 'mp' specs
    paddle.seed(3)
    sharded = GPTAttention(256, 2, dropout=0.0, use_mp=True)
    assert not _traces_the_kernel(monkeypatch, "tpu", sharded, *case)


@pytest.mark.parametrize("ahead", [1, 4])
def test_stream_rows_counts_what_the_kernel_fetches(ahead):
    """``stream_rows``, the host's count behind
    ``serving.decode_rows_walked`` where the core is the kernel,
    against a brute-force count of the kernel's copies: for every live
    slot, page after page until the one that holds the window's last
    row, nothing for a parked slot, no page past a slot's last and no
    padding items (``walk_rows`` counts whole trips of 8 items of 256
    rows)."""
    from paddle_tpu.models.programs import walk_rows
    from paddle_tpu.ops.ragged_paged_attn import stream_rows
    bs, table_rows = 16, 2048
    rng = np.random.RandomState(11)
    for _ in range(20):
        pos = rng.randint(1, table_rows - ahead, 32)
        pos[rng.rand(32) < 0.7] = 0
        brute = 0
        for p in pos:
            page = 0
            while p and page * bs <= p + ahead - 1:
                brute += bs
                page += 1
        got = stream_rows(pos, ahead, table_rows, bs)
        assert got == brute
        live = int(np.minimum(pos[pos > 0] + ahead, table_rows).sum())
        assert live <= got < live + bs * int((pos > 0).sum())
        assert got <= walk_rows(pos, ahead, table_rows, bs,
                                row_width=2 * 16 * 128)
    assert stream_rows(np.zeros(32, int), ahead, table_rows, bs) == 0


@pytest.fixture
def core_gpt():
    """Two layers at heads of 128 and a 512-entry position table: the
    smallest model whose decode attention can take the kernel.  A new
    model a test: the model keeps its step programs by shape."""
    paddle.seed(0)
    m = GPTModel(num_layers=2, hidden_size=256, num_heads=2,
                 vocab_size=128, max_position=512, dropout=0.0)
    m.eval()
    return m


@pytest.mark.pallas
@pytest.mark.parametrize("spec_k", [None, 2])
def test_engine_decodes_through_the_kernel(one_device, monkeypatch, core_gpt, spec_k):
    """An engine whose model traces for a TPU platform (the kernel runs
    interpreted here): greedy streams token-identical to
    ``generate()`` through decode (S = 1) and speculative verify
    (S = k + 1), prompts on both sides of one 256-row step; every
    decode dispatch counted in ``serving.attn_kernel_dispatches``, the
    rows by the kernel's rule, and ``/healthz`` names the form and
    why."""
    from paddle_tpu.models import gpt
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
               for n in (5, 300, 253)]
    refs = [_ref(core_gpt, p, 5).tolist() for p in prompts]
    monkeypatch.setattr(gpt, "_backend", lambda: "tpu")
    kw = {"spec_k": spec_k} if spec_k else {}
    eng = _engine(core_gpt, num_slots=4, max_seq_len=512, **kw)
    core = eng.debug_requests()["engine"]["attn_core"]
    assert core == {"form": "kernel", "platform": "tpu", "head_dim": 128,
                    "pool_dtype": "float32", "why": core["why"]}
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    assert [r.result(timeout=2).tolist() for r in reqs] == refs
    reg = eng.registry
    ticks = reg.get("serving.fused_sample_ticks").value
    assert ticks > 0
    assert reg.get("serving.attn_kernel_dispatches").value == ticks
    walked = reg.get("serving.decode_rows_walked").value
    live = reg.get("serving.decode_rows_live").value
    # whole blocks of 8 rows, at most one a live slot past its rows
    assert walked % 8 == 0 and live <= walked < live + 8 * 3 * ticks


def test_engine_names_the_walk_and_why(tiny_gpt, core_gpt):
    """On the CPU, at heads of 16 and for int8 pools the engine keeps
    the walk, says so in ``/healthz`` and counts no kernel dispatch."""
    for model, kw, word in ((tiny_gpt, {}, "head size 16"),
                            (core_gpt, {"max_seq_len": 512}, "platform cpu"),
                            (core_gpt, {"max_seq_len": 512,
                                        "kv_dtype": "int8"}, "int8")):
        eng = _engine(model, **kw)
        core = eng.debug_requests()["engine"]["attn_core"]
        assert core["form"] == "walk" and word in core["why"]
        eng.submit(_prompts(1)[0], max_new_tokens=3)
        eng.run_until_idle()
        assert eng.registry.get(
            "serving.attn_kernel_dispatches").value == 0
        assert eng.registry.get("serving.fused_sample_ticks").value > 0
    ragged = _engine(core_gpt, max_seq_len=512, attn_impl="ragged")
    assert ragged.debug_requests()["engine"]["attn_core"] is None


@pytest.mark.pallas
def test_gpt_decode_streams_pages_through_one_kernel_on_the_v5e(
        one_device, monkeypatch):
    """GPT's paged decode attention at the widths ``gpt3-1.3b-serve``
    runs, traced for a TPU and compiled for the compile-only ``TPU v5
    lite`` device: no loop and no gather of cached rows is left where
    the walk stood, ONE Mosaic call a layer reads both pools where
    they lie (nothing copies, transposes or converts a pool), and the
    engine's compile check takes the decode and the verify window."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.gpt import GPTAttention
    from paddle_tpu.ops.ragged_paged_attn import compile_check

    with nn.LazyGuard():
        attn = GPTAttention(2048, 16, dropout=0.0)
    attn.to(dtype="bfloat16")
    params = dict(attn.named_parameters())
    names = sorted(params)
    pool_shape = (3073, 16, 16, 128)

    def step(p_list, x, k_pool, v_pool, tables, pos):
        with _swapped(params, dict(zip(names, p_list))):
            out, k_pool, v_pool = attn.decode_slots_paged(
                paddle.Tensor(x), k_pool, v_pool, tables, pos)
        return out._data, k_pool, v_pool

    with _described_v5e() as sds:
        # the described device first: looking it up asks for the
        # process's real backend
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(step, donate_argnums=(2, 3)).lower(
            [sds(params[n].shape) for n in names], sds((32, 1, 2048)),
            sds(pool_shape), sds(pool_shape), sds((32, 128), jnp.int32),
            sds((32,), jnp.int32)).compile().as_text()
        dev = sds((1,)).sharding._device
        for window in (1, 5):
            compile_check(num_slots=32, window=window, num_heads=16,
                          head_dim=128, block_size=16,
                          blocks_per_slot=128, num_blocks=3073,
                          dtype=jnp.bfloat16, device=dev)
    assert " while(" not in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert "ragged_paged_attn_stream" in text
    assert not re.findall(r"= bf16\[[\d,]*16,128\]\S* gather\(", text)
    assert not re.findall(
        r"= (?:bf16|f32)\[(?:3073,16|49168),16,128\]\S* "
        r"(?:copy|transpose|convert)\(", text)


# -- the grouped-query walk's trip as a kernel (ops/gq_walk_trip.py) --------

def _gq_case(S, reach, K, g, positions, dtype="float32", bs=8, blocks=96,
             seed=3):
    """``GQAttention.attend``'s arguments over a pool of noise: one
    slot a position (0: a parked lane), each with its own blocks in a
    shuffled order.  Items are 256 rows (32 blocks of 8) and a trip
    takes one item a slot at most (``walk_group``)."""
    import jax.numpy as jnp
    from paddle_tpu.models.sdar_moe import GQAttention
    rng = np.random.RandomState(seed)
    B, hd, W = len(positions), 128, 2 * K * 128
    attn = GQAttention(64, K * g, K, hd, 1e4, 1e-6, S, reach=reach)
    order = 1 + rng.permutation(B * blocks)

    def noise(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)
                           ).astype(dtype)
    return (attn, noise(B, S, K * g, hd), noise(B, S, W),
            noise(1 + B * blocks, bs, W),
            jnp.asarray(order.reshape(B, blocks).astype(np.int32)),
            jnp.asarray(np.asarray(positions, np.int32)))


def _attend_as(monkeypatch, platform, attn, *args):
    """``attend``'s output and whether it holds a Pallas call, traced
    for ``platform`` (the kernel runs interpreted on this CPU)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import sdar_moe
    monkeypatch.setattr(sdar_moe, "_backend", lambda: platform)
    text = str(jax.make_jaxpr(lambda *a: attn.attend(*a))(*args))
    out = jax.jit(lambda *a: attn.attend(*a))(*args)
    return np.asarray(out.astype(jnp.float32)), "pallas_call" in text


# what each case holds beside a parked slot (position 0) and a last
# trip with padding items (the items are no whole number of trips):
# ``pos`` on an item's edge (256, 512), on a block's edge (264), inside
# a block (700, 513) and a slot whose rows end in the first item
_GQ_CASES = {
    # a reach that straddles an item: rows 401..700 of slot 0 lie in
    # items 1 and 2, rows 214..513 of slot 3 in items 0, 1 and 2
    "S=1, a reach": dict(S=1, reach=300, K=2, g=3,
                         positions=[700, 0, 256, 513, 40, 264]),
    "S=1, no reach": dict(S=1, reach=None, K=2, g=3,
                          positions=[700, 0, 256, 513, 767, 264]),
    "S=4": dict(S=4, reach=None, K=4, g=8,
                positions=[700, 0, 256, 512, 44, 764, 264]),
    "S=4, a reach": dict(S=4, reach=258, K=2, g=2,
                         positions=[700, 0, 256, 512, 44]),
    "every slot parked": dict(S=1, reach=None, K=2, g=2,
                              positions=[0, 0, 0]),
}


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_GQ_CASES))
def test_gq_trip_kernel_matches_the_walk(one_device, monkeypatch, case,
                                         dtype):
    """A trip of ``GQAttention.attend``'s work list as the kernel
    against the XLA trip it replaces on a TPU, over ragged work lists
    (``_GQ_CASES``).  Both are an online softmax over the same rows;
    float32 pools agree to rounding, bfloat16 rows are contracted as
    stored with float32 sums and weights (three bf16 terms) where the
    walk's products are ``Precision.HIGHEST``: inside half a bf16 step
    of the output."""
    args = _gq_case(dtype=dtype, **_GQ_CASES[case])
    walk, is_kernel = _attend_as(monkeypatch, "cpu", *args)
    assert not is_kernel
    kernel, is_kernel = _attend_as(monkeypatch, "tpu", *args)
    assert is_kernel
    tol = 2e-5 if dtype == "float32" else 2 ** -8
    np.testing.assert_allclose(kernel, walk, rtol=tol, atol=tol)
    parked = np.asarray(args[-1]) == 0
    assert parked.any()
    assert np.array_equal(kernel[parked], walk[parked])


@pytest.mark.pallas
def test_gq_trip_folds_a_run_into_the_state_where_it_lies(one_device):
    """The kernel alone on one trip of four items, two runs (slot 2:
    items of chunks 0 and 1; slot 0: chunk 0) and a padding item: the
    state of the slots the trip names moves as a float64 fold of the
    same rows says, every other slot's state comes back bit for bit,
    and the padding item's pages (ids past the pool: a copy would
    fault) are never asked for."""
    import jax.numpy as jnp
    from paddle_tpu.ops import gq_walk_trip as trip
    rng = np.random.RandomState(9)
    B, K, g, S, hd, bs, P = 4, 2, 2, 1, 128, 8, 4
    R, chunk, W = trip.state_rows(g * S, np.float32), P * bs, 2 * K * hd
    pool = rng.randn(40 * bs, W).astype(np.float32)
    qg = rng.randn(B, S, K, g, hd).astype(np.float32)
    top = rng.randn(B, K, g, S).astype(np.float32)
    den = (1 + rng.rand(B, K, g, S)).astype(np.float32)
    acc = rng.randn(B, S, K, g, hd).astype(np.float32)
    pos = np.array([20, 0, 50, 0], np.int32)
    cols = np.array([[3, 9, 1, 30], [7, 2, 11, 5], [4, 6, 8, 10],
                     [10 ** 6] * 4], np.int32)
    slot_of = np.array([2, 2, 0, 3], np.int32)
    chunk_of = np.array([0, 1, 0, 0], np.int32)
    valid = np.array([True, True, True, False])
    meta = trip.trip_meta(jnp.asarray(cols), jnp.asarray(slot_of),
                          jnp.asarray(chunk_of), jnp.asarray(valid),
                          jnp.asarray(pos), chunk)
    state = trip.pack_state(jnp.asarray(top), jnp.asarray(den),
                            jnp.asarray(acc), R)
    out = trip.gq_walk_trip(
        state, trip.pack_queries(jnp.asarray(qg), R), jnp.asarray(pool),
        meta, heads=K, steps=S, reach=None, block_size=bs)
    got_den, got_acc = trip.unpack_state(out, K, g, S)
    out = np.asarray(out)
    for b in (1, 3):
        assert np.array_equal(out[b], np.asarray(state)[b])
    for b, items in ((2, (0, 1)), (0, (2,))):
        rows = np.concatenate([pool[c * bs:(c + 1) * bs]
                               for i in items for c in cols[i]])
        at = np.concatenate([chunk_of[i] * chunk + np.arange(chunk)
                             for i in items])
        rows = rows[at < pos[b]].astype(np.float64)
        for k in range(K):
            keys = rows[:, 2 * k * hd:(2 * k + 1) * hd]
            vals = rows[:, (2 * k + 1) * hd:(2 * k + 2) * hd]
            for gi in range(g):
                sc = keys @ qg[b, 0, k, gi].astype(np.float64) \
                    / math.sqrt(hd)
                m = max(top[b, k, gi, 0], sc.max())
                keep = math.exp(top[b, k, gi, 0] - m)
                p = np.exp(sc - m)
                np.testing.assert_allclose(
                    got_den[b, k, gi, 0], den[b, k, gi, 0] * keep
                    + p.sum(), rtol=1e-5)
                np.testing.assert_allclose(
                    got_acc[b, 0, k, gi], acc[b, 0, k, gi] * keep
                    + p @ vals, rtol=1e-4, atol=1e-4)


_GQ_CORE = dict(paged=True, quant=False, head_dim=128, mesh=False,
                table_rows=4096, block_size=16, slots=32)


@pytest.mark.parametrize("platform,change,form,word", [
    ("tpu", {}, "kernel", "one TPU"),
    ("cpu", {}, "walk", "platform cpu"),
    ("tpu", {"head_dim": 64}, "walk", "head size 64"),
    ("tpu", {"quant": True}, "walk", "int8 pools"),
    ("tpu", {"mesh": True}, "walk", "a mesh"),
    ("tpu", {"table_rows": 256}, "walk", "one chunk"),
    ("tpu", {"slots": 1}, "walk", "one slot"),
    ("tpu", {"slots": 2, "table_rows": 32768}, "kernel", "one TPU"),
])
def test_gq_walk_core_rule(platform, change, form, word):
    """The rule the grouped-query walk shares with ``_slot_attn``
    (``models/programs.py`` ``slot_attn_core``), asked with the slots
    of the program: each reason for the XLA trip and the one for the
    kernel, in words ``/healthz`` can show."""
    from paddle_tpu.models.programs import slot_attn_core
    got, why = slot_attn_core(platform, **{**_GQ_CORE, **change})
    assert got == form and word in why


@pytest.mark.pallas
def test_gq_attend_takes_the_form_the_rule_names(one_device, monkeypatch):
    """``attend`` itself, traced and not run: the kernel for several
    slots over a float pool longer than one chunk at heads of 128 on a
    TPU platform string; the XLA trip on the CPU, for one slot (the
    chunk program), for a table of one chunk, at heads of 64, for an
    int8 pool and under a process-wide mesh."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.sdar_moe import GQAttention

    attn, q, new, pool, tables, pos = _gq_case(
        1, None, 2, 2, [700, 0, 300])

    def traced(platform, attn, *args):
        return _attend_as(monkeypatch, platform, attn, *args)[1]
    assert traced("tpu", attn, q, new, pool, tables, pos)
    assert not traced("cpu", attn, q, new, pool, tables, pos)
    assert not traced("tpu", attn, q[:1], new[:1], pool, tables[:1],
                      pos[:1])
    assert not traced("tpu", attn, q, new, pool, tables[:, :32],
                      jnp.minimum(pos, 200))
    narrow = GQAttention(64, 8, 4, 64, 1e4, 1e-6, 1)
    assert not traced("tpu", narrow, q.reshape(3, 1, 8, 64), new, pool,
                      tables, pos)
    assert not traced("tpu", attn, q, new, pool.astype(jnp.int8), tables,
                      pos)
    devices = np.asarray(jax.devices()[:2]).reshape(2, 1)
    monkeypatch.setattr(mesh_mod, "_global_mesh",
                        jax.sharding.Mesh(devices, ("mp", "dp")))
    assert not traced("tpu", attn, q, new, pool, tables, pos)


@pytest.mark.parametrize("reach", [None, 300])
def test_walk_rows_counts_what_the_trip_kernel_copies(reach):
    """``walk_rows(padded=False)``, the host's count behind
    ``serving.decode_rows_walked`` where a trip is the kernel: the
    work list's items and no padding item, against a brute-force count
    and against the XLA trips' whole groups."""
    from paddle_tpu.models.programs import walk_rows
    bs, table_rows, chunk = 16, 4096, 256
    rng = np.random.RandomState(13)
    for _ in range(20):
        pos = rng.randint(1, table_rows - 4, 32)
        pos[rng.rand(32) < 0.5] = 0
        brute = 0
        for p in pos[pos > 0]:
            first = 0 if reach is None else max(p + 1 - reach, 0) // chunk
            brute += (-(-p // chunk) - first) * chunk
        got = walk_rows(pos, 0, table_rows, bs, 1024, reach, padded=False)
        whole = walk_rows(pos, 0, table_rows, bs, 1024, reach)
        assert got == brute
        assert got <= whole < got + 32 * chunk and whole % (32 * chunk) == 0


def _seed_leaves(model, seed=0):
    """Every leaf of ``model`` drawn from ``seed`` (matrices normal
    0.08, gains 1 + normal 0.1, a router's bias normal 0.1)."""
    import jax
    import jax.numpy as jnp
    model.eval()
    for i, (name, p) in enumerate(model.named_parameters()):
        v = jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(seed), i), tuple(p.shape), jnp.float32)
        if name.endswith("_bias"):
            v = 0.1 * v
        else:
            v = 1.0 + 0.1 * v if len(p.shape) == 1 else 0.08 * v
        p.set_value(v)
    return model


_GQ_ROUTED = dict(
    vocab_size=128, max_position_embeddings=512, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=2,
    rope_theta=10000)


def _gq_family(family, head_dim=128):
    """A new tiny model of one of the three families whose decode
    attention is ``GQAttention.attend`` (a new one a test: a model
    keeps its step programs by shape), heads of ``head_dim``."""
    from paddle_tpu.models import AfmoeModel, Lfm2MoeModel, SDARMoEModel
    if family == "sdar_moe":
        return _seed_leaves(SDARMoEModel(dict(
            _GQ_ROUTED, head_dim=head_dim, num_hidden_layers=2,
            norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
            rms_norm_eps=1e-6, rope_scaling=None), block_length=4,
            denoising_steps=4, mask_token_id=127))
    if family == "afmoe":
        return _seed_leaves(AfmoeModel(dict(
            _GQ_ROUTED, head_dim=head_dim, num_hidden_layers=3,
            num_dense_layers=1, num_shared_experts=1, route_norm=True,
            route_scale=2.448, score_func="sigmoid", n_group=1,
            topk_group=1, mup_enabled=True, sliding_window=270,
            rms_norm_eps=1e-5, rope_scaling=None,
            layer_types=["sliding_attention", "full_attention",
                         "sliding_attention"])))
    return _seed_leaves(Lfm2MoeModel(dict(
        _GQ_ROUTED, hidden_size=4 * head_dim, num_hidden_layers=3,
        num_dense_layers=1, conv_L_cache=3, conv_bias=False,
        norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
        norm_eps=1e-5, layer_types=["conv", "full_attention", "conv"])))


def _gq_served(monkeypatch, family, platform, requests, **kw):
    """``requests`` [(prompt, max_new)], one after the other's first
    token at most, through an engine over a new model of ``family``
    whose programs are traced for ``platform``."""
    from paddle_tpu.models import sdar_moe
    monkeypatch.setattr(sdar_moe, "_backend", lambda: platform)
    eng = Engine(_gq_family(family, **kw), num_slots=3, max_seq_len=512,
                 kv_block_size=8, kv_blocks=200, prefill_chunk=64,
                 registry=monitor.StatRegistry())
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in requests[:2]]
    eng.run_until_idle()
    # the same prompt again: its whole blocks are adopted
    reqs += [eng.submit(p, max_new_tokens=m) for p, m in requests[2:]]
    eng.run_until_idle()
    return [list(r.generated) for r in reqs], eng


@pytest.mark.pallas
@pytest.mark.parametrize("family", ["sdar_moe", "afmoe"])
def test_engine_walks_grouped_query_rows_through_the_trip_kernel(
        one_device, monkeypatch, family):
    """Tiny engines of the two families whose heads take the kernel,
    their programs traced for a TPU (the kernel interpreted here):
    greedy streams token-identical to the XLA walk's through chunked
    prefill (prompts on both sides of one 256-row item, a window of
    270 in ``afmoe``), a prefix hit and decode; ``/healthz`` names the
    form, every decode dispatch is one of
    ``serving.attn_kernel_dispatches``, and the rows are the items'
    alone (no padding item)."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 127, (n,)).astype(np.int32)
               for n in (300, 40)]
    requests = [(prompts[0], 9), (prompts[1], 6), (prompts[0], 7)]
    walk, eng_w = _gq_served(monkeypatch, family, "cpu", requests)
    kernel, eng = _gq_served(monkeypatch, family, "tpu", requests)
    assert kernel == walk and all(kernel)
    core = eng.debug_requests()["engine"]["attn_core"]
    assert core == {"form": "kernel", "platform": "tpu", "head_dim": 128,
                    "pool_dtype": "float32", "why": core["why"]}
    assert eng_w.debug_requests()["engine"]["attn_core"]["form"] == "walk"
    reg = eng.registry
    assert reg.get("serving.prefix_hit_tokens").value > 0
    ticks = reg.get("serving.fused_sample_ticks").value
    assert ticks > 0
    assert reg.get("serving.attn_kernel_dispatches").value == ticks
    assert eng_w.registry.get("serving.attn_kernel_dispatches").value == 0
    # items of 256 rows and nothing else; the XLA trips pad to whole
    # groups of three
    walked = reg.get("serving.decode_rows_walked").value
    padded = eng_w.registry.get("serving.decode_rows_walked").value
    assert walked % 256 == 0 and 0 < walked < padded


@pytest.mark.parametrize("family,head_dim,word", [
    ("sdar_moe", 128, "platform cpu"),
    ("afmoe", 128, "platform cpu"),
    ("lfm2_moe", 64, "head size 64"),
])
def test_grouped_query_engines_name_the_walk_and_why(family, head_dim,
                                                     word, monkeypatch):
    """On the CPU every family keeps the XLA trip and says why; at
    heads of 64 (``lfm2_moe``) it keeps it on a TPU platform string
    too: the rule adapts by what it sees, not by the model's name."""
    from paddle_tpu.models import sdar_moe
    prompt = np.arange(1, 41, dtype=np.int32)
    for platform in ("cpu", "tpu") if head_dim % 128 else ("cpu",):
        monkeypatch.setattr(sdar_moe, "_backend", lambda: platform)
        got, eng = _gq_served(monkeypatch, family, platform,
                              [(prompt, 4)], head_dim=head_dim)
        core = eng.debug_requests()["engine"]["attn_core"]
        assert core["form"] == "walk" and word in core["why"]
        assert core["head_dim"] == head_dim and len(got[0]) == 4
        assert eng.registry.get(
            "serving.attn_kernel_dispatches").value == 0
        assert eng.registry.get("serving.fused_sample_ticks").value > 0


_GQ_CELLS = {
    # trinity-large-preview-serve: K=8, g=6, rows of 2,048, 16 items
    "trinity": dict(kv_heads=8, groups=6, steps=1, row_width=2048,
                    num_slots=32, group=16, num_blocks=15361),
    # sdar-30b-a3b-serve: K=4, g=8, S=4, rows of 1,024, 32 items
    "sdar": dict(kv_heads=4, groups=8, steps=4, row_width=1024,
                 num_slots=32, group=32, num_blocks=10241),
}


@pytest.mark.pallas
@pytest.mark.parametrize("cell,reach", [
    ("trinity", 4096), ("trinity", None), ("sdar", None)])
def test_gq_trip_kernel_compiles_on_the_v5e(cell, reach):
    """Mosaic takes the kernel at both cells' shapes (blocks of 16,
    bf16 pools, items of 16 pages) for the compile-only ``TPU v5
    lite`` device, as ``Engine`` asks at construction
    (``walk_kernel_check``); heads of 64 are refused by name."""
    import jax.numpy as jnp
    from paddle_tpu.ops.gq_walk_trip import compile_check
    with _described_v5e() as sds:
        dev = sds((1,)).sharding._device
        shape = dict(_GQ_CELLS[cell], head_dim=128, block_size=16,
                     pages=16, dtype=jnp.bfloat16, reach=reach, device=dev)
        compile_check(**shape)
        if cell == "sdar":
            with pytest.raises(Exception, match="aligned to tiling"):
                compile_check(**{**shape, "head_dim": 64, "kv_heads": 8})


@pytest.mark.pallas
def test_sdar_decode_keeps_one_loop_a_layer_around_the_trip_kernel(
        one_device, monkeypatch):
    """SDAR's step over 32 slots at the cell's widths (two layers),
    traced for a TPU and compiled for the compile-only ``TPU v5 lite``
    device: ONE device loop a layer's walk, whose body is the trip's
    slice of the work list and ONE Mosaic call that reads the pool
    where it lies and updates the running state in place (no gather
    of cached rows, no copy or convert of a pool, no float32 fusion
    in the loop)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import nn
    from paddle_tpu.jit import _swapped
    from paddle_tpu.models.sdar_moe import GQAttention

    with nn.LazyGuard():
        attn = GQAttention(2048, 32, 4, 128, 1e6, 1e-6, 4)
    attn.to(dtype="bfloat16")
    params = dict(attn.named_parameters())
    names = sorted(params)

    def step(p_list, h, pool, tables, pos):
        with _swapped(params, dict(zip(names, p_list))):
            return attn.step_slots_paged(h, pool, tables, pos, pos)

    with _described_v5e() as sds:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(step, donate_argnums=(2,)).lower(
            [sds(params[n].shape) for n in names], sds((32, 4, 2048)),
            sds((10241, 16, 1024)), sds((32, 256), jnp.int32),
            sds((32,), jnp.int32)).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert "gq_walk_trip" in text
    assert not re.findall(r"= bf16\[[\d,]*256,1024\]\S* gather\(", text)
    assert not re.findall(
        r"= (?:bf16|f32)\[(?:10241,16|163856),1024\]\S* "
        r"(?:copy|transpose|convert)\(", text)
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    start = text.index("%" + body + " ")
    loop = text[start:text.index("\n}\n", start)]
    assert "gq_walk_trip" in loop and " f32[" not in loop.replace(
        "f32[32,128,256]", "")


# -- knob validation --------------------------------------------------

def test_attn_impl_validation(tiny_gpt):
    with pytest.raises(ValueError, match="attn_impl"):
        GPTModel(num_layers=1, hidden_size=32, num_heads=2,
                 vocab_size=64, max_position=32, attn_impl="bogus")
    with pytest.raises(ValueError, match="attn_impl"):
        _engine(tiny_gpt, attn_impl="bogus")
    with pytest.raises(ValueError, match="paged"):
        _engine(tiny_gpt, attn_impl="ragged", kv_block_size=None)
    # the engine inherits the model's knob when not overridden
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0, attn_impl="ragged")
    m.eval()
    eng = _engine(m)
    assert eng.attn_impl == "ragged"
    assert _engine(m, attn_impl="xla").attn_impl == "xla"
    assert _engine(tiny_gpt).attn_impl == "xla"


# -- engine-path parity vs the XLA oracle -----------------------------

@pytest.mark.pallas
@pytest.mark.parametrize("cfg", [
    dict(async_depth=1),
    dict(async_depth=2),
    dict(prefill_chunk=8, async_depth=2),
    dict(spec_k=3, async_depth=2),
    dict(prefill_chunk=8, spec_k=3, async_depth=2),
    dict(kv_dtype="int8", async_depth=2),
    dict(kv_dtype="int8", prefill_chunk=8, spec_k=3, async_depth=2),
], ids=["plain-d1", "plain-d2", "chunked-d2", "spec-d2",
        "chunked-spec-d2", "kvint8-d2", "kvint8-chunked-spec-d2"])
def test_ragged_parity_vs_xla_oracle(tiny_gpt, cfg):
    """THE acceptance criterion, full layout matrix with the
    STREAMING kernel as the ``attn_impl="ragged"`` default: GREEDY
    streams are token-identical to the XLA oracle across paged plain
    / chunked / spec / int8-KV dispatch shapes at async depth 1 and 2
    — and equal per-request ``generate()``.  (The seeded-stream
    guarantee is determinism:
    ``test_ragged_stream_seeded_deterministic``.)"""
    prompts = _prompts(4)
    xla, _ = _serve_mixed(tiny_gpt, prompts, greedy_only=True,
                          attn_impl="xla", **cfg)
    rag, eng = _serve_mixed(tiny_gpt, prompts, greedy_only=True,
                            attn_impl="ragged", **cfg)
    assert xla == rag
    if cfg.get("kv_dtype") is None:
        # int8 engines legitimately diverge from the fp generate()
        # oracle (quantized cache); fp engines must not
        for i in range(4):
            assert rag[i] == _ref(tiny_gpt, prompts[i], 6).tolist()
    # refcount hygiene: the ragged path's width-masked writes never
    # leak a block reference
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    assert eng.block_pool.in_use() == 0


@pytest.mark.pallas
def test_ragged_stream_seeded_deterministic(tiny_gpt):
    """The streaming kernel's seeded contract: same seed => same
    stream, run-for-run (online softmax reorders float summation, so
    nothing is bitwise-vs-XLA — but a seeded stream must still be
    reproducible)."""
    p = _prompts(1)[0]
    runs = []
    for _ in range(2):
        eng = _engine(tiny_gpt, attn_impl="ragged", spec_k=2,
                      async_depth=2)
        r = eng.submit(p, max_new_tokens=10, temperature=0.8,
                       top_p=0.9, seed=42)
        eng.run_until_idle()
        runs.append(r.result(timeout=2).tolist())
    assert runs[0] == runs[1]


@pytest.mark.pallas
@pytest.mark.parametrize("cfg", [
    dict(),
    dict(prefill_chunk=8, spec_k=3),
], ids=["plain", "chunked-spec"])
def test_ragged_preempt_resume_parity(tiny_gpt, cfg):
    """Preemption-resume under the ragged kernel: the preempted
    stream's continuation is token-identical to an uninterrupted
    ``generate()`` (greedy), across the unified dispatch shapes."""
    eng = _engine(tiny_gpt, num_slots=1, attn_impl="ragged",
                  async_depth=2, **cfg)
    p_low, p_high = _prompts(2)
    low = eng.submit(p_low, max_new_tokens=12, priority=0)
    for _ in range(5):
        eng.step()
    assert not low.done()
    high = eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    np.testing.assert_array_equal(high.result(timeout=2),
                                  _ref(tiny_gpt, p_high, 4))
    np.testing.assert_array_equal(low.result(timeout=2),
                                  _ref(tiny_gpt, p_low, 12))
    assert low.preemptions >= 1
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    assert eng.block_pool.in_use() == 0


@pytest.mark.pallas
def test_ragged_preempt_seeded_stream_unchanged(tiny_gpt):
    """A seeded stream across a ragged-path preemption equals the
    uninterrupted run: the device key folds the emitted-token
    counter, and the kernel path preserves it across the resume."""
    p_low, p_high = _prompts(2)
    un = _engine(tiny_gpt, num_slots=1, attn_impl="ragged")
    r0 = un.submit(p_low, max_new_tokens=12, temperature=0.8,
                   top_p=0.9, seed=5)
    un.run_until_idle()
    eng = _engine(tiny_gpt, num_slots=1, attn_impl="ragged")
    low = eng.submit(p_low, max_new_tokens=12, temperature=0.8,
                     top_p=0.9, seed=5)
    for _ in range(5):
        eng.step()
    eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    assert low.preemptions >= 1
    assert low.result(timeout=2).tolist() == \
        r0.result(timeout=2).tolist()


# -- compile-matrix collapse (the perf_opt claim) ---------------------

@pytest.mark.pallas
def test_ragged_compile_matrix_collapse():
    """Satellite regression: a mixed workload (chunked long prompts +
    short decode + spec_k=3, paged, depth2) compiles STRICTLY FEWER
    programs under ``attn_impl="ragged"`` than under the XLA path —
    the (chunk shape, spec_k) matrix collapses to exactly ONE
    ``ragged_window`` program — and a second traffic wave compiles
    NOTHING on either arm (no steady-state thrash).  The dispatch
    count collapses with it: the XLA arm pays one program call per
    prefill chunk beside its fused ticks, the ragged arm's chunks ride
    inside the window dispatch."""
    prompts = _prompts(6)

    def wave(eng):
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle()
        for r in reqs:
            r.result(timeout=2)

    counts, dispatches = {}, {}
    for impl in ("xla", "ragged"):
        paddle.seed(0)
        m = GPTModel.from_config("tiny", dropout=0.0)  # fresh caches
        m.eval()
        reg = monitor.StatRegistry()
        eng = Engine(m, num_slots=4, max_seq_len=48, registry=reg,
                     kv_block_size=8, prefill_chunk=8, spec_k=3,
                     async_depth=2, attn_impl=impl)
        wave(eng)
        c1 = reg.get("serving.compiles_total").value
        wave(eng)
        c2 = reg.get("serving.compiles_total").value
        assert c2 == c1, \
            f"{impl}: second wave recompiled ({c1} -> {c2})"
        counts[impl] = c1
        dispatches[impl] = reg.get("serving.fused_sample_ticks").value \
            + (reg.get("serving.prefill_chunks").value
               if impl == "xla" else 0)
        if impl == "ragged":
            # exactly one program serves decode + spec-verify +
            # chunk-prefill — the collapse, not just a reduction
            assert c1 == 1
            assert len(m._ragged_window_fn_cache) == 1
    assert counts["ragged"] < counts["xla"]
    assert dispatches["ragged"] < dispatches["xla"], dispatches


@pytest.mark.pallas
def test_ragged_one_program_however_traffic_varies(tiny_gpt):
    """However prompt lengths, sampling params, and request mixes
    vary, a ragged engine config resolves to ONE compiled window
    program (widths are data, not shape)."""
    eng = _engine(tiny_gpt, prefill_chunk=8, spec_k=3,
                  attn_impl="ragged")
    before = len(tiny_gpt._ragged_window_fn_cache)
    for p in _prompts(6):
        eng.submit(p, max_new_tokens=4)
    eng.submit(_prompts(1)[0], max_new_tokens=4, temperature=0.7,
               top_k=20, seed=3)
    eng.run_until_idle()
    added = len(tiny_gpt._ragged_window_fn_cache) - before
    assert added <= 1  # one NEW program for this (B, W, pool) config


# -- epilogue / payload / surfaces ------------------------------------

@pytest.mark.pallas
def test_ragged_spec_d2h_payload_stays_97_bytes(tiny_gpt):
    """The acceptance scan folds into the ragged epilogue, so a spec
    tick still downloads picks [B, W] + n_acc + n_emit + the packed
    done mask = 97 bytes at B=4, spec_k=3 — the same steady state as
    the fused XLA spec path, with no separate acceptance dispatch."""
    eng = _engine(tiny_gpt, spec_k=3, attn_impl="ragged",
                  async_depth=2)
    reqs = [eng.submit(p, max_new_tokens=6) for p in _prompts(4)]
    eng.run_until_idle()
    for r in reqs:
        r.result(timeout=2)
    # picks 4*4*4 + n_acc 4*4 + n_emit 4*4 + done 1 = 97
    assert eng.registry.get("serving.d2h_bytes_per_tick").value == 97


@pytest.mark.pallas
def test_ragged_healthz_debug_and_trace_span(tiny_gpt):
    """/healthz and /debug/requests report the kernel selection AND
    the max observed context length, the trace carries
    ``decode.ragged_stream`` spans (never the XLA path's
    ``decode.dispatch``) so
    traces distinguish kernel dispatches, and the per-tick block-walk
    gauge is populated."""
    from paddle_tpu.serving.httpd import _Handler

    eng = _engine(tiny_gpt, prefill_chunk=8, attn_impl="ragged")
    p = _prompts(1)[0]
    r = eng.submit(p, max_new_tokens=4)
    eng.run_until_idle()
    r.result(timeout=2)
    dbg = eng.debug_requests()["engine"]
    assert dbg["attn_impl"] == "ragged"
    assert dbg["max_context_len"] == len(p) + 4

    h = object.__new__(_Handler)
    h.engine = eng
    h.path = "/healthz"
    sent = {}

    def _send(code, payload, ctype="application/json", headers=None):
        sent["resp"] = (code, payload)

    h._send = _send
    import json as _json
    h._send_json = lambda code, obj: _send(code, _json.dumps(obj))
    h.do_GET()
    code, body = sent["resp"]
    assert code == 200
    health = _json.loads(body)
    assert health["attn_impl"] == "ragged"
    assert health["max_context_len"] == len(p) + 4

    names = {ev.get("name")
             for ev in eng.chrome_trace()["traceEvents"]}
    assert "decode.ragged_stream" in names
    assert "decode.dispatch" not in names
    # block-walk attribution: the last dispatch walked >= 1 block
    assert eng.registry.get(
        "serving.kv_blocks_walked_per_tick").value >= 1


@pytest.mark.pallas
def test_ragged_trace_span_and_walk_gauge(tiny_gpt):
    """The kernel's dispatches carry their own span name and the walk
    gauge reads the live horizon, not the whole per-slot table."""
    eng = _engine(tiny_gpt, num_slots=2, attn_impl="ragged")
    r = eng.submit(_prompts(1)[0], max_new_tokens=4)
    eng.run_until_idle()
    r.result(timeout=2)
    names = {ev.get("name")
             for ev in eng.chrome_trace()["traceEvents"]}
    assert "decode.ragged_stream" in names
    # one live lane on the final tick: a 5..9-token stream's horizon
    assert 1 <= eng.registry.get(
        "serving.kv_blocks_walked_per_tick").value < eng._bps


@pytest.mark.pallas
@pytest.mark.router
def test_router_probe_copies_attn_impl_signal(tiny_gpt):
    """The router prober copies ``attn_impl`` and
    ``max_context_len`` into the replica's registry signals like it
    does ``kv_dtype`` — the fleet view can tell which kernel body
    each replica serves and its long-context exposure."""
    from paddle_tpu.serving import (InProcessReplica, Router,
                                    RouterPolicy)

    eng = _engine(tiny_gpt, attn_impl="ragged")
    r = eng.submit(_prompts(1)[0], max_new_tokens=3)
    eng.run_until_idle()
    r.result(timeout=2)
    rep_client = InProcessReplica("r0", eng)
    probe = rep_client.probe()
    assert probe["attn_impl"] == "ragged"
    assert probe["max_context_len"] > 0
    router = Router({"r0": rep_client},
                    policy=RouterPolicy(seed=0), kv_block_size=8,
                    registry=monitor.StatRegistry())
    router.probe_once()
    rep = router._reps()[0]
    assert rep.signals["attn_impl"] == "ragged"
    assert rep.signals["max_context_len"] == probe["max_context_len"]


# -- long-context serving (the streaming kernel's reason to exist) ----

@pytest.fixture(scope="module")
def long_gpt():
    """The tiny config with a raised context ceiling — long-context
    engines need max_position above the tiny default of 64."""
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0, max_position=256)
    m.eval()
    return m


def _long_prompt(n, seed=3):
    rng = np.random.RandomState(seed)
    return rng.randint(0, 128, (n,)).astype(np.int32)


@pytest.mark.pallas
@pytest.mark.longctx
@pytest.mark.parametrize("cfg", [
    dict(),
    dict(prefill_chunk=8, async_depth=2),
    dict(kv_dtype="int8", prefill_chunk=8),
], ids=["plain", "chunked-d2", "kvint8-chunked"])
def test_longctx_greedy_identity(long_gpt, cfg):
    """Tier-1 long-context twin: a prompt spanning MANY KV blocks
    (>= 8x block_size) decodes greedily token-identical across the
    XLA oracle and the streaming kernel — and (fp
    engines) equals per-request ``generate()``.  This is the
    engine-level face of the kernel allclose test: reassociated float
    sums at 13+ blocks still never flip a greedy pick on a real
    checkpoint's logit margins."""
    p = _long_prompt(100)                       # 13 blocks of 8
    streams = {}
    for impl in ("xla", "ragged"):
        eng = _engine(long_gpt, num_slots=2, max_seq_len=128,
                      attn_impl=impl, **cfg)
        r = eng.submit(p, max_new_tokens=8)
        eng.run_until_idle()
        streams[impl] = r.result(timeout=5).tolist()
        assert eng.debug_requests()["engine"]["max_context_len"] \
            == len(p) + 8
    # the kernel's last dispatch walked its one live lane to the
    # causal horizon (107 positions = 14 blocks of 8), not the
    # 16-block table
    assert eng.registry.get(
        "serving.kv_blocks_walked_per_tick").value \
        == (len(p) + 8 - 2) // 8 + 1 == 14
    assert streams["xla"] == streams["ragged"]
    if cfg.get("kv_dtype") is None:
        assert streams["ragged"] == _ref(long_gpt, p, 8).tolist()


@pytest.mark.pallas
@pytest.mark.longctx
def test_longctx_preempt_resume(long_gpt):
    """Preemption-resume of a LONG stream under the streaming kernel:
    a high-priority arrival evicts a 100-token-context stream
    mid-decode; the resumed continuation is token-identical to the
    uninterrupted ``generate()``."""
    eng = _engine(long_gpt, num_slots=1, max_seq_len=128,
                  attn_impl="ragged", prefill_chunk=8, async_depth=2)
    p_long = _long_prompt(100)
    p_high = _long_prompt(9, seed=5)
    low = eng.submit(p_long, max_new_tokens=10, priority=0)
    for _ in range(400):
        if len(low.generated) >= 2:
            break
        eng.step()
    assert not low.done()
    high = eng.submit(p_high, max_new_tokens=4, priority=5)
    eng.run_until_idle()
    np.testing.assert_array_equal(high.result(timeout=5),
                                  _ref(long_gpt, p_high, 4))
    np.testing.assert_array_equal(low.result(timeout=5),
                                  _ref(long_gpt, p_long, 10))
    assert low.preemptions >= 1
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
    assert eng.block_pool.in_use() == 0


@pytest.mark.pallas
@pytest.mark.longctx
@pytest.mark.migration
def test_longctx_migration(long_gpt):
    """KV block migration of a LONG stream between streaming-kernel
    engines: export after a few emitted tokens moves the full
    13-block context, the destination finishes the stream
    token-identical to the unmigrated oracle."""
    p = _long_prompt(100)
    oracle = _engine(long_gpt, num_slots=2, max_seq_len=128,
                     attn_impl="ragged")
    r0 = oracle.submit(p, max_new_tokens=10)
    oracle.run_until_idle()
    ref = r0.result(timeout=5).tolist()

    src = _engine(long_gpt, num_slots=2, max_seq_len=128,
                  attn_impl="ragged")
    dst = _engine(long_gpt, num_slots=2, max_seq_len=128,
                  attn_impl="ragged")
    r = src.submit(p, max_new_tokens=10)
    for _ in range(400):
        if len(r.generated) >= 3 or r.done():
            break
        src.step()
    assert not r.done()
    d = src.migrate_out(request_id=r.id, min_tokens=3,
                        deliver="return", wait=False)
    verdict = None
    for _ in range(100):
        src.step()
        try:
            verdict = d.wait(0)
            break
        except TimeoutError:
            continue
    assert verdict is not None and verdict["payload"] is not None
    # a 100-token context + emitted tail crosses many blocks
    assert verdict["payload"]["kv"]["n_blocks"] >= 12
    got = None
    dm = dst.migrate_in(verdict["payload"], wait=False)
    for _ in range(100):
        dst.step()
        try:
            got = dm.wait(0)
            break
        except TimeoutError:
            continue
    assert got is not None
    dst.run_until_idle()
    assert got["request"].result(timeout=5).tolist() == ref
    src.run_until_idle()
    if src.prefix_cache is not None:
        src.prefix_cache.clear()
    assert src.block_pool.in_use() == 0


@pytest.mark.pallas
@pytest.mark.longctx
@pytest.mark.slow
def test_longctx_multithousand_token_leg(tiny_gpt):
    """The slow multi-thousand-token leg: a 2048-token prompt over a
    2304-position model, chunked prefill, streaming kernel — greedy
    decode matches per-request ``generate()`` and the walk gauge
    reads the live horizon (~256+ blocks), not the table size."""
    paddle.seed(0)
    m = GPTModel.from_config("tiny", dropout=0.0, max_position=2304)
    m.eval()
    p = _long_prompt(2048, seed=11)
    eng = Engine(m, num_slots=1, max_seq_len=2304, kv_block_size=16,
                 registry=monitor.StatRegistry(), attn_impl="ragged",
                 prefill_chunk=32, async_depth=2)
    r = eng.submit(p, max_new_tokens=6)
    eng.run_until_idle()
    got = r.result(timeout=30).tolist()
    assert got == _ref(m, p, 6).tolist()
    walked = eng.registry.get(
        "serving.kv_blocks_walked_per_tick").value
    assert walked >= 2048 // 16


def test_ragged_step_failure_recovers(tiny_gpt):
    """Step-failure recovery under the ragged path: waiters unblock
    loudly, refcounts rebuild to zero, and the engine serves correct
    streams afterwards."""
    eng = _engine(tiny_gpt, num_slots=2, attn_impl="ragged")
    prompts = _prompts(2)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()

    def boom(*a, **kw):
        raise RuntimeError("synthetic ragged dispatch failure")

    eng._ragged_fn = boom
    with pytest.raises(RuntimeError):
        eng.step()
    for r in reqs:
        with pytest.raises(RuntimeError, match="engine step failed"):
            r.result(timeout=2)
    assert eng.scheduler.occupancy() == 0
    assert eng.block_pool.in_use() == 0
    assert all(eng.block_pool.refcount(b) == 0
               for b in range(eng.block_pool.num_blocks))
    eng._ragged_fn = None
    r2 = eng.submit(prompts[0], max_new_tokens=6)
    eng.run_until_idle()
    assert r2.result(timeout=2).tolist() == \
        _ref(tiny_gpt, prompts[0], 6).tolist()
