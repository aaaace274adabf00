"""RNN/BiRNN wrappers, decode, grid_sample, hsigmoid/nce losses, static
shims (reference tests: test_rnn_cells.py, test_rnn_decode_api.py,
test_grid_sample_function.py, test_hsigmoid_op.py, test_nce.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, static
import paddle_tpu.nn.functional as F


def test_rnn_wrapper_matches_manual_cell_loop():
    paddle.seed(0)
    cell = nn.GRUCell(4, 8)
    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 5, 4)
                         .astype("float32"))
    out, final = nn.RNN(cell)(x)
    # manual unroll
    states = None
    outs = []
    for t in range(5):
        o, states = cell(x[:, t], states)
        outs.append(o.numpy())
    np.testing.assert_allclose(out.numpy(),
                               np.stack(outs, axis=1), rtol=1e-5)
    np.testing.assert_allclose(final.numpy(), outs[-1], rtol=1e-5)


def test_birnn_reverse_direction():
    paddle.seed(1)
    cell_fw, cell_bw = nn.SimpleRNNCell(3, 4), nn.SimpleRNNCell(3, 4)
    x = paddle.to_tensor(np.random.RandomState(1).rand(2, 6, 3)
                         .astype("float32"))
    out, _ = nn.BiRNN(cell_fw, cell_bw)(x)
    assert out.shape == [2, 6, 8]
    # backward half at t=last equals one bw-cell step on x[:, -1]
    o_last, _ = cell_bw(x[:, -1], None)
    np.testing.assert_allclose(out.numpy()[:, -1, 4:], o_last.numpy(),
                               rtol=1e-5)


def test_grid_sample_identity():
    # an identity grid reproduces the input (align_corners=True)
    h = w = 5
    ys, xs = np.meshgrid(np.linspace(-1, 1, h), np.linspace(-1, 1, w),
                         indexing="ij")
    grid = np.stack([xs, ys], axis=-1)[None].astype("float32")
    x = np.random.RandomState(2).rand(1, 2, h, w).astype("float32")
    out = F.grid_sample(paddle.to_tensor(x), paddle.to_tensor(grid))
    np.testing.assert_allclose(out.numpy(), x, atol=1e-5)


def test_grid_sample_zeros_padding():
    x = np.ones((1, 1, 4, 4), "float32")
    grid = np.full((1, 1, 1, 2), -3.0, "float32")  # far out of bounds
    out = F.grid_sample(paddle.to_tensor(x), paddle.to_tensor(grid),
                        padding_mode="zeros")
    assert float(out.numpy().ravel()[0]) == 0.0


def test_hsigmoid_trains():
    paddle.seed(3)
    num_classes, feat = 8, 16
    layer = nn.HSigmoidLoss(feat, num_classes)
    from paddle_tpu import optimizer
    opt = optimizer.Adam(learning_rate=0.1,
                         parameters=layer.parameters())
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.rand(32, feat).astype("float32"))
    y = paddle.to_tensor((rng.rand(32, 1) * num_classes).astype("int64"))
    first = last = None
    for _ in range(40):
        loss = paddle.mean(layer(x, y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
        last = float(loss)
    assert last < first * 0.5


def test_nce_trains():
    paddle.seed(4)
    layer = nn.NCELoss(8, 50, num_neg_samples=5)
    from paddle_tpu import optimizer
    opt = optimizer.Adam(learning_rate=0.05,
                         parameters=layer.parameters())
    rng = np.random.RandomState(4)
    x = paddle.to_tensor(rng.rand(16, 8).astype("float32"))
    y = paddle.to_tensor((rng.rand(16, 1) * 50).astype("int64"))
    first = last = None
    for _ in range(40):
        loss = paddle.mean(layer(x, y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
        last = float(loss)
    assert last < first


def test_beam_search_decode():
    paddle.seed(5)
    vocab, hidden, beam = 12, 16, 3
    cell = nn.GRUCell(8, hidden)
    emb = nn.Embedding(vocab, 8)
    proj = nn.Linear(hidden, vocab)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                               beam_size=beam, embedding_fn=emb,
                               output_fn=proj)
    init = paddle.to_tensor(np.random.RandomState(5)
                            .rand(4, hidden).astype("float32"))
    ids, lengths = nn.dynamic_decode(dec, inits=init, max_step_num=7,
                                     return_length=True)
    assert ids.shape == [4, beam, 7]
    assert lengths.shape == [4, beam]
    assert ids.numpy().max() < vocab
    # beams are sorted by score: beam 0 should exist and be valid ids
    assert (ids.numpy() >= 0).all()


def test_pairwise_distance_values():
    x = paddle.to_tensor(np.array([[3.0, 0.0]], "float32"))
    y = paddle.to_tensor(np.array([[0.0, 4.0]], "float32"))
    d = nn.PairwiseDistance(p=2.0)(x, y)
    assert float(d.numpy()[0]) == pytest.approx(5.0, rel=1e-4)


def test_static_compiled_program_runs():
    paddle.enable_static()
    main = static.Program()
    try:
        with static.program_guard(main):
            x = static.data("x", [4, 3])
            out = static.nn.fc(x, 2)
            compiled = static.CompiledProgram(main).with_data_parallel(
                loss_name=None, build_strategy=static.BuildStrategy())
            exe = static.Executor()
            res, = exe.run(compiled,
                           feed={"x": np.ones((4, 3), "float32")},
                           fetch_list=[out])
            assert res.shape == (4, 2)
    finally:
        paddle.disable_static()


def test_static_accuracy_auc_ops():
    paddle.enable_static()
    main = static.Program()
    try:
        with static.program_guard(main):
            pred = static.data("pred", [6, 2])
            label = static.data("label", [6, 1], dtype="int64")
            acc = static.accuracy(pred, label)
            a = static.auc(pred, label)
            exe = static.Executor()
            pv = np.array([[0.9, 0.1], [0.2, 0.8], [0.3, 0.7],
                           [0.6, 0.4], [0.1, 0.9], [0.8, 0.2]], "float32")
            lv = np.array([[0], [1], [1], [0], [1], [1]], "int64")
            accv, aucv = exe.run(feed={"pred": pv, "label": lv},
                                 fetch_list=[acc, a])
            assert float(accv) == pytest.approx(5 / 6, rel=1e-5)
            # ground truth: 7 of 8 (pos, neg) pairs concordant
            assert float(aucv) == pytest.approx(0.875, abs=0.01)
    finally:
        paddle.disable_static()


def test_serialize_program_roundtrip(tmp_path):
    paddle.enable_static()
    main = static.Program()
    try:
        with static.program_guard(main):
            x = static.data("x", [2, 3])
            out = static.nn.fc(x, 4)
            prog_bytes = static.serialize_program([x], [out])
            params_bytes = static.serialize_persistables([x], [out])
            exe = static.Executor()
            xv = np.ones((2, 3), "float32")
            ref, = exe.run(feed={"x": xv}, fetch_list=[out])
        static.save_to_file(str(tmp_path / "m.pdmodel"), prog_bytes)
        loaded = static.deserialize_program(
            static.load_from_file(str(tmp_path / "m.pdmodel")))
        got = loaded.run({"x": xv})[0]
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5)
    finally:
        paddle.disable_static()


def test_array_ops():
    arr = paddle.create_array()
    paddle.array_write(paddle.to_tensor([1.0]), 0, arr)
    paddle.array_write(paddle.to_tensor([2.0]), 1, arr)
    assert int(paddle.array_length(arr).numpy()) == 2
    assert float(paddle.array_read(arr, 1).numpy()[0]) == 2.0


# ---- regressions from code review ----------------------------------------

def test_dynamic_decode_under_jit():
    import jax
    paddle.seed(6)
    cell = nn.GRUCell(4, 8)
    emb = nn.Embedding(10, 4)
    proj = nn.Linear(8, 10)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                               beam_size=2, embedding_fn=emb,
                               output_fn=proj)

    def decode(init_arr):
        ids, lengths = nn.dynamic_decode(
            dec, inits=paddle.Tensor(init_arr), max_step_num=4,
            return_length=True)
        return ids._data, lengths._data

    import jax.numpy as jnp
    ids, lengths = jax.jit(decode)(
        jnp.ones((2, 8), jnp.float32))
    assert ids.shape == (2, 2, 4)


def test_decode_length_first_step_end():
    # a sequence ending at step 0 must have length 1, not max_step_num
    import jax.numpy as jnp
    from paddle_tpu.nn import decode as dec_mod

    class ConstDecoder:
        end_token = 1

        def initialize(self, inits):
            ids = jnp.zeros((1, 1), jnp.int32)
            lp = jnp.zeros((1, 1), jnp.float32)
            fin = jnp.zeros((1, 1), bool)
            return ids, {}, lp, fin

        def step(self, inputs, states):
            # end_token always wins
            logits = jnp.array([[0.0, 10.0, 0.0]], jnp.float32)
            return logits, states

    ids, lengths = dec_mod.dynamic_decode(ConstDecoder(), inits=None,
                                          max_step_num=5,
                                          return_length=True)
    assert int(lengths.numpy()[0, 0]) == 1


def test_dynamic_decode_return_length_false():
    paddle.seed(7)
    cell = nn.GRUCell(4, 8)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                               beam_size=2,
                               embedding_fn=nn.Embedding(10, 4),
                               output_fn=nn.Linear(8, 10))
    out = nn.dynamic_decode(
        dec, inits=paddle.to_tensor(np.ones((2, 8), "float32")),
        max_step_num=3)
    assert not isinstance(out, tuple)  # single value without lengths


def test_diag_embed_custom_dims():
    x = paddle.to_tensor(np.ones((2, 3), "float32"))
    out = F.diag_embed(x, dim1=0, dim2=1)
    assert out.shape == [3, 3, 2]


def test_grid_sample_reflection():
    x = np.arange(16, dtype="float32").reshape(1, 1, 4, 4)
    # coordinate just past the right edge reflects back inside
    grid = np.array([[[[1.5, 0.0]]]], "float32")
    out = F.grid_sample(paddle.to_tensor(x), paddle.to_tensor(grid),
                        padding_mode="reflection")
    border = F.grid_sample(paddle.to_tensor(x), paddle.to_tensor(grid),
                           padding_mode="border")
    # reflection != border clamp for out-of-range coords
    assert float(out.numpy().ravel()[0]) != float(
        border.numpy().ravel()[0])


def test_rnn_sequence_length_masks():
    paddle.seed(8)
    cell = nn.GRUCell(3, 5)
    x = np.random.RandomState(9).rand(2, 6, 3).astype("float32")
    lens = np.array([3, 6], "int64")
    out, final = nn.RNN(cell)(paddle.to_tensor(x),
                              sequence_length=paddle.to_tensor(lens))
    # padded steps of sequence 0 are zeroed
    np.testing.assert_array_equal(out.numpy()[0, 3:], 0.0)
    # final state of sequence 0 equals running only its 3 valid steps
    out3, final3 = nn.RNN(cell)(paddle.to_tensor(x[:1, :3]))
    np.testing.assert_allclose(final.numpy()[0], final3.numpy()[0],
                               rtol=1e-5)


def test_nce_log_q_includes_sample_count():
    # the noise term must use k*q: loss at init ~ -log sigmoid(-log(k/C))*k...
    # check indirectly: two layers with different k give different losses
    paddle.seed(10)
    x = paddle.to_tensor(np.zeros((4, 8), "float32"))
    y = paddle.to_tensor(np.zeros((4, 1), "int64"))
    l5 = nn.NCELoss(8, 100, num_neg_samples=5)
    # zero input -> logits = bias = 0 -> loss depends only on log_q term
    v5 = float(paddle.mean(l5(x, y)).numpy())
    l20 = nn.NCELoss(8, 100, num_neg_samples=20)
    v20 = float(paddle.mean(l20(x, y)).numpy())
    import math
    def expected(k):
        lq = math.log(k / 100)
        pos = math.log1p(math.exp(lq))          # softplus(-(0 - lq))
        neg = k * math.log1p(math.exp(-lq))     # k * softplus(0 - lq)... 
        return pos + neg
    # softplus(-( -lq)) = softplus(lq); neg: softplus(0 - lq)= softplus(-lq)
    assert v5 == pytest.approx(
        math.log1p(math.exp(math.log(5/100)))
        + 5 * math.log1p(math.exp(-math.log(5/100))), rel=1e-3)
    assert v20 != pytest.approx(v5, rel=1e-2)


def test_birnn_sequence_length_passthrough():
    paddle.seed(11)
    cell_fw, cell_bw = nn.GRUCell(3, 4), nn.GRUCell(3, 4)
    bi = nn.BiRNN(cell_fw, cell_bw)
    x = np.random.RandomState(12).rand(2, 5, 3).astype("float32")
    lens = np.array([2, 5], "int64")
    out, _ = bi(paddle.to_tensor(x),
                sequence_length=paddle.to_tensor(lens))
    # both directions zero the padded steps of sequence 0
    np.testing.assert_array_equal(out.numpy()[0, 2:], 0.0)


def test_reverse_rnn_sequence_length_ignores_padding():
    paddle.seed(12)
    cell = nn.GRUCell(3, 4)
    x = np.random.RandomState(13).rand(1, 6, 3).astype("float32")
    lens = np.array([3], "int64")
    rnn_rev = nn.RNN(cell, is_reverse=True)
    out, final = rnn_rev(paddle.to_tensor(x),
                         sequence_length=paddle.to_tensor(lens))
    # reverse run over only the valid prefix gives the same final state
    out_ref, final_ref = nn.RNN(cell, is_reverse=True)(
        paddle.to_tensor(x[:, :3]))
    np.testing.assert_allclose(final.numpy(), final_ref.numpy(), rtol=1e-5)


def test_npair_loss_single_implementation():
    import paddle_tpu.nn.functional as FF
    a = paddle.to_tensor(np.random.RandomState(1).rand(4, 8)
                         .astype("float32"))
    p = paddle.to_tensor(np.random.RandomState(2).rand(4, 8)
                         .astype("float32"))
    lab = paddle.to_tensor(np.array([0, 1, 0, 1], "int64"))
    v1 = float(FF.npair_loss(a, p, lab).numpy())
    v2 = float(FF.common.npair_loss(a, p, lab).numpy())
    assert v1 == pytest.approx(v2, rel=1e-6)


def test_affine_grid_identity_transform():
    theta = np.array([[[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]]], "float32")
    grid = F.affine_grid(paddle.to_tensor(theta), [1, 1, 4, 4])
    assert grid.shape == [1, 4, 4, 2]
    # identity theta + grid_sample reproduces the input
    x = np.random.RandomState(3).rand(1, 2, 4, 4).astype("float32")
    out = F.grid_sample(paddle.to_tensor(x), grid)
    np.testing.assert_allclose(out.numpy(), x, atol=1e-5)


def test_viterbi_square_layout_matches_bruteforce():
    # paddle.text contract: SQUARE transitions, BOS = n-2, EOS = n-1
    from itertools import product
    B, T, N = 2, 4, 5
    rng = np.random.RandomState(0)
    em = paddle.to_tensor(rng.rand(B, T, N).astype("float32"))
    tr = rng.rand(N, N).astype("float32")
    lens_np = np.array([4, 2], "int32")
    score, path = F.viterbi_decode(em, paddle.to_tensor(tr),
                                   paddle.to_tensor(lens_np))
    for bi in range(B):
        T_eff = int(lens_np[bi])
        e0 = em.numpy()[bi]
        best, bpath = -1e9, None
        for p in product(range(N), repeat=T_eff):
            s = tr[N - 2, p[0]] + e0[0, p[0]]
            for i in range(1, T_eff):
                s += tr[p[i - 1], p[i]] + e0[i, p[i]]
            s += tr[p[-1], N - 1]
            if s > best:
                best, bpath = s, p
        assert float(score.numpy()[bi]) == pytest.approx(best, rel=1e-4)
        assert list(path.numpy()[bi][:T_eff]) == list(bpath)


def test_linear_chain_crf_nll_nonnegative():
    B, T, N = 2, 4, 3
    rng = np.random.RandomState(0)
    em = paddle.to_tensor(rng.rand(B, T, N).astype("float32"))
    trans = paddle.to_tensor(rng.rand(N + 2, N).astype("float32"))
    lens = paddle.to_tensor(np.array([4, 2], "int32"))
    lab = paddle.to_tensor(rng.randint(0, N, (B, T)).astype("int32"))
    nll = F.linear_chain_crf(em, trans, lab, lens)
    assert nll.shape == [B, 1]
    assert (nll.numpy() >= 0).all()


def _fluid_to_square(trans_fluid, N):
    """[N+2, N] fluid CRF layout -> square [(N+2), (N+2)] text layout."""
    n = N + 2
    sq = np.full((n, n), -1e9, "float32")
    sq[:N, :N] = trans_fluid[2:]
    sq[n - 2, :N] = trans_fluid[0]       # BOS -> tag
    sq[:N, n - 1] = trans_fluid[1]       # tag -> EOS
    return sq


@pytest.mark.slow
def test_crf_loss_trains():
    # transition + emission params learn to predict a fixed tag sequence
    paddle.seed(13)
    B, T, N = 4, 5, 3
    rng = np.random.RandomState(14)
    feats = paddle.to_tensor(rng.rand(B, T, 8).astype("float32"))
    labels = paddle.to_tensor(
        np.tile(np.array([0, 1, 2, 1, 0], "int32"), (B, 1)))
    lens = paddle.to_tensor(np.full((B,), T, "int32"))
    proj = nn.Linear(8, N)
    trans = paddle.create_parameter([N + 2, N], "float32")
    from paddle_tpu import optimizer
    opt = optimizer.Adam(learning_rate=0.05,
                         parameters=proj.parameters() + [trans])
    first = last = None
    for _ in range(30):
        em = proj(feats)
        loss = paddle.mean(F.linear_chain_crf(em, trans, labels, lens))
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss)
        last = float(loss)
    assert last < first * 0.5
    # decoding recovers the trained sequence (convert fluid layout to the
    # square text layout, pad emissions for BOS/EOS tags)
    sq = paddle.to_tensor(_fluid_to_square(trans.numpy(), N))
    em = proj(feats).numpy()
    em_pad = np.concatenate(
        [em, np.full((B, T, 2), -1e9, "float32")], axis=-1)
    _, path = F.viterbi_decode(paddle.to_tensor(em_pad), sq, lens)
    np.testing.assert_array_equal(path.numpy(), labels.numpy())


def test_long_tail_functionals():
    pe = F.add_position_encoding(
        paddle.to_tensor(np.zeros((2, 4, 6), "float32")))
    # position 0: sin(0)=0 for first half, cos(0)=1 for second half
    np.testing.assert_allclose(pe.numpy()[0, 0, :3], 0.0, atol=1e-6)
    np.testing.assert_allclose(pe.numpy()[0, 0, 3:], 1.0, atol=1e-6)

    big = paddle.to_tensor(np.ones((2, 5), "float32"))
    small = paddle.to_tensor(np.ones((1, 3), "float32"))
    padded = F.pad_constant_like(big, small, pad_value=7.0)
    assert padded.shape == [2, 5]
    assert float(padded.numpy()[1, 4]) == 7.0

    fsp = F.fsp_matrix(
        paddle.to_tensor(np.ones((1, 2, 3, 3), "float32")),
        paddle.to_tensor(np.ones((1, 4, 3, 3), "float32")))
    np.testing.assert_allclose(fsp.numpy(), np.ones((1, 2, 4)),
                               rtol=1e-6)

    seq = F.im2sequence(
        paddle.to_tensor(np.arange(16, dtype="float32")
                         .reshape(1, 1, 4, 4)), filter_size=2, stride=2)
    assert seq.shape == [4, 4]
    np.testing.assert_array_equal(seq.numpy()[0], [0, 1, 4, 5])

    h = F.hash(paddle.to_tensor(np.array([1, 2, 3], "int64")),
               hash_size=100, num_hash=2)
    assert h.shape == [3, 2]
    assert (h.numpy() >= 0).all() and (h.numpy() < 100).all()
    # deterministic
    h2 = F.hash(paddle.to_tensor(np.array([1, 2, 3], "int64")),
                hash_size=100, num_hash=2)
    np.testing.assert_array_equal(h.numpy(), h2.numpy())


def test_im2sequence_asymmetric_padding():
    x = paddle.to_tensor(np.arange(16, dtype="float32")
                         .reshape(1, 1, 4, 4))
    # pad top only (reference order [up, left, down, right])
    s = F.im2sequence(x, filter_size=2, stride=2,
                      padding=[2, 0, 0, 0])
    # height becomes 6 -> oh = 3
    assert s.shape == [3 * 2, 4]
    np.testing.assert_array_equal(s.numpy()[0], [0, 0, 0, 0])  # pad rows
    with pytest.raises(NotImplementedError):
        F.im2sequence(x, filter_size=2, input_image_size=x)


def test_hash_many_and_pad_like_validation():
    h = F.hash(paddle.to_tensor(np.array([1, 2, 3], "int64")),
               hash_size=50, num_hash=4)   # was OverflowError for >= 3
    assert h.shape == [3, 4]
    with pytest.raises(ValueError):
        F.pad_constant_like(
            paddle.to_tensor(np.ones((2, 3), "float32")),
            paddle.to_tensor(np.ones((3, 2), "float32")))


@pytest.mark.parametrize("seq_q,seq_kv,want_q,want_kv", [
    (512, 4096, 512, 512), (8192, 8192, 512, 512), (1024, 1024, 512, 512),
    (256, 1024, 256, 512)])
def test_flash_default_block_sizes_clamp(seq_q, seq_kv, want_q, want_kv):
    """The blockwise kernel's blocks clamp to the sequence extent, 512
    at most (v5e, PR 38: 2.05 ms a layer at 1,024 positions against
    2.19 at blocks of 1,024, which skip nothing under the causal mask),
    and the fused backward takes the forward's."""
    from paddle_tpu.nn.functional import attention as att
    bs = att._default_block_sizes(seq_q, seq_kv)
    assert (bs.block_q, bs.block_kv) == (want_q, want_kv)
    assert bs.block_kv_compute == want_kv
    assert (bs.block_q_dkv, bs.block_kv_dkv) == (want_q, want_kv)
    assert bs.use_fused_bwd_kernel and bs.has_backward_blocks


@pytest.mark.parametrize("seq,want", [
    (2560, 512), (2176, 128), (3584, 512), (7680, 512), (1024, 512),
    (1280, 256)])
def test_flash_block_sizes_divide_sequence(seq, want):
    """Blocks must divide the sequence (the kernel's mask tables are
    cut in whole blocks); 2176 is admitted by the rule (divisible by
    128) but by no larger block."""
    from paddle_tpu.nn.functional import attention as att
    assert att._default_block_sizes(seq, seq).block_q == want
    assert seq % want == 0


@pytest.mark.parametrize("platform,seq_q,seq_kv,head_dim,masked,want", [
    # the training cell: gpt2-medium, 8 x 1,024 tokens, heads of 64
    ("tpu", 1024, 1024, 64, False, "blockwise"),
    ("tpu", 2048, 2048, 64, False, "blockwise"),
    ("tpu", 512, 512, 64, False, "blockwise"),
    ("tpu", 1024, 1024, 128, False, "blockwise"),
    ("tpu", 4096, 4096, 128, False, "blockwise"),
    # no Mosaic off the TPU
    ("cpu", 1024, 1024, 64, False, "dense"),
    ("gpu", 4096, 4096, 128, False, "dense"),
    # an arbitrary mask is the dense form's
    ("tpu", 1024, 1024, 64, True, "dense"),
    # whole 128-row tiles of both sequences only
    ("tpu", 1000, 1000, 64, False, "dense"),
    ("tpu", 1024, 1000, 64, False, "dense"),
    # under the measured crossover (v5e, PR 38): 256 positions at heads
    # of 64, 512 at heads of 128 (0.74 ms dense against 0.77)
    ("tpu", 256, 256, 64, False, "dense"),
    ("tpu", 512, 512, 128, False, "dense"),
    ("tpu", 128, 4096, 64, False, "dense"),
    # heads of 256 win from 2,048 positions only (1.05 against 1.14 ms
    # at 1,024; 2.33 against 1.54 at 2,048)
    ("tpu", 1024, 1024, 256, False, "dense"),
    ("tpu", 2048, 2048, 256, False, "blockwise"),
    # head sizes nobody measured (PR 38's tables: 64, 128 and 256)
    ("tpu", 1024, 1024, 80, False, "dense"),
    ("tpu", 2048, 2048, 192, False, "dense"),
])
def test_attention_path_rule(platform, seq_q, seq_kv, head_dim, masked,
                             want):
    """The dispatch is one pure function of what a call shows."""
    from paddle_tpu.nn.functional import attention as att
    assert att.attention_path(platform, seq_q, seq_kv, head_dim,
                              masked) == want


def test_attention_path_keeps_the_dense_form_on_a_mesh():
    """GSPMD cannot partition a Mosaic kernel: a program compiled for
    several devices keeps the dense form whatever the shapes."""
    from paddle_tpu.nn.functional import attention as att
    assert att.attention_path("tpu", 1024, 1024, 64, False,
                              devices=4) == "dense"
    assert att.attention_path("tpu", 1024, 1024, 64, False,
                              devices=1) == "blockwise"


@pytest.mark.parametrize("own,process,want", [
    (4, None, 4),    # examples/train_gpt2.py: build_mesh + mesh=, no global
    (1, 8, 1),       # a one-chip step after a fleet call left its mesh
    (None, None, 8),  # no mesh given: TrainStep makes the process's
])
def test_train_step_traces_attention_for_its_own_mesh(monkeypatch, own,
                                                      process, want):
    """What ``_sdpa`` hands the rule as ``devices`` is the size of the
    mesh the STEP is compiled for (``TrainStep.mesh``, published while
    it traces), whatever the process-global mesh holds: a step over an
    explicit mesh of four keeps the dense form though no global mesh is
    set, and a global mesh left behind does not turn a one-device step
    dense.  ``aot_compile`` lowers the same program."""
    import jax
    from paddle_tpu import distributed as dist, optimizer
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models import GPTModel
    from paddle_tpu.nn.functional import attention as att
    from paddle_tpu.parallel.train_step import TrainStep

    def mesh_of(n):
        return n and dist.build_mesh(dp=n, devices=jax.devices()[:n])
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh_of(process))
    seen = []
    rule = att.attention_path

    def spy(platform, seq_q, seq_kv, head_dim, masked, devices, dtype):
        seen.append(devices)
        return rule(platform, seq_q, seq_kv, head_dim, masked, devices,
                    dtype)
    monkeypatch.setattr(att, "attention_path", spy)
    model = GPTModel.from_config("tiny", dropout=0.0, fused_loss=True,
                                 max_position=64)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = TrainStep(model, opt, loss_fn=None, mesh=mesh_of(own))
    ids = np.random.RandomState(0).randint(0, 128, (8, 33)).astype(np.int32)
    batch = [ids[:, :-1], ids[:, 1:]]
    step.aot_compile(batch)
    traced = len(seen)
    assert traced and set(seen) == {want}
    assert np.isfinite(float(step.step(batch).numpy()))
    assert len(seen) == 2 * traced and set(seen) == {want}
    # nothing stays published once the step has been traced
    assert mesh_mod.program_devices() == (
        process or (8 if own is None else 1))


@pytest.mark.parametrize("dtype,head_dim,seq,want", [
    ("bfloat16", 64, 512, "blockwise"),
    # float32 moves twice the bytes through the dense form: the kernel
    # wins from 512 positions at heads of 128 too (2.57 against 1.25 ms)
    ("float32", 128, 512, "blockwise"), ("float32", 64, 256, "dense"),
    ("float32", 256, 4096, "dense"),    # not measured
    ("float16", 64, 1024, "dense"),     # Mosaic refuses it on the v5e
])
def test_attention_path_by_dtype(dtype, head_dim, seq, want):
    """The crossover is read by dtype and head size from what PR 38
    measured; a pair that is not in the table keeps the dense form."""
    import jax.numpy as jnp
    from paddle_tpu.nn.functional import attention as att
    for spelt in (dtype, jnp.dtype(dtype), getattr(jnp, dtype)):
        assert att.attention_path("tpu", seq, seq, head_dim, False,
                                  dtype=spelt) == want


def test_attention_has_no_module_switches():
    """One rule, no switch: the threshold and the block-size override
    that PR 38 removed stay removed."""
    from paddle_tpu.nn.functional import attention as att
    for name in ("FLASH_MIN_SEQ", "FLASH_BLOCK_SIZES", "_flash_available"):
        assert not hasattr(att, name), name


def test_sdpa_counts_the_path_it_traced():
    """``nn.attention.dense`` / ``.blockwise`` count call sites as they
    are traced: on the CPU every one is dense."""
    from paddle_tpu import monitor
    dense = monitor.counter("nn.attention.dense")
    blockwise = monitor.counter("nn.attention.blockwise")
    d0, b0 = dense.value, blockwise.value
    x = paddle.to_tensor(np.ones((1, 128, 2, 64), "float32"))
    F.scaled_dot_product_attention(x, x, x, is_causal=True)
    assert dense.value == d0 + 1 and blockwise.value == b0


def _attention_operands(shape, seed=0):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(k, shape, jnp.float32) for k in ks]


@pytest.mark.pallas
@pytest.mark.parametrize("case", ["causal", "full", "packed", "offset",
                                  "three_blocks"])
def test_blockwise_attention_matches_the_dense_form(case):
    """Values and gradients of the blockwise path against
    ``_reference_attention`` in float32 at ``[2, 256, 4, 64]``, the
    kernel interpreted on the CPU.  256 positions are one block;
    ``three_blocks`` (384 positions, blocks of 128) carries the running
    maximum and sum across blocks and skips the blocks above the
    diagonal."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional import attention as att
    q, k, v, do = _attention_operands(
        (1, 384, 2, 64) if case == "three_blocks" else (2, 256, 4, 64))
    causal, seg, mask = case != "full", None, None
    if case == "packed":
        seg = jnp.asarray(np.repeat(np.array([[0, 1], [0, 0]]), 128,
                                    axis=1), jnp.int32)
        mask = (seg[:, :, None] == seg[:, None, :])[:, None]
    if case == "offset":
        # 128 queries against 256 keys: the mask is bottom-right aligned
        q, do = q[:, :128], do[:, :128]

    def dense(q, k, v):
        return att._reference_attention(q, k, v, mask, None, causal)

    def blockwise(q, k, v):
        return att._blockwise_attention(q, k, v, seg, 0.125, causal,
                                        interpret=True)
    want, pull = jax.vjp(dense, q, k, v)
    got, pull_b = jax.vjp(blockwise, q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for g, w in zip(pull_b(do), pull(do)):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_blockwise_attention_at_heads_of_128(dtype):
    """Heads of 128 have a scale that is no power of two (2 ** -3.5),
    which the queries carry into the kernel.  In float32 the blockwise
    path is the dense form to rounding; in bf16 it is as close to the
    float32 reference as the dense bf16 form is (values closer: its
    scores stay float32, where the dense form rounds them to bf16), so
    the scale costs no precision.  ``[2, 256, 4, 128]``, causal,
    interpreted on the CPU."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional import attention as att
    ops = [x.astype(dtype) for x in _attention_operands((2, 256, 4, 128))]
    exact = [x.astype(jnp.float32) for x in ops]

    def dense(q, k, v):
        return att._reference_attention(q, k, v, None, None, True)

    def blockwise(q, k, v):
        return att._blockwise_attention(q, k, v, None, 128 ** -0.5, True,
                                        interpret=True)

    def run(f, q, k, v, do):
        out, pull = jax.vjp(f, q, k, v)
        return [np.asarray(x, np.float32) for x in (out,) + pull(do)]

    want, plain, got = run(dense, *exact), run(dense, *ops), run(
        blockwise, *ops)
    if dtype == "float32":
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=5e-6)
        return
    off = [np.abs(g - w).max() for g, w in zip(got, want)]
    off_dense = [np.abs(p - w).max() for p, w in zip(plain, want)]
    assert off[0] < off_dense[0]
    assert all(o < 1.5 * d for o, d in zip(off, off_dense)), (off, off_dense)
