"""Length-bucket batching: the TPU answer to LoD dynamic shapes
(SURVEY.md §7 hard-part 5 — bounded compile variants + padding)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import io
from paddle_tpu.io import (BucketedBatchSampler, bucketed_collate,
                           pad_to_bucket, bucket_for)


class RaggedDataset(io.Dataset):
    def __init__(self, lengths):
        self.lengths = lengths

    def __getitem__(self, i):
        L = self.lengths[i]
        return (np.full((L,), i, np.int64), np.asarray(i % 2, np.int64))

    def __len__(self):
        return len(self.lengths)


def test_bucket_for():
    assert bucket_for(1, (8, 16)) == 8
    assert bucket_for(8, (8, 16)) == 8
    assert bucket_for(9, (8, 16)) == 16
    with pytest.raises(ValueError, match="largest bucket"):
        bucket_for(17, (8, 16))


def test_pad_to_bucket_shapes_and_lengths():
    arrays = [np.ones((5, 3)), np.ones((7, 3)), np.ones((2, 3))]
    batch, lengths = pad_to_bucket(arrays, buckets=(8, 16), axis=0)
    assert batch.shape == (3, 8, 3)
    np.testing.assert_array_equal(lengths, [5, 7, 2])
    assert batch[2, 2:].sum() == 0  # padded region

def test_sampler_never_mixes_buckets():
    lengths = [5, 30, 6, 31, 7, 60, 8, 61]
    ds = RaggedDataset(lengths)
    sampler = BucketedBatchSampler(ds, batch_size=2, buckets=(8, 32, 64))
    batches = list(sampler)
    assert sorted(i for b in batches for i in b) == list(range(8))
    for b in batches:
        bks = {bucket_for(lengths[i], (8, 32, 64)) for i in b}
        assert len(bks) == 1, (b, bks)


def test_dataloader_with_buckets_bounded_shapes():
    lengths = [3, 9, 4, 10, 5, 17, 6, 18, 30, 29]
    ds = RaggedDataset(lengths)
    loader = io.DataLoader(
        ds, batch_sampler=BucketedBatchSampler(ds, batch_size=2,
                                               buckets=(8, 16, 32)),
        collate_fn=bucketed_collate(buckets=(8, 16, 32)))
    seen_shapes = set()
    rows = 0
    for x, y, lens in loader:
        seen_shapes.add(tuple(np.asarray(x.numpy()).shape[1:]))
        rows += np.asarray(x.numpy()).shape[0]
        # padding is zero beyond each row's length
        xn, ln = np.asarray(x.numpy()), np.asarray(lens.numpy())
        for r in range(xn.shape[0]):
            assert (xn[r, ln[r]:] == 0).all()
    assert rows == len(lengths)
    # at most one shape per bucket — the bounded-compile contract
    assert seen_shapes <= {(8,), (16,), (32,)}, seen_shapes


def test_bucketed_training_compiles_per_bucket_only():
    from paddle_tpu import nn
    lengths = [4, 5, 12, 13, 4, 12, 5, 13]
    ds = RaggedDataset(lengths)
    loader = io.DataLoader(
        ds, batch_sampler=BucketedBatchSampler(ds, batch_size=2,
                                               buckets=(8, 16)),
        collate_fn=bucketed_collate(buckets=(8, 16)))
    paddle.seed(0)
    net = nn.Sequential(nn.Embedding(64, 8))

    class MeanPoolNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.emb = nn.Embedding(64, 8)
            self.fc = nn.Linear(8, 2)

        def forward(self, x):
            return self.fc(paddle.mean(self.emb(x), axis=1))

    from paddle_tpu.parallel.train_step import TrainStep
    net = MeanPoolNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    step = TrainStep(net, opt, loss_fn=nn.CrossEntropyLoss())
    for x, y, lens in loader:
        step.step([x], [y])
    # one compiled variant per bucket, not per distinct raw length
    assert len(step._compiled) == 2, len(step._compiled)


class TestRaggedSkewStress:
    """VERDICT round-2 missing #1: the dense+lengths reduction must hold
    at realistic length skew.  Full measured table (8192-doc lognormal,
    wall-clock legs): the round-3 'Ragged skew' measurement +
    tools/exp/_exp_ragged.py."""

    def _corpus(self, n=2048):
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "exp"))
        from _exp_ragged import make_corpus, analytic, quantile_ladder
        return make_corpus(n), analytic, quantile_ladder

    def test_bucketing_bounds_compiles_and_waste_under_skew(self):
        from paddle_tpu.io.bucketing import (BucketedBatchSampler,
                                             DEFAULT_BUCKETS, bucket_for)
        (docs, lengths), analytic, _ = self._corpus()

        class DS:
            def __getitem__(self, i):
                return docs[i]

            def __len__(self):
                return len(docs)

        sampler = BucketedBatchSampler(
            DS(), batch_size=8, buckets=DEFAULT_BUCKETS,
            length_fn=lambda i: int(lengths[i]), shuffle=True)
        batches = [list(b) for b in sampler]
        import numpy as np
        r = analytic(lengths, [np.asarray(b) for b in batches],
                     lambda bl: bucket_for(int(bl.max()), DEFAULT_BUCKETS),
                     "bucketed")
        # compile variants bounded by 2 x ladder size (full + remainder
        # batch per bucket), NOT by the number of distinct lengths
        assert r["compiles"] <= 2 * len(DEFAULT_BUCKETS), r
        # padding waste stays moderate under heavy lognormal skew
        assert r["padding_waste_pct"] < 25.0, r
        # vs naive global-max padding (~85% waste on this distribution)
        naive = analytic(lengths,
                         [np.arange(i, min(i + 8, len(docs)))
                          for i in range(0, len(docs), 8)],
                         lambda bl: int(lengths.max()), "naive")
        assert naive["padding_waste_pct"] > 3 * r["padding_waste_pct"]
        # every sample appears exactly once
        seen = sorted(i for b in batches for i in b)
        assert seen == list(range(len(docs)))


class TestTokenBudgetBatching:
    def _ds(self, lens):
        class DS:
            def __getitem__(self, i):
                return (np.zeros(lens[i], np.int64),
                        np.int64(i % 3))

            def __len__(self):
                return len(lens)
        return DS()

    def test_packs_to_budget(self):
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        lens = [5, 9, 3, 8, 2, 2, 7]
        s = TokenBudgetBatchSampler(self._ds(lens), token_budget=12)
        batches = list(s)
        seen = sorted(i for b in batches for i in b)
        assert seen == list(range(7))
        for b in batches:
            assert sum(lens[i] for i in b) <= 12
        assert len(s) == len(batches)

    def test_oversized_sample_raises(self):
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        s = TokenBudgetBatchSampler(self._ds([4, 20]), token_budget=12)
        with pytest.raises(ValueError, match="truncate"):
            list(s)

    def test_max_batch_size_caps_rows(self):
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        s = TokenBudgetBatchSampler(self._ds([1] * 10), token_budget=100,
                                    max_batch_size=4)
        for b in s:
            assert len(b) <= 4

    def test_ragged_collate_end_to_end(self):
        from paddle_tpu import io
        from paddle_tpu.io.bucketing import (TokenBudgetBatchSampler,
                                             ragged_collate)
        from paddle_tpu.core.ragged import RaggedTensor, sequence_pool
        lens = [5, 9, 3, 8, 2, 2, 7]
        ds = self._ds(lens)
        sampler = TokenBudgetBatchSampler(ds, token_budget=12)
        loader = io.DataLoader(ds, batch_sampler=sampler,
                               collate_fn=ragged_collate(
                                   capacity=12, extra_fields=(1,)),
                               num_workers=0)
        total = 0
        for values, splits, labels in loader:
            rt = RaggedTensor(values, splits)
            pooled = sequence_pool(rt, "sum")
            assert pooled.shape[0] == len(labels)
            assert values.shape[0] == 12  # fixed capacity: ONE compile
            total += int(np.asarray(splits.numpy())[-1])
        assert total == sum(lens)

    def test_zero_waste_vs_bucketed_padding(self):
        """At the BASELINE round-3 skew, token budgeting wastes only the
        final-batch remainder — far below padded bucketing's 17%."""
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "exp"))
        from _exp_ragged import make_corpus
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        (docs, lengths) = make_corpus(1024)

        class DS:
            def __getitem__(self, i):
                return docs[i]

            def __len__(self):
                return len(docs)

        budget = 4096
        s = TokenBudgetBatchSampler(
            DS(), token_budget=budget,
            length_fn=lambda i: int(lengths[i]), shuffle=True)
        batches = list(s)
        used = [sum(int(lengths[i]) for i in b) for b in batches]
        waste = 1 - sum(used) / (len(batches) * budget)
        assert waste < 0.02, waste  # vs 0.171 for the x1.5 ladder

    def test_len_contract_under_shuffle(self):
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        lens = list(np.random.RandomState(0).randint(1, 10, 40))
        s = TokenBudgetBatchSampler(self._ds(lens), token_budget=16,
                                    shuffle=True)
        # len() BEFORE the epoch sees the same permutation the epoch
        # will iterate
        n = len(s)
        assert n == sum(1 for _ in s)
        # MID-epoch (and post-epoch) len() reports the running/last
        # epoch's count, never a pre-drawn future permutation
        it = iter(s)
        next(it)
        running = len(s)
        assert running == 1 + sum(1 for _ in it)

    def test_drop_last_keeps_fullish_bins(self):
        from paddle_tpu.io.bucketing import TokenBudgetBatchSampler
        # one nearly-full bin (9/10) + one sparse bin (2/10)
        lens = [9, 2]
        s = TokenBudgetBatchSampler(self._ds(lens), token_budget=10,
                                    drop_last=True)
        batches = list(s)
        kept = [i for b in batches for i in b]
        assert 0 in kept and 1 not in kept

    def test_collate_is_pure_numpy(self):
        """Workers never touch jax: the collate output must be numpy."""
        from paddle_tpu.io.bucketing import ragged_collate
        c = ragged_collate(capacity=12, extra_fields=(1,))
        out = c([(np.zeros(3, np.int64), np.int64(1)),
                 (np.zeros(5, np.int64), np.int64(0))])
        for o in out:
            assert type(o).__module__ == "numpy", type(o)

    def test_to_padded_overflow_raises(self):
        from paddle_tpu.core.ragged import RaggedTensor
        rt = RaggedTensor.from_rows(
            [np.zeros((9, 1), np.float32)])
        with pytest.raises(ValueError, match="max_len"):
            rt.to_padded(max_len=7)

    def test_ragged_collate_fixed_rows(self):
        """max_rows fixes every output shape — one compile, not one per
        packed row count."""
        from paddle_tpu.io.bucketing import ragged_collate
        c = ragged_collate(capacity=16, extra_fields=(1,), max_rows=4)
        shapes = set()
        for rows in ([3, 5], [2, 2, 2, 2], [9]):
            out = c([(np.zeros(l, np.int64), np.int64(0))
                     for l in rows])
            shapes.add(tuple(o.shape for o in out))
            # padded splits repeat the total (zero-length tail rows)
            assert out[1][-1] == sum(rows)
        assert len(shapes) == 1
        with pytest.raises(ValueError, match="max_rows"):
            c([(np.zeros(1, np.int64), np.int64(0))] * 5)
