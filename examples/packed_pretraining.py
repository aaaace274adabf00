"""Zero-waste GPT pretraining on skewed documents.

Pipeline: skewed corpus -> TokenBudgetBatchSampler (pooled first-fit
packing, ~0.3% waste) -> ragged_collate (fixed shapes: one compile) ->
GPTModel(doc_lens=...) with per-document position reset and
block-diagonal attention (flash SegmentIds on TPU; derived mask on
CPU).  Run:

    JAX_PLATFORMS=cpu python examples/packed_pretraining.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import io, optimizer
from paddle_tpu.io.bucketing import (TokenBudgetBatchSampler,
                                     ragged_collate)
from paddle_tpu.models import GPTModel
from paddle_tpu.parallel.train_step import TrainStep

VOCAB, BUDGET, MAX_DOCS = 128, 96, 12
MAX_POSITION = 96  # per-doc positions reach doc length; table must cover


def make_corpus(n_docs=128, seed=0):
    rs = np.random.RandomState(seed)
    # docs may span the whole budget; the model below is built with
    # max_position >= BUDGET so per-document position resets always fit
    lens = np.clip(rs.geometric(0.08, n_docs), 4, BUDGET)
    return [rs.randint(0, VOCAB, l).astype(np.int32) for l in lens]


class Docs(io.Dataset):
    def __init__(self, docs):
        self.docs = docs

    def __getitem__(self, i):
        return (self.docs[i],)

    def __len__(self):
        return len(self.docs)


class PackedGPT(paddle.nn.Layer):
    """Adapter: (packed ids, doc_lens, labels) -> LM loss."""

    def __init__(self):
        super().__init__()
        self.gpt = GPTModel.from_config("tiny", dropout=0.1,
                                        max_position=MAX_POSITION)

    def forward(self, ids, doc_lens, labels):
        return self.gpt(ids, labels=labels, doc_lens=doc_lens)


def to_batch(values, splits):
    """collate output -> (ids [1, cap], doc_lens [1, D], labels)."""
    splits = np.asarray(splits)
    lens = (splits[1:] - splits[:-1]).astype(np.int32)
    ids = np.asarray(values)[None, :].astype(np.int32)
    labels = np.concatenate([ids[0, 1:], [0]])[None, :].astype(np.int64)
    return ids, lens[None, :], labels


def main():
    paddle.seed(0)
    docs = make_corpus()
    ds = Docs(docs)
    sampler = TokenBudgetBatchSampler(
        ds, token_budget=BUDGET, shuffle=True,
        max_batch_size=MAX_DOCS,
        length_fn=lambda i: len(docs[i]))
    loader = io.DataLoader(
        ds, batch_sampler=sampler,
        collate_fn=ragged_collate(capacity=BUDGET, max_rows=MAX_DOCS),
        num_workers=0)

    model = PackedGPT()
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = TrainStep(model, opt, loss_fn=None, donate=False)

    total_tokens = sum(len(d) for d in docs)
    first = last = None
    for epoch in range(3):
        for (values, splits) in loader:
            ids, doc_lens, labels = to_batch(values, splits)
            loss = step.step([ids, doc_lens, labels])
            first = first if first is not None else float(loss.numpy())
        last = float(loss.numpy())
        print(f"epoch {epoch}: loss {last:.4f} "
              f"({len(sampler)} packed batches, {total_tokens} tokens)")
    assert last < first, (first, last)
    print(f"packed pretraining OK: loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
