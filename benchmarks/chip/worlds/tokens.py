"""World kind ``tokens``: rows of token ids over a whole vocabulary, one
topic label a row, and the rows' device shards, made from ``--seed`` alone.

A row is ``row_tokens`` ids (a model reads the first S = row_tokens - 1
and predicts the last S).  Its first id, and every id not taken from the
bigram table, is a draw from a Zipf(``zipf_exponent``) unigram over a
seeded order of the ``vocab_size`` ids; with probability
``bigram_share`` an id is instead the successor of the one before it in
the bigram table of the row's topic (one of ``topics`` seeded tables, each
mapping every id to one successor).  The label of a row is its topic, and
``world.dirichlet_shards`` shards by it; with ``size_sigma`` 0 every
device holds the same number of rows.  The test rows are drawn the same
way, after the training rows.
"""
from __future__ import annotations

import numpy as np

from chipbench.world import Dataset, World, dirichlet_shards


def make_rows(num_rows, row_tokens, vocab, *, topics, zipf_exponent,
              bigram_share, seed):
    """``(ids (num_rows, row_tokens) int32, topics (num_rows,) int32)``."""
    rng = np.random.default_rng([seed, 3])
    order = rng.permutation(vocab)
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_exponent
    cdf = np.cumsum(weights / weights.sum())
    successor = rng.integers(0, vocab, (topics, vocab))
    topic = rng.integers(0, topics, num_rows)

    def unigram(n):
        return order[np.minimum(np.searchsorted(cdf, rng.random(n)),
                                vocab - 1)]

    ids = np.empty((num_rows, row_tokens), np.int64)
    ids[:, 0] = unigram(num_rows)
    for j in range(1, row_tokens):
        follow = rng.random(num_rows) < bigram_share
        ids[:, j] = np.where(follow, successor[topic, ids[:, j - 1]],
                             unigram(num_rows))
    return ids.astype(np.int32), topic.astype(np.int32)


def build(config, seed):
    """The configuration's token rows and shards for one ``--seed``."""
    data = config["data"]
    train, test = data["train_rows"], data["test_rows"]
    ids, topic = make_rows(
        train + test, data["row_tokens"], config["vocab_size"],
        topics=data["topics"], zipf_exponent=data["zipf_exponent"],
        bigram_share=data["bigram_share"], seed=seed)
    ds = Dataset(ids[:train], topic[:train], ids[train:], topic[train:])
    shards, sizes = dirichlet_shards(
        ds.y_train, config["fl"]["num_devices"], seed,
        alpha=data["alpha"], size_sigma=data["size_sigma"],
        min_per_device=data["min_per_device"],
    )
    return World(ds, shards, np.asarray(sizes))
