"""Token-sequence federation: every client a Zipf law over its own
permutation of the vocabulary.

Silos that fine-tune a language model on private documents differ in WHICH
words are frequent, not in how skewed word frequencies are: each client
draws its ids from the configuration's vocabulary slice by a Zipf law
(``p(rank r) ~ r^-exponent``) over a permutation of its own. A sequence is
``seq_len + 1`` draws: the inputs are the first ``seq_len``, the targets the
ids that follow them. As for the image federation the seed is split in two:

- ``partition_seed`` (the cell's) fixes how many sequences each client
  holds: the packed round plan's shape follows the sampled cohort's counts.
- ``seed`` (the run's) makes every client's permutation and every id.

The program receives the ``FedDataset`` (ids as int32: they must reach the
model unrounded, a bf16 stack would not hold an id above 256); the reference
reads the same host arrays through ``rows``.
"""

from __future__ import annotations

import numpy as np


def client_counts(config: dict, cell: dict) -> np.ndarray:
    lo, hi = config["data"]["client_sequences"]
    rng = np.random.default_rng(int(cell["partition_seed"]))
    return rng.integers(int(lo), int(hi) + 1, int(cell["clients"])).astype(np.int64)


def make(config: dict, cell: dict, seed: int):
    """-> (FedDataset for the program, rows(ids) for the reference)."""
    from fedml_tpu.data import FedDataset

    data = config["data"]
    t, vocab = int(data["seq_len"]), int(data["vocab"])
    batch = int(config["recipe"]["batch_size"])
    counts = client_counts(config, cell)
    n_pad = -(-int(counts.max()) // batch) * batch
    k = len(counts)
    rng = np.random.default_rng([int(seed), 0x70C5])
    law = np.arange(1, vocab + 1, dtype=np.float64) ** -float(data["zipf_exponent"])
    cdf = np.cumsum(law / law.sum())
    tx = np.zeros((k, n_pad, t), np.int32)
    ty = np.zeros((k, n_pad, t), np.int32)
    tm = np.zeros((k, n_pad), np.float32)

    def draw(n, perm):
        ranks = np.searchsorted(cdf, rng.random((n, t + 1)))
        return perm[np.minimum(ranks, vocab - 1)].astype(np.int32)

    for c, n in enumerate(counts):
        ids = draw(int(n), rng.permutation(vocab))
        tx[c, :n], ty[c, :n], tm[c, :n] = ids[:, :-1], ids[:, 1:], 1.0
    # the timed loop never evaluates; the dataset type wants a test pool
    ids = draw(batch, rng.permutation(vocab))
    ds = FedDataset(train_x=tx, train_y=ty, train_mask=tm, train_counts=counts,
                    test_x=ids[:, :-1], test_y=ids[:, 1:],
                    test_mask=np.ones(batch, np.float32), class_num=vocab,
                    task="nwp", name=config["name"])

    def rows(ids):
        ids = np.asarray(ids)
        return tx[ids], ty[ids], tm[ids], counts[ids]

    return ds, rows
