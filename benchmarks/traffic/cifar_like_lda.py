"""Image-classification federation: seeded class blobs, LDA-partitioned.

A copy of the repo's ``make_synthetic_classification`` + Dirichlet
partition (``fedml_tpu/data/synthetic.py``, ``fedml_tpu/core/partition.py``),
kept here so that no later PR can change the yardstick's data, with the
seed split in two:

- ``partition_seed`` (the cell's) fixes every client's record count and its
  count of each class. The packed round plan's shape follows the sampled
  cohort's counts, so counts that moved with the run's seed would compile a
  new program, minutes cold, for every seed.
- ``seed`` (the run's) makes the class means, every pixel value, and the
  order of the labels inside each client.

The program receives the ``FedDataset``; the reference reads the same host
arrays through ``rows``.
"""

from __future__ import annotations

import numpy as np


def lda_partition(labels: np.ndarray, n_clients: int, classes: int,
                  alpha: float, rng: np.random.Generator,
                  min_floor: int = 10) -> list:
    """Dirichlet label partition with the capacity balancing and the
    minimum-size retry of the reference (noniid_partition.py:6-91)."""
    n = len(labels)
    floor = max(1, min(min_floor, n // (n_clients * 10)))
    for _ in range(1000):
        batches: list = [[] for _ in range(n_clients)]
        for k in range(classes):
            idx_k = np.where(labels == k)[0]
            if not len(idx_k):
                continue
            rng.shuffle(idx_k)
            p = rng.dirichlet(np.repeat(alpha, n_clients))
            p = np.array([pi * (len(b) < n / n_clients)
                          for pi, b in zip(p, batches)])
            p = p / p.sum() if p.sum() > 0 else np.full(n_clients, 1 / n_clients)
            cuts = (np.cumsum(p) * len(idx_k)).astype(int)[:-1]
            batches = [b + part.tolist()
                       for b, part in zip(batches, np.split(idx_k, cuts))]
        if min(len(b) for b in batches) >= floor:
            return [np.asarray(b, np.int64) for b in batches]
    raise RuntimeError(f"LDA partition of {n} records over {n_clients} "
                       f"clients (alpha {alpha}) never reached {floor}")


def client_labels(config: dict, cell: dict) -> list:
    """Each client's labels as the partition fixes them (a multiset: the
    run's seed only reorders them)."""
    data = config["data"]
    rng = np.random.default_rng(int(cell["partition_seed"]))
    labels = rng.integers(0, data["classes"], data["train_records"])
    parts = lda_partition(labels, int(cell["clients"]), data["classes"],
                          float(data["partition_alpha"]), rng)
    return [labels[p].astype(np.int32) for p in parts]


def make(config: dict, cell: dict, seed: int):
    """-> (FedDataset for the program, rows(ids) for the reference)."""
    from fedml_tpu.data import FedDataset

    data = config["data"]
    shape = tuple(data["input_shape"])
    dim = int(np.prod(shape))
    batch = int(config["recipe"]["batch_size"])
    per_client = client_labels(config, cell)
    n_pad = -(-max(len(y) for y in per_client) // batch) * batch
    rng = np.random.default_rng([int(seed), 0xC1FA])
    means = rng.standard_normal((data["classes"], dim), np.float32) \
        * np.float32(data["separation"])
    k = len(per_client)
    tx = np.zeros((k, n_pad, dim), np.float32)
    ty = np.zeros((k, n_pad), np.int32)
    tm = np.zeros((k, n_pad), np.float32)
    for c, y in enumerate(per_client):
        y = rng.permutation(y)
        n = len(y)
        tx[c, :n] = rng.standard_normal((n, dim), np.float32)
        tx[c, :n] += means[y]
        ty[c, :n] = y
        tm[c, :n] = 1.0
    tx = tx.reshape((k, n_pad) + shape)
    counts = np.asarray([len(y) for y in per_client], np.int64)
    # the timed loop never evaluates; the dataset type wants a test pool
    n_test = 256
    ey = rng.integers(0, data["classes"], n_test).astype(np.int32)
    ex = (means[ey] + rng.standard_normal((n_test, dim), np.float32)
          ).reshape((n_test,) + shape)
    ds = FedDataset(train_x=tx, train_y=ty, train_mask=tm, train_counts=counts,
                    test_x=ex, test_y=ey, test_mask=np.ones(n_test, np.float32),
                    class_num=int(data["classes"]), name=config["name"])

    def rows(ids):
        ids = np.asarray(ids)
        return tx[ids], ty[ids], tm[ids], counts[ids]

    return ds, rows
