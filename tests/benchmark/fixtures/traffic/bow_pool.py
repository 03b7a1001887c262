"""Cross-device tag prediction: virtual clients over a pool of rows.

The reference reads each sampled client's sentences from an h5 file at
round time and turns them into bag-of-words rows. The stand-in here builds
one pool of such rows in set-up (sparse, non-negative, each the mean of the
one-hot vectors of a sentence's words; tags a few of ``classes``) and gives
every virtual client a contiguous, wrapping slice of it, so that a round's
host work is what a deployment's is: sample, gather the cohort's rows into
one padded block, cast, ship. It is not the repo's per-round
``rng.standard_normal((n, 10000))``, which would time numpy's generator.

- ``partition_seed`` (the cell's) fixes every client's record count
  (lognormal, capped) and where its slice starts.
- ``seed`` (the run's) makes the pool's words and tags.
"""

from __future__ import annotations

import numpy as np


def client_counts(config: dict, cell: dict) -> np.ndarray:
    rec = cell["records"]
    rng = np.random.default_rng(int(cell["partition_seed"]))
    return np.clip(rng.lognormal(np.log(rec["mean"]), rec["sigma"],
                                 int(cell["clients"])),
                   1, rec["cap"]).astype(np.int64)


def make_pool(config: dict, cell: dict, seed: int):
    data = config["data"]
    dim, classes = int(data["input_dim"]), int(data["classes"])
    rows_n, words = int(cell["pool_rows"]), int(data["words_per_row"])
    rng = np.random.default_rng([int(seed), 0xB0F])
    # Zipf-like word frequencies over the vocabulary
    p = 1.0 / np.arange(1, dim + 1) ** 0.9
    tok = rng.choice(dim, size=(rows_n, words), p=p / p.sum())
    x = np.zeros((rows_n, dim), np.float32)
    np.add.at(x, (np.arange(rows_n)[:, None], tok), np.float32(1.0 / words))
    # 1 to 3 tags per row, tied to its first words so the task is learnable
    n_tags = rng.integers(1, 4, rows_n)
    y = np.zeros((rows_n, classes), np.float32)
    for j in range(3):
        on = n_tags > j
        y[np.nonzero(on)[0], (tok[on, j] * 7 + j) % classes] = 1.0
    return x, y


def make(config: dict, cell: dict, seed: int):
    """-> (CrossDeviceDataset for the program, rows(ids) for the reference)."""
    from fedml_tpu.data.crossdevice import CrossDeviceDataset

    data = config["data"]
    dim, classes = int(data["input_dim"]), int(data["classes"])
    batch = int(config["recipe"]["batch_size"])
    counts = client_counts(config, cell)
    n_pad = -(-int(cell["records"]["cap"]) // batch) * batch
    px, py = make_pool(config, cell, seed)
    pool_n = px.shape[0]
    starts = np.random.default_rng(
        [int(cell["partition_seed"]), 1]).integers(0, pool_n, len(counts))
    lane = np.arange(n_pad)

    def materialize(ids):
        ids = np.asarray(ids)
        n = counts[ids]
        real = lane[None, :] < n[:, None]                     # [m, n_pad]
        src = (starts[ids][:, None] + lane[None, :]) % pool_n
        x = np.zeros((len(ids), n_pad, dim), np.float32)
        y = np.zeros((len(ids), n_pad, classes), np.float32)
        x[real] = px[src[real]]
        y[real] = py[src[real]]
        return x, y, real.astype(np.float32)

    def rows(ids):
        ids = np.asarray(ids)
        return (*materialize(ids), counts[ids])

    n_test = 256
    ds = CrossDeviceDataset(
        materialize=materialize, counts=counts, n_pad=n_pad,
        sample_shape=(dim,), x_dtype=np.float32, y_shape=(classes,),
        y_dtype=np.float32, test_x=px[:n_test], test_y=py[:n_test],
        test_mask=np.ones(n_test, np.float32), class_num=classes,
        task="tag_prediction", name=config["name"])
    return ds, rows
