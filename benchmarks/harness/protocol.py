"""The federated round as a specification, independent of ``fedml_tpu``.

The plain references follow the program's first rounds on the same rows in
the same order, so they need the rules that fix which clients a round
trains and in which order each client's records are visited. These are the
algorithm's published semantics (reference ``fedavg_api.py:83-91`` for the
sampling; one permutation per local epoch, real records first), written out
here so that nothing is read from the program's internals. The originals
are ``fedml_tpu/core/rng.py`` (``sample_clients``, ``round_key``) and
``fedml_tpu/parallel/local.py`` (``epoch_fn``): a PR that changes either
changes what a round computes and has to bring a benchmark PR with it.

Imports numpy and jax only.
"""

from __future__ import annotations

import jax
import numpy as np


def run_key(seed: int) -> jax.Array:
    """The root key of a run: weights, shuffles and nothing else."""
    return jax.random.key(int(seed))


def sample_cohort(round_idx: int, n_total: int, n_round: int,
                  sampling_seed: int) -> np.ndarray:
    """Sorted client ids of round ``round_idx``, without replacement; the
    whole federation when every client takes part."""
    if n_total == n_round:
        return np.arange(n_total, dtype=np.int64)
    rng = np.random.default_rng(sampling_seed * 1_000_003 + round_idx)
    return np.sort(rng.choice(n_total, n_round, replace=False)).astype(np.int64)


def client_keys(root: jax.Array, round_idx: int, n: int) -> jax.Array:
    """One key per cohort position (sampled order) for round ``round_idx``."""
    return jax.random.split(jax.random.fold_in(root, round_idx), n)


def epoch_orders(client_key: jax.Array, epochs: int, mask: np.ndarray) -> np.ndarray:
    """``[epochs, n_pad]`` record order of one client: per epoch a seeded
    permutation, stably sorted so that real records (mask 1) come first."""
    mask = np.asarray(mask)
    n_pad = int(mask.shape[0])
    orders = []
    for ekey in jax.random.split(client_key, epochs):
        perm = np.asarray(jax.random.permutation(ekey, n_pad))
        orders.append(perm[np.argsort(-mask[perm], kind="stable")])
    return np.stack(orders)
