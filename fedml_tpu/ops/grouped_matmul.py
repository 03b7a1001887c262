"""Grouped matmul over token rows sorted by expert, and the row moves
around it.

A sparse-expert layer sorts its (token, choice) pairs by expert and runs ONE
matmul per projection over the sorted rows: rows ``[start_g, start_g +
size_g)`` of ``x`` meet ``w[g]``. No dropped row: the layer chooses, each
step, a static row capacity from the router's own count that holds every
row the router filled (``models/moe.py: row_rungs``), and the last capacity
is every pair; an expert may hold no row at all, or all of them. On the TPU
``jax.lax.ragged_dot`` lowers to the compiler's own grouped kernels
(forward, and both gradients through its transpose rules), with XLA's
operation count equal to ``2 * rows * K * N``; ``tests/test_latent_moe.py``
holds it against a per-expert loop.

The moves are gathers in BOTH directions: XLA's transpose of a row gather
is a scatter-add, which a TPU serialises row by row. A permutation's
transpose is the inverse permutation's gather, and a fan-out's transpose is
a gather and a sum over the fan, so each gets its own VJP. Both moves take
the FIRST ``C`` sorted slots only (the layer's row capacity): a slot past
them reads as a zero row, which is what the pair of an absent expert adds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``x [M, K]`` rows sorted by group, ``w [G, K, N]``, ``group_sizes
    [G]`` int32 with ``sum <= M`` -> ``[M, N]``; rows past the last group
    belong to no group (``lax.ragged_dot``'s reference gives them zeros;
    the sparse layer masks them itself). Unbatched only: under a ``vmap``
    (more than one packed lane) the TPU compiler refuses the batched
    ``ragged_dot`` ("number of batch dimensions should be 0")."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


def _rows_or_zero(x: jax.Array, at: jax.Array) -> jax.Array:
    """``x[at]``, and a zero row where ``at`` is past ``x``'s last row."""
    return jnp.take(x, at, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def permute_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``x [C, D]`` (the first ``C`` sorted slots) -> ``[P, D]``, one row a
    pair: ``out[p] = x[perm[p]]``, a zero row where ``perm[p] >= C``. ``inv
    [C]`` is the first ``C`` entries of ``perm``'s inverse permutation; the
    cotangent comes back by its gather, ``C`` rows."""
    return _rows_or_zero(x, perm)


def _permute_fwd(x, perm, inv):
    return _rows_or_zero(x, perm), inv


def _permute_bwd(inv, ct):
    return jnp.take(ct, inv, axis=0), None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def fan_out_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``x [N, D]`` -> ``[C, D]`` with ``out[i] = x[perm[i] % N]``: row
    ``n`` is copied to its ``k`` (choice, token) slots ``c*N + n`` (choice-
    major, so that ``[k, N, D]`` views pad no axis), the slots are permuted
    by the sort whose first ``C`` entries are ``perm`` (inverse ``inv [k*N]``),
    and the first ``C`` are kept. The cotangent is un-permuted by a gather
    (zero for a slot that was not kept) and summed over each token's ``k``
    slots."""
    return jnp.take(x, perm % x.shape[0], axis=0)


def _fan_fwd(x, perm, inv):
    # an empty array carries the token count to the backward pass
    return fan_out_rows(x, perm, inv), (inv, jnp.zeros((x.shape[0], 0)))


def _fan_bwd(res, ct):
    inv, tokens = res
    back = _rows_or_zero(ct, inv).reshape(-1, tokens.shape[0], ct.shape[-1])
    return (jnp.sum(back.astype(jnp.float32), axis=0).astype(ct.dtype),
            None, None)


fan_out_rows.defvjp(_fan_fwd, _fan_bwd)


@jax.custom_vjp
def embed_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]`` whose gradient is a one-hot matmul (float32
    accumulation) instead of a scatter-add of one row per token."""
    return jnp.take(table, ids, axis=0)


def _embed_fwd(table, ids):
    # the table rides along only for its shape and dtype (no copy is made)
    return jnp.take(table, ids, axis=0), (ids, table)


def _embed_bwd(res, ct):
    ids, table = res
    flat = ct.reshape(-1, ct.shape[-1])
    hot = jax.nn.one_hot(ids.reshape(-1), table.shape[0], dtype=flat.dtype,
                         axis=0)
    return (jnp.dot(hot, flat, preferred_element_type=jnp.float32)
            .astype(table.dtype), None)


embed_rows.defvjp(_embed_fwd, _embed_bwd)
