"""Local SGD of one client, written out plainly: the part every reference
shares. ``E`` epochs of ``S`` minibatch steps over rows that arrive already
ordered (``benchmarks/harness/protocol.epoch_orders``); steps beyond the
client's real batches change nothing; the loss reported is the mean over
the last epoch's real steps. Imports jax only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rounded(tree, dtype):
    """Values a store of ``dtype`` would hold, kept in float32 arrays. By
    ``reduce_precision``, which XLA keeps: a cast there and back inside a
    jitted program is removed on the TPU as excess precision, and a control
    built on it read exactly as the stated precision (my chip run, PR 23)."""
    if dtype is None:
        return tree
    info = jnp.finfo(dtype)
    return jax.tree.map(lambda a: jax.lax.reduce_precision(
        a, exponent_bits=info.nexp, mantissa_bits=info.nmant), tree)


def make_local_train(loss_fn, *, lr: float, momentum: float, store_dtype=None):
    """``loss_fn(params, state, bx, by, bm) -> (loss, new_state)``.

    Returns ``local_train(params, state, xs, ys, ms, steps_real)`` with
    ``xs`` of shape ``[epochs, steps, batch, ...]``; gives back the trained
    ``(params, state)`` and the last epoch's mean loss. ``store_dtype``
    rounds parameters and momentum after every step: the control in which
    they are kept in a lower precision than the configuration states."""

    def step(carry, batch):
        params, state, trace = carry
        bx, by, bm, live = batch
        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, bx, by, bm)
        if momentum:
            new_trace = jax.tree.map(lambda g, t: g + momentum * t, grads, trace)
        else:
            new_trace = grads
        new_params = jax.tree.map(lambda p, t: p - lr * t, params, new_trace)
        new_params = rounded(new_params, store_dtype)
        new_trace = rounded(new_trace, store_dtype)
        keep = lambda n, o: jax.tree.map(
            lambda a, b: jnp.where(live, a, b), n, o)
        return ((keep(new_params, params), keep(new_state, state),
                 keep(new_trace, trace)), jnp.where(live, loss, 0.0))

    @jax.jit
    def local_train(params, state, xs, ys, ms, steps_real):
        steps = xs.shape[1]
        live = jnp.arange(steps) < steps_real
        trace = jax.tree.map(jnp.zeros_like, params)

        def epoch(carry, ep):
            ex, ey, em = ep
            carry, losses = jax.lax.scan(step, carry, (ex, ey, em, live))
            return carry, jnp.sum(losses) / jnp.maximum(steps_real, 1)

        (params, state, _), ep_losses = jax.lax.scan(
            epoch, (params, state, trace), (xs, ys, ms))
        return params, state, ep_losses[-1]

    return local_train
