"""The delta rule with a per-channel decay (``fedml_tpu/ops/kda.py``): the
chunked scan against the token-by-token recurrence, values and gradients,
and its exponents at the gate's bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.kda import (KDA_CHUNK, KDA_KEEP, KDA_SUB, kda_chunked,
                               kda_recurrent)


def inputs(seed, b=2, h=3, t=64, dk=16, dv=8, gate=None):
    """As the mixer makes them: unit keys, scaled unit queries, log-decays
    in ``[-5, 0]`` (or all ``gate``), steps in ``(0, 1)``."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t, dk)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk,sub", [(16, 4), (32, 16), (64, 16), (32, 8)])
def test_chunked_scan_is_the_recurrence(chunk, sub):
    x = inputs(1)
    want = kda_recurrent(*x)
    got = kda_chunked(*x, chunk=chunk, sub=sub, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("chunk,sub", [(16, 8), (64, 16)])
def test_gradients_of_every_operand_match_the_recurrence(chunk, sub):
    x = inputs(2)
    ct = jax.random.normal(jax.random.key(9), x[2].shape)
    want = jax.grad(lambda *a: jnp.sum(kda_recurrent(*a) * ct),
                    argnums=(0, 1, 2, 3, 4))(*x)
    got = jax.grad(lambda *a: jnp.sum(kda_chunked(
        *a, chunk=chunk, sub=sub, dtype=jnp.float32) * ct),
        argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip("qkvgb", got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))
                                                           + 1), err_msg=name)


@pytest.mark.parametrize("gate", [-5.0, 0.0])
def test_finite_and_exact_with_every_gate_at_a_bound_over_whole_chunks(gate):
    """-5 a position over two chunks of 64 is ``e^-640`` end to end: no
    factor the scan forms may overflow, and none may turn a zero into a
    NaN. 0 is the other end: no decay at all, the plain delta rule."""
    x = inputs(3, t=128, gate=gate)
    got = kda_chunked(*x, chunk=64, sub=16, dtype=jnp.float32)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, kda_recurrent(*x), atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(kda_chunked(*a, chunk=64, sub=16)),
                     argnums=(0, 1, 2, 3, 4))(*x)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@pytest.mark.parametrize("keep", [1, 2, 3, 4])
def test_states_kept_for_the_backward_do_not_change_a_number(keep):
    """A state every ``keep`` chunks, the steps between replayed: the same
    values and gradients as a state a chunk (3 does not divide 4 chunks:
    every chunk's is kept)."""
    x = inputs(7)

    def run(keep):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(kda_chunked(
            *a, chunk=16, sub=4, keep=keep, dtype=jnp.float32))),
            argnums=(0, 1, 2, 3, 4))(*x)

    (want, gw), (got, gg) = run(1), run(keep)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(gg, gw):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_defaults_hold_the_bound_in_float32():
    """A sub-block's largest exponent is ``5 * (sub - 1)``: it has to stay
    under float32's (and bfloat16's) ``e^88``."""
    assert 5.0 * (KDA_SUB - 1) < 88.0 and KDA_CHUNK % KDA_SUB == 0
    assert (4096 // KDA_CHUNK) % KDA_KEEP == 0


def test_module_precision_stays_near_the_recurrence():
    """bfloat16 operands, float32 state and solve: rounding, not drift."""
    x = inputs(4, t=128, dk=32, dv=32)
    want = kda_recurrent(*x)
    got = kda_chunked(*(a.astype(jnp.bfloat16) for a in x[:3]), *x[3:])
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(
        jnp.max(jnp.abs(want)))


def test_a_sequence_shorter_than_a_chunk_and_a_ragged_one():
    x = inputs(5, t=8)
    np.testing.assert_allclose(kda_chunked(*x, dtype=jnp.float32),
                               kda_recurrent(*x), atol=2e-5)
    with pytest.raises(ValueError, match="no multiple"):
        kda_chunked(*inputs(5, t=80), chunk=64)


def test_the_state_runs_along_the_sequence():
    """Position ``t``'s output depends on every earlier position and on no
    later one."""
    x = inputs(6, b=1, h=1, t=64, gate=-0.05)
    base = kda_chunked(*x, chunk=16, sub=4, dtype=jnp.float32)
    v2 = x[2].at[0, 0, 20].add(1.0)
    moved = kda_chunked(x[0], x[1], v2, x[3], x[4], chunk=16, sub=4,
                        dtype=jnp.float32)
    diff = jnp.max(jnp.abs(moved - base), axis=-1)[0, 0]
    assert float(jnp.max(diff[:20])) == 0.0 and float(diff[20]) > 0.0
    assert float(diff[40]) > 0.0     # carried across two chunk boundaries
