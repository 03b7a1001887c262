"""``ops/ssd.py``: the chunked state-space recurrence against the recurrence
itself (values and every gradient), chunks that do and do not divide the
length, a decay slow enough that a state crosses four chunks, and a cut at a
chunk's edge that the comparison catches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import ssd

NAMES = ("x", "dt", "A_log", "B", "C", "D")


def _inputs(seed=0, b=2, t=40, h=3, p=8, n=16, slow=False):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (b, t, h, p))
    # slow: exp(dt A) about 0.995 a position, so that what position 0
    # writes is still two thirds there 64 positions (four chunks of 16)
    # later, and no skip, so that the output is the state's read-out alone;
    # else the mixer's own range
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, h))
                         - (7.0 if slow else 3.0))
    a_log = jnp.log(jax.random.uniform(k[2], (h,), minval=1.0,
                                       maxval=4.0 if slow else 16.0))
    bb, cc = (jax.random.normal(k[i], (b, t, n)) for i in (3, 4))
    d = (0.0 if slow else 1.0) + 0.1 * jax.random.normal(k[5], (h,))
    return x, dt, a_log, bb, cc, d


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@pytest.mark.parametrize("chunk", [8, 16, 40, 64, 12, 7])
def test_chunked_values_equal_the_recurrence(chunk):
    """8 divides 40, 12 and 7 do not (the last chunk is filled with
    positions that write nothing), 64 is clamped to the length."""
    args = _inputs()
    want = ssd.ssd_recurrent(*args)
    got = ssd.ssd_chunked(*args, chunk=chunk, dtype=jnp.float32)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("chunk", [8, 12])
def test_chunked_gradients_equal_the_recurrences(name, chunk):
    args = _inputs(1)
    i = NAMES.index(name)
    ct = jax.random.normal(jax.random.key(9), args[0].shape)

    def grad(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * ct), argnums=i)(*args)

    want = grad(ssd.ssd_recurrent)
    got = grad(lambda *a: ssd.ssd_chunked(*a, chunk=chunk, dtype=jnp.float32))
    assert _rel(got, want) < 1e-5


def test_bf16_operands_stay_near_the_recurrence():
    args = _inputs(2)
    want = ssd.ssd_recurrent(*args)
    got = ssd.ssd_chunked(*args, chunk=8, dtype=jnp.bfloat16)
    assert 1e-4 < _rel(got, want) < 2e-2


def test_no_exponent_overflows_at_the_fastest_decay():
    """``dt A`` of -30 a position: ``exp(a_i) exp(-a_j)`` would be inf *
    0; the difference is formed first."""
    x, dt, a_log, b, c, d = _inputs(3)
    dt = jnp.full_like(dt, 2.0)
    a_log = jnp.full_like(a_log, jnp.log(15.0))
    got = ssd.ssd_chunked(x, dt, a_log, b, c, d, chunk=40, dtype=jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(
        *a, chunk=40, dtype=jnp.float32)), argnums=(0, 1, 2))(x, dt, a_log, b, c, d)
    assert np.isfinite(got).all()
    assert all(np.isfinite(g).all() for g in grads)
    np.testing.assert_allclose(got, ssd.ssd_recurrent(x, dt, a_log, b, c, d),
                               atol=1e-4)


def _cut_at_edges(x, dt, a_log, b, c, d, chunk):
    """What a program computes that drops the state at every chunk's edge:
    each chunk as a sequence of its own."""
    t = x.shape[1]
    return jnp.concatenate([
        ssd.ssd_recurrent(x[:, s:s + chunk], dt[:, s:s + chunk], a_log,
                          b[:, s:s + chunk], c[:, s:s + chunk], d)
        for s in range(0, t, chunk)], axis=1)


def test_a_state_crosses_four_chunks_and_a_cut_at_an_edge_is_seen():
    """Under the slow decay two thirds or more of what position 0 wrote are left
    64 positions on: the output of the LAST of four chunks of 16 depends on
    the first chunk's input, the chunked form carries it, and the same
    comparison FAILS for a form that drops the state at a chunk's edge."""
    args = _inputs(4, t=64, slow=True)
    x, dt, a_log = args[:3]
    left = jnp.exp(-jnp.sum(dt, axis=1) * jnp.exp(a_log))
    assert float(jnp.min(left)) > 0.6
    want = ssd.ssd_recurrent(*args)
    got = ssd.ssd_chunked(*args, chunk=16, dtype=jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    # the last chunk hears the first: its output moves with x[:, :16]
    moved = ssd.ssd_chunked(x.at[:, :16].set(0.0), *args[1:], chunk=16,
                            dtype=jnp.float32)
    assert _rel(moved[:, 48:], want[:, 48:]) > 0.05
    # a form that loses the carry is caught by the same yardstick
    cut = _cut_at_edges(*args, chunk=16)
    assert _rel(cut[:, :16], want[:, :16]) < 1e-6
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(
            cut, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    assert _rel(cut[:, 48:], want[:, 48:]) > 0.1
