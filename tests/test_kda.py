"""The delta rule with a per-channel decay (``fedml_tpu/ops/kda.py``): the
chunked scan against the token-by-token recurrence, values and gradients,
and its exponents at the gate's bound; on both paths, the ``jax.numpy`` scan
(``xla``) and the kernel pair (``pallas``, interpreted on the CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import kda
from fedml_tpu.ops.kda import (KDA_CHUNK, KDA_KEEP, KDA_SUB, kda_chunked,
                               kda_recurrent)

#: the smallest heads the kernel pair tiles: widths of one 128-lane tile
TILED = dict(b=1, h=2, dk=128, dv=128)


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


@pytest.mark.parametrize("chunk,sub,impl", [
    (16, 4, "xla"), (32, 16, "xla"), (64, 16, "xla"), (32, 8, "xla"),
    (32, 16, "pallas"), (64, 16, "pallas"), (16, 8, "pallas"),
    (32, 8, "pallas")])
def test_chunked_scan_is_the_recurrence(chunk, sub, impl):
    x = inputs(1, t=128, **TILED) if impl == "pallas" else inputs(1)
    want = kda_recurrent(*x)
    got = kda_chunked(*x, chunk=chunk, sub=sub, dtype=jnp.float32, impl=impl)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if impl == "pallas":      # and the other path's numbers, not only near
        np.testing.assert_allclose(got, kda_chunked(
            *x, chunk=chunk, sub=sub, dtype=jnp.float32, impl="xla"),
            atol=1e-6)


@pytest.mark.parametrize("chunk,sub,impl", [
    (16, 8, "xla"), (64, 16, "xla"), (32, 16, "pallas"), (64, 16, "pallas")])
def test_gradients_of_every_operand_match_the_recurrence(chunk, sub, impl):
    x = inputs(2, t=128, **TILED) if impl == "pallas" else inputs(2)
    ct = jax.random.normal(jax.random.key(9), x[2].shape)
    want = jax.grad(lambda *a: jnp.sum(kda_recurrent(*a) * ct),
                    argnums=(0, 1, 2, 3, 4))(*x)
    got = jax.grad(lambda *a: jnp.sum(kda_chunked(
        *a, chunk=chunk, sub=sub, dtype=jnp.float32, impl=impl) * ct),
        argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip("qkvgb", got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))
                                                           + 1), err_msg=name)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("gate", [-5.0, 0.0])
def test_finite_and_exact_with_every_gate_at_a_bound_over_whole_chunks(gate,
                                                                       impl):
    """-5 a position over two chunks of 64 is ``e^-640`` end to end: no
    factor the scan forms may overflow, and none may turn a zero into a
    NaN. 0 is the other end: no decay at all, the plain delta rule."""
    x = inputs(3, t=128, gate=gate, **(TILED if impl == "pallas" else {}))
    got = kda_chunked(*x, chunk=64, sub=16, dtype=jnp.float32, impl=impl)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, kda_recurrent(*x), atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(kda_chunked(
        *a, chunk=64, sub=16, impl=impl)), argnums=(0, 1, 2, 3, 4))(*x)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("keep", [1, 2, 3, 4])
def test_states_kept_for_the_backward_do_not_change_a_number(keep, impl):
    """A state every ``keep`` chunks, the steps between replayed: the same
    values and gradients as a state a chunk (3 does not divide 4 chunks:
    every chunk's is kept)."""
    x = inputs(7, **(TILED if impl == "pallas" else {}))
    sub = 8 if impl == "pallas" else 4        # a kernel's sub-block: 8 rows

    def run(keep):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(kda_chunked(
            *a, chunk=16, sub=sub, keep=keep, dtype=jnp.float32, impl=impl))),
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
    assert kda.kernel_tiles(4096, 128, 128, KDA_CHUNK, KDA_SUB)  # the LM's


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_module_precision_stays_near_the_recurrence(impl):
    """bfloat16 operands, float32 state and solve: rounding, not drift."""
    x = inputs(4, t=128, **(TILED if impl == "pallas" else
                            dict(dk=32, dv=32)))
    want = kda_recurrent(*x)
    got = kda_chunked(*(a.astype(jnp.bfloat16) for a in x[:3]), *x[3:],
                      impl=impl)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(
        jnp.max(jnp.abs(want)))


def test_a_sequence_shorter_than_a_chunk_and_a_ragged_one():
    x = inputs(5, t=8)
    np.testing.assert_allclose(kda_chunked(*x, dtype=jnp.float32),
                               kda_recurrent(*x), atol=2e-5)
    with pytest.raises(ValueError, match="no multiple"):
        kda_chunked(*inputs(5, t=80), chunk=64)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_state_runs_along_the_sequence(impl):
    """Position ``t``'s output depends on every earlier position and on no
    later one; a change at position 0 shows at the last chunk."""
    x = inputs(6, b=1, h=1, t=64, gate=-0.05,
               **(dict(dk=128, dv=128) if impl == "pallas" else {}))

    def run(v):
        return kda_chunked(x[0], x[1], v, x[3], x[4], chunk=16,
                           sub=8 if impl == "pallas" else 4, keep=2,
                           dtype=jnp.float32, impl=impl)

    def moved_by(at):
        return jnp.max(jnp.abs(run(x[2].at[0, 0, at].add(1.0)) - run(x[2])),
                       axis=-1)[0, 0]

    diff = moved_by(20)
    assert float(jnp.max(diff[:20])) == 0.0 and float(diff[20]) > 0.0
    assert float(diff[40]) > 0.0     # carried across two chunk boundaries
    assert float(jnp.min(moved_by(0)[48:])) > 0.0   # and across all four


def _step_operands(seed, c=16, dk=128, dv=128):
    """One head's chunk: a start state, the six operands and cotangents of
    the step's two results, at the sizes of values the scan meets."""
    ks = jax.random.split(jax.random.key(seed), 9)
    shapes = [(dk, dv), (c, dv), (c, dk), (c, c), (c, dk), (c, dk), (dk,),
              (dk, dv), (c, dv)]
    state, *x, d_state, do = (jax.random.normal(k, s) for k, s in
                              zip(ks, shapes))
    x[-1] = jax.nn.sigmoid(x[-1])                              # the decay
    return state, tuple(x), d_state, do


def test_the_chunk_steps_adjoint_by_hand_is_autodiffs():
    """``_step_adjoint``, which the backward kernel runs a chunk at a time
    on the transposed state, against ``jax.vjp`` of the ``jax.numpy`` scan's
    own step: the state's cotangent and all six operands'."""
    state, x, d_state, do = _step_operands(11)

    def batch(a):
        return a[None, None]

    (new, o), vjp = jax.vjp(
        lambda s, *x: kda._step(s, x, jnp.float32), *map(batch, (state, *x)))
    want_state, *want = (a[0, 0] for a in vjp((batch(d_state), batch(do))))
    w_v, w_k, p, q_in, k_out, decay = x
    u = kda._delta(state.T, w_v, w_k, jnp.float32)
    np.testing.assert_allclose(
        kda._next_state(state.T, u, k_out, decay[None], jnp.float32).T,
        new[0, 0], rtol=1e-5, atol=1e-5)
    got_state, got = kda._step_adjoint(state.T, u, d_state.T, do, w_k, p, q_in,
                                       k_out, decay[None], jnp.float32)
    np.testing.assert_allclose(got_state.T, want_state, rtol=1e-4, atol=1e-4)
    for name, a, b in zip(("w_v", "w_k", "p", "q_in", "k_out", "decay"), got,
                          want):
        np.testing.assert_allclose(a.reshape(b.shape), b, rtol=1e-4, atol=1e-4,
                                   err_msg=name)


def _pallas_calls(fn, *args):
    found = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_the_carried_state_and_its_cotangent_are_float32_on_the_kernel_path():
    """With the module's bfloat16 operands: both kernels' VMEM scratch (the
    state; its cotangent and the rebuilt states) and the states kept in HBM
    are float32, the gradient runs through exactly the two kernels, and
    nothing but the inputs and the kept states passes from one to the
    other."""
    x = inputs(8, t=128, **TILED)
    x = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]
    calls = _pallas_calls(jax.grad(lambda *a: jnp.sum(kda_chunked(
        *a, chunk=32, keep=2, impl="pallas")), argnums=(0, 1, 2, 3, 4)), *x)
    assert len(calls) == 2
    fwd, bwd = calls
    for call, n_scratch in ((fwd, 1), (bwd, 2)):
        scratch = call.params["grid_mapping"].scratch_avals
        assert len(scratch) == n_scratch
        assert all(a.dtype == jnp.float32 for a in scratch)
    o, kept = (v.aval for v in fwd.outvars)
    assert o.dtype == kept.dtype == jnp.float32
    assert kept.shape == (2, 2, 128, 128)            # a state every 2 chunks
    assert len(bwd.invars) == 5 + 2                  # inputs, states, do
    assert [v.aval.dtype for v in bwd.outvars] == [a.dtype for a in x]


@pytest.mark.parametrize("shape", [dict(t=8, dk=128, dv=128),
                                   dict(t=64, dk=16, dv=8),
                                   dict(t=64, dk=128, dv=64),
                                   dict(t=64, dk=128, dv=128, sub=4)])
def test_a_shape_the_kernels_do_not_tile_takes_the_other_path(shape):
    """A sequence shorter than a chunk, a head width that is no multiple of
    128, a sub-block of half a sublane tile: ``impl='pallas'`` runs the
    ``jax.numpy`` scan all the same, values and gradients."""
    sub = shape.pop("sub", 8)
    x = inputs(5, b=1, h=2, **shape)
    assert not kda.kernel_tiles(shape["t"], shape["dk"], shape["dv"], 32, sub)

    def run(impl):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(kda_chunked(
            *a, chunk=32, sub=sub, dtype=jnp.float32, impl=impl))),
            argnums=(0, 1, 2, 3, 4))

    assert not _pallas_calls(run("pallas"), *x)
    (got, gg), (want, gw) = run("pallas")(*x), run("xla")(*x)
    assert float(got) == float(want)
    for a, b in zip(gg, gw):
        np.testing.assert_array_equal(a, b)
