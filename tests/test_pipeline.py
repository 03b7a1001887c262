"""GPipe pipeline parallelism (fedml_tpu/parallel/pipeline.py): the N-stage
microbatched schedule must equal the single-device step exactly — the
pipeline only reorders compute (reference's 2-stage analogue: SplitNN,
split_nn/client.py:24-34)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.ops.xent import masked_cross_entropy
from fedml_tpu.parallel.pipeline import (
    make_pp_lm_train_step, place_pp_params, pp_mesh, stack_pipeline_params,
    unstack_pipeline_params,
)

VOCAB, DIM, HEADS, LAYERS, T = 31, 16, 2, 4, 8


def _model():
    return TransformerLM(vocab_size=VOCAB, dim=DIM, heads=HEADS,
                         layers=LAYERS, max_len=T, attn_impl="xla")


def _data(b):
    gen = np.random.default_rng(0)
    x = jnp.asarray(gen.integers(0, VOCAB, size=(b, T)), jnp.int32)
    y = jnp.asarray(gen.integers(0, VOCAB, size=(b, T)), jnp.int32)
    m = jnp.asarray(gen.random((b, T)) < 0.9, jnp.float32)
    return x, y, m


def _reference_step(mod, tx, variables, opt_state, x, y, m):
    def loss_fn(params):
        logits = mod.apply({"params": params}, x)
        per = masked_cross_entropy(logits, y, m, impl="xla")
        return jnp.sum(per) / jnp.maximum(jnp.sum(m), 1.0)

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    updates, opt_state = tx.update(grads, opt_state, variables["params"])
    return optax.apply_updates(variables["params"], updates), opt_state, loss


@pytest.mark.parametrize("n_dp,n_pp,n_micro", [
    # ~15 s: the deep-pipeline shape rides the slow lane; (4, 2, 4) keeps
    # the exact-equality pin (dp axis + microbatching) inside tier-1
    pytest.param(2, 4, 2, marks=pytest.mark.slow),
    (4, 2, 4),
])
def test_pipeline_matches_single_device(n_dp, n_pp, n_micro):
    mod = _model()
    mesh = pp_mesh(n_dp, n_pp)
    x, y, m = _data(b=2 * n_dp * n_micro)
    variables = mod.init(jax.random.key(0), jnp.zeros((1, T), jnp.int32))
    tx = optax.sgd(0.1, momentum=0.9)

    ref_params, _, ref_loss = _reference_step(
        mod, tx, variables, tx.init(variables["params"]), x, y, m)

    pp_params = place_pp_params(
        stack_pipeline_params(variables, LAYERS), mesh)
    opt_state = tx.init(pp_params)
    step = make_pp_lm_train_step(mod, tx, mesh, n_micro=n_micro,
                                 attn_impl="xla")
    pp_params, opt_state, loss = step(pp_params, opt_state, x, y, m)

    assert np.isfinite(float(loss))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    got = unstack_pipeline_params(pp_params, LAYERS)["params"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        got, ref_params)


def test_stack_unstack_roundtrip():
    mod = _model()
    variables = mod.init(jax.random.key(1), jnp.zeros((1, T), jnp.int32))
    rt = unstack_pipeline_params(stack_pipeline_params(variables, LAYERS),
                                 LAYERS)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        rt, variables)


def test_pipeline_two_steps_converge():
    """Two pipeline steps on the same batch must reduce the loss."""
    mod = _model()
    mesh = pp_mesh(2, 4)
    x, y, m = _data(b=8)
    variables = mod.init(jax.random.key(2), jnp.zeros((1, T), jnp.int32))
    tx = optax.sgd(0.5)
    pp_params = place_pp_params(
        stack_pipeline_params(variables, LAYERS), mesh)
    opt_state = tx.init(pp_params)
    step = make_pp_lm_train_step(mod, tx, mesh, n_micro=4, attn_impl="xla")
    pp_params, opt_state, l0 = step(pp_params, opt_state, x, y, m)
    _, _, l1 = step(pp_params, opt_state, x, y, m)
    assert float(l1) < float(l0)


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_3d_dp_pp_sp_matches_single_device(sp_mode):
    """DP x PP x SP in one program: pipeline stages with sequence-parallel
    attention inside each stage must equal the single-device step."""
    from fedml_tpu.parallel.pipeline import make_pp_sp_lm_train_step, pp3d_mesh

    mod = _model()
    mesh = pp3d_mesh(2, 2, 2)
    x, y, m = _data(b=2 * 2 * 2)  # n_dp * n_micro * mb
    variables = mod.init(jax.random.key(3), jnp.zeros((1, T), jnp.int32))
    tx = optax.sgd(0.1, momentum=0.9)

    ref_params, _, ref_loss = _reference_step(
        mod, tx, variables, tx.init(variables["params"]), x, y, m)

    pp_params = place_pp_params(
        stack_pipeline_params(variables, LAYERS), mesh)
    opt_state = tx.init(pp_params)
    step = make_pp_sp_lm_train_step(mod, tx, mesh, n_micro=2,
                                    attn_impl="xla", sp_mode=sp_mode)
    xs = jax.device_put(x, jax.NamedSharding(mesh, jax.P("dp", "sp")))
    ys_ = jax.device_put(y, jax.NamedSharding(mesh, jax.P("dp", "sp")))
    ms = jax.device_put(m, jax.NamedSharding(mesh, jax.P("dp", "sp")))
    pp_params, opt_state, loss = step(pp_params, opt_state, xs, ys_, ms)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    got = unstack_pipeline_params(pp_params, LAYERS)["params"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5),
        got, ref_params)
