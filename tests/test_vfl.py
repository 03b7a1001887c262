"""Vertical-FL correctness tests (reference standalone/classical_vertical_fl/).

The load-bearing property: the three executions of the protocol — fused
autodiff, shard_map over a party mesh axis, and the explicit guest/host
common-gradient relay — are the SAME math and must produce identical
parameters from identical inits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from fedml_tpu.algorithms.vfl import (
    VFLAPI,
    build_protocol_vfl,
    init_party_params,
    make_sharded_vfl_step,
    pad_party_params,
    party_component,
)
from fedml_tpu.data.vertical import make_synthetic_vertical


def _ds():
    return make_synthetic_vertical((6, 5), n_train=128, n_test=64, seed=7)


def test_vfl_fused_learns():
    ds = _ds()
    api = VFLAPI(ds, hidden_dim=8, lr=0.05, batch_size=32, seed=1)
    out = api.fit(epochs=12, seed=2)
    assert out["Test/Acc"] > 0.8, out


def test_protocol_matches_fused():
    ds = _ds()
    api = VFLAPI(ds, hidden_dim=8, lr=0.05, batch_size=32, seed=3)
    proto = build_protocol_vfl(ds, hidden_dim=8, lr=0.05, seed=3)

    # identical batches through both paths
    for step in range(5):
        idx = np.arange(step * 16, step * 16 + 16)
        xs = [p[idx] for p in ds.train_parts]
        y = ds.train_y[idx]
        api.params, api.opt_states, _ = api._step(
            api.params, api.opt_states, [jnp.asarray(x) for x in xs], jnp.asarray(y)
        )
        proto.fit(xs[0], y, {1: xs[1]}, step)

    for a, b in zip(api.params[0].values(), proto.guest.params.values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(api.params[1].values(), proto.hosts[1].params.values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_sharded_matches_fused():
    ds = _ds()
    P_parties = 2
    devs = np.array(jax.devices()[:P_parties])
    mesh = Mesh(devs, ("party",))
    api = VFLAPI(ds, hidden_dim=8, lr=0.05, batch_size=32, seed=4)
    stacked = pad_party_params(api.params, ds.party_dims)
    step, tx = make_sharded_vfl_step(mesh, lr=0.05)
    sopt = jax.vmap(tx.init)(stacked)

    d_max = max(ds.party_dims)
    # enough steps that a trainable-mask bug would compound visibly
    for s in range(4):
        idx = np.arange((s % 3) * 32, (s % 3) * 32 + 32)
        xs = [p[idx] for p in ds.train_parts]
        y = jnp.asarray(ds.train_y[idx])
        xp = np.zeros((P_parties, 32, d_max), np.float32)
        for p, x in enumerate(xs):
            xp[p, :, : x.shape[1]] = x
        stacked, sopt, loss = step(stacked, sopt, jnp.asarray(xp), y)
        api.params, api.opt_states, floss = api._step(
            api.params, api.opt_states, [jnp.asarray(x) for x in xs], y
        )
        np.testing.assert_allclose(float(loss), float(floss), atol=1e-5)

    np.testing.assert_allclose(
        np.asarray(stacked["local_w"][0, : ds.party_dims[0]]),
        np.asarray(api.params[0]["local_w"]), atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(stacked["head_w"][1]), np.asarray(api.params[1]["head_w"]), atol=1e-4,
    )
    # the structural guest-bias mask must never train
    np.testing.assert_array_equal(
        np.asarray(stacked["head_b_mask"][:, 0]), np.array([1.0, 0.0])
    )


def test_guest_alone_underperforms_federation():
    """The property VFL exists for: the guest's slice alone is insufficient."""
    ds = make_synthetic_vertical((4, 12), n_train=512, n_test=256, seed=9)
    full = VFLAPI(ds, hidden_dim=8, lr=0.05, batch_size=64, seed=1)
    full.fit(epochs=15, seed=2)
    guest_only_ds = make_synthetic_vertical((4, 12), n_train=512, n_test=256, seed=9)
    guest_only_ds.train_parts = guest_only_ds.train_parts[:1]
    guest_only_ds.test_parts = guest_only_ds.test_parts[:1]
    solo = VFLAPI(guest_only_ds, hidden_dim=8, lr=0.05, batch_size=64, seed=1)
    solo.fit(epochs=15, seed=2)
    assert full.history[-1]["Test/Acc"] > solo.history[-1]["Test/Acc"] + 0.05
