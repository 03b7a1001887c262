"""Each plain reference against ``run_round`` at a tiny size; every control
(the reference one precision below what the configuration states, in the
program's place) has to come out as not correct."""

import jax
import numpy as np
import pytest

from benchmarks.harness import check, protocol
from benchmarks.harness.cell import build_api

ROUNDS = [1, 2]


def _program_and_reference(spec, cell_name, seed, variant="reference",
                           tweak=None):
    cell = spec.cell(cell_name)
    config = spec.config(cell["config"])
    ref = spec.module("references", config["reference"])
    ds, rows = spec.module("traffic", config["generator"]).make(
        config, cell, seed)
    api = build_api(config, cell, ds)
    root = protocol.run_key(seed)
    init = ref.init(jax.random.fold_in(root, 1), config)
    assert not check.same_tree(jax.device_get(api.variables),
                               jax.device_get(init))
    api.variables, api.root_key = init, root
    init_h = jax.device_get(init)
    losses, states = [], []
    for r in ROUNDS:
        losses.append(float(api.run_round(r)))
        state = jax.device_get(api.variables)
        if tweak:
            state = tweak(state)
            api.variables = state
        states.append(state)
    api.close()
    ref_losses, ref_states = check.reference_rounds(
        ref, config, cell, rows, init_h, seed, ROUNDS, variant)
    return cell["limits"], init_h, (losses, states), (ref_losses, ref_states)


@pytest.mark.parametrize("cell_name", ["tiny_sim", "tiny_xdev"])
def test_reference_follows_run_round(tiny_spec, cell_name):
    limits, init, prog, refd = _program_and_reference(tiny_spec, cell_name, 5)
    out = check.compare(*prog, *refd, init, limits)
    assert out["ok"], out["numbers"]
    # and closely: the rules of protocol.py are the program's
    assert max(n[1] for n in out["numbers"] if n[0].startswith("loss_rel")) < 5e-3


@pytest.mark.parametrize("cell_name", ["tiny_sim", "tiny_xdev"])
def test_update_rounded_to_bf16_fails(tiny_spec, cell_name):
    import jax.numpy as jnp

    def to_bf16(state):
        return jax.tree.map(lambda a: np.asarray(
            jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)), state)

    limits, init, prog, refd = _program_and_reference(
        tiny_spec, cell_name, 5, tweak=to_bf16)
    out = check.compare(*prog, *refd, init, limits)
    assert not out["ok"]
    failed = {n[0] for n in out["numbers"] if not n[3]}
    assert "lowp_share" in failed


@pytest.mark.parametrize("config_name,cell_name",
                         [("tiny_resnet", "tiny_sim"), ("tiny_lr", "tiny_xdev")])
def test_control_in_the_programs_place_fails(tiny_spec, config_name, cell_name):
    """The reference at a lower precision than stated, compared as a program
    would be: every control fails at least one number; the reference at the
    stated precision fails none."""
    cell = tiny_spec.cell(cell_name)
    config = tiny_spec.config(config_name)
    ref = tiny_spec.module("references", config["reference"])
    _ds, rows = tiny_spec.module("traffic", config["generator"]).make(
        config, cell, 7)
    init = jax.device_get(ref.init(jax.random.key(7), config))
    base = check.reference_rounds(ref, config, cell, rows, init, 7, ROUNDS)
    for variant in ref.CONTROLS:
        low = check.reference_rounds(ref, config, cell, rows, init, 7,
                                     ROUNDS, variant)
        out = check.compare(*low, *base, init, cell["limits"])
        assert not out["ok"], (variant, out["numbers"])
    stated = check.reference_rounds(ref, config, cell, rows, init, 7, ROUNDS,
                                    "stated")
    out = check.compare(*stated, *base, init, cell["limits"])
    assert out["ok"], out["numbers"]


def test_update_numbers_on_hand_made_states():
    init = {"params": {"a": np.ones((4, 4)), "b": np.zeros(3)}}
    ref = {"params": {"a": np.ones((4, 4)) * 1.5, "b": np.ones(3) * 0.1}}

    def gap(state):
        return check.norm_gap(check.leaf_norms(state, ref, init))

    assert gap(init)[0] == pytest.approx(1.0)            # state unchanged
    half = {"params": {"a": np.ones((4, 4)) * 1.25, "b": np.ones(3) * 0.05}}
    assert gap(half)[0] == pytest.approx(0.5)            # half the update
    assert gap(ref)[0] == 0.0
    # one leaf far off moves the whole vector's norm little, and is named
    off = {"params": {"a": np.ones((4, 4)) * 1.5, "b": np.ones(3) * 0.2}}
    g, leaf = gap(off)
    assert g < 0.02 and "params/b" in leaf
    # the difference's norm: whole vector, and the median leaf
    whole, median = check.diff_l2(check.leaf_norms(off, ref, init))
    assert whole == pytest.approx(np.sqrt(3 * 0.01) / np.sqrt(16 * 0.25 + 3 * 0.01))
    assert median == pytest.approx(0.5)                  # leaves read 0 and 1
    # an update of the right norm in the wrong direction: no gap, all difference
    turned = {"params": {"a": np.ones((4, 4)) * 0.5, "b": np.ones(3) * -0.1}}
    assert gap(turned)[0] == pytest.approx(0.0)
    assert check.diff_l2(check.leaf_norms(turned, ref, init))[0] == pytest.approx(2.0)


def test_a_limit_for_a_number_the_check_does_not_compute_is_an_error():
    s = {"params": {"a": np.ones(2)}}
    with pytest.raises(KeyError):
        check.compare([1.0], [s], [1.0], [s], s, {"no_such_number": 1.0})
    out = check.compare([1.0], [s], [1.0], [s], s, {})
    assert out["ok"] and all(n[2] is None for n in out["numbers"])


def test_protocol_sampling_and_orders_are_deterministic():
    a = protocol.sample_cohort(3, 100, 10, 0)
    assert np.array_equal(a, protocol.sample_cohort(3, 100, 10, 0))
    assert len(set(a)) == 10 and list(a) == sorted(a)
    assert np.array_equal(protocol.sample_cohort(3, 8, 8, 0), np.arange(8))
    mask = np.array([1, 1, 1, 0, 0, 1, 0, 0], np.float32)
    orders = protocol.epoch_orders(jax.random.key(1), 2, mask)
    assert orders.shape == (2, 8)
    for o in orders:
        assert sorted(o) == list(range(8))
        assert mask[o][:4].all() and not mask[o][4:].any()
