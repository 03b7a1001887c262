"""packed-everywhere (ISSUE 12): the joint-lanes MXU fast path as the
DEFAULT training abstraction.

Pinned contracts:
1. coverage matrix: every shipped algorithm x {dropout, no-dropout} x
   {plain, silo} either reports ``packed_conv_active`` or names a
   documented fallback reason (DESIGN.md §15 exception table) — no silent
   vmap paths;
2. per-paradigm parity: packed-vs-vmap end-to-end equivalence for
   fedopt/fedprox/fednova/fedagc, adaptive CLIENT optimizers, and a
   dropout model, at the fedseg-documented tolerance, mirroring
   tests/test_packed_conv.py's structure; flag-off stays bit-identical;
3. the packed FedOpt round program's static lane ceiling >= 0.8
   (census-pinned like the 0.895 flagship pin, honest useful-FLOPs intact);
4. fallback accounting: registry "packed" counter lane + per-federation
   warn keying (obs.reset clears both);
5. Silo per-client early EXIT is a masked lane freeze inside the same
   compiled program, equivalent to zero-weighting on every schedule.
"""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedagc import FedAGCAPI
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.fednova import FedNovaAPI
from fedml_tpu.algorithms.fedopt import FedOptAPI
from fedml_tpu.algorithms.fedprox import FedProxAPI
from fedml_tpu.algorithms.silo import SiloRunner
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.obs import cost
from fedml_tpu.parallel import packed as packed_mod

# the fedseg-documented equivalence scale (see tests/test_packed_conv.py)
W_RTOL, W_ATOL = 1e-2, 1.5e-3

ALGOS = {
    "fedavg": (FedAvgAPI, {}),
    "fedopt": (FedOptAPI, dict(server_optimizer="adam", server_lr=0.01)),
    "fedprox": (FedProxAPI, dict(fedprox_mu=0.5)),
    "fednova": (FedNovaAPI, dict(momentum=0.9)),
    "fedagc": (FedAGCAPI, {}),
}

#: the DESIGN.md §15 exception table — the ONLY admissible fallback reasons
#: after packed-everywhere (substring match; anything else is a silent gap)
DOCUMENTED_REASONS = (
    "packed_conv=off",
    "no packed conv variant",
    "flax-rng dropout",
    "pack_lanes=0",
    "no packed-lane algorithm mirror",
    "round path runs no packed lanes",
)


def _ds(shape=(12, 12, 1), clients=8, records=16, seed=5):
    return make_synthetic_classification(
        "pe", shape, 4, clients, records_per_client=records,
        partition_method="hetero", partition_alpha=0.4, batch_size=4,
        seed=seed)


def _cfg(model, **kw):
    base = dict(model=model, dataset="pe", client_num_in_total=8,
                client_num_per_round=8, comm_round=1, batch_size=4,
                epochs=1, lr=0.005, momentum=0.0, seed=0,
                frequency_of_the_test=1000, pack_lanes=4, device_data="on",
                packed_conv="blockdiag")
    base.update(kw)
    return FedConfig(**base)


# -- 1. the coverage matrix ---------------------------------------------------

@pytest.fixture(scope="module")
def cnn_ds():
    return _ds()


@pytest.mark.parametrize("algo", sorted(ALGOS))
@pytest.mark.parametrize("model", ["cnn", "cnn_dropout"])
@pytest.mark.parametrize("silo", [False, True])
def test_coverage_matrix_no_silent_vmap(algo, model, silo, cnn_ds):
    """Every shipped optimizer x {dropout, no-dropout} x {silo, plain}
    combination reports packed_conv_active=True, or names a reason from
    the documented exception table. After packed-everywhere, these conv
    models all pack — a False here is a regression to silent vmap."""
    cls, kw = ALGOS[algo]
    cfg = _cfg(model, **kw)
    bundle = create_model(model, 4, input_shape=(12, 12, 1))
    if silo:
        api = SiloRunner(cnn_ds, cfg, cls, bundle).api
    else:
        api = cls(cnn_ds, cfg, bundle)
    st = api.packed_status()
    if not st["packed_conv_active"]:
        assert st["reason"] and any(
            r in st["reason"] for r in DOCUMENTED_REASONS), st
        pytest.fail(f"{algo}/{model}/silo={silo} fell back: {st}")
    assert st["scheduled"], st


@pytest.mark.parametrize("opt", ["sgd", "adam", "adamw", "adagrad", "yogi"])
def test_coverage_client_optimizers_all_pack(opt):
    """Every client optimizer make_optimizer ships rides the stacked
    per-lane state — none disqualifies the joint form."""
    conv = create_model("resnet20", 4, input_shape=(8, 8, 3))
    assert packed_mod.packed_fallback_reason(conv, "blockdiag", opt) is None


def test_coverage_unpackable_models_name_documented_reasons():
    lr = create_model("lr", 4, input_shape=(6,))
    r = packed_mod.packed_fallback_reason(lr, "blockdiag")
    assert "no packed conv variant" in r
    # a dropout model whose packed twin does NOT opt into the explicit
    # per-lane key stream keeps the documented dropout fallback
    import dataclasses

    drop = create_model("cnn_dropout", 4)
    legacy_twin = dataclasses.replace(
        drop.packed_variant("blockdiag"), explicit_dropout=False)
    legacy = dataclasses.replace(
        drop, packed_variant=lambda impl: legacy_twin)
    r = packed_mod.packed_fallback_reason(legacy, "blockdiag")
    assert "flax-rng dropout" in r


def test_packed_round_engages_for_silo_fedopt(cnn_ds):
    """One end-to-end silo run: the harness's API compiles and runs the
    PACKED round program (server state threaded), not a fallback."""
    runner = SiloRunner(cnn_ds, _cfg("cnn", comm_round=1,
                                     server_optimizer="adam",
                                     server_lr=0.01, frequency_of_the_test=1),
                        FedOptAPI, create_model("cnn", 4,
                                                input_shape=(12, 12, 1)))
    h = runner.train()
    assert runner.api._packed_steps, "packed round program must engage"
    assert len(h["GLOBAL/Train/Loss"]) == 1
    leaves = jax.tree.leaves(runner.api.server_state)
    assert leaves and any(np.abs(np.asarray(l)).max() > 0 for l in leaves)


# -- 2. per-paradigm packed-vs-vmap parity pins -------------------------------

@pytest.fixture(scope="module")
def conv_ds():
    return _ds(shape=(8, 8, 3), records=12, seed=3)


def _run_conv(ds, cls, rounds=1, **kw):
    kw.setdefault("packed_conv", "off")
    cfg = _cfg("resnet20", **kw)
    api = cls(ds, cfg, create_model("resnet20", 4, input_shape=(8, 8, 3)))
    losses = [float(api.run_round(r)) for r in range(1, rounds + 1)]
    return api, losses


# stateful server (momentum buffer threads through the packed round) but
# NOT adam: normalized server updates amplify one-ULP lowering drift into
# ±server_lr element flips — the chaos class the adaptive-CLIENT pin below
# documents and bounds loosely
FEDOPT_SGD_KW = dict(server_optimizer="sgd", server_momentum=0.9,
                     server_lr=0.05)


@pytest.fixture(scope="module")
def fedopt_off_run(conv_ds):
    """FedOpt on the packed schedule with vmap lanes — the off arm shared
    by the joint-form parity pin and the packed-vs-plain pin."""
    return _run_conv(conv_ds, FedOptAPI, **FEDOPT_SGD_KW)


# fedopt rides tier-1 as the representative adaptive paradigm; the other
# three (~10 s each) pin the same joint-vs-vmap parity on the slow lane —
# their cheap packed-vs-sim twins in test_packed_zoo.py stay in-budget
@pytest.mark.parametrize("algo", [
    "fedopt",
    pytest.param("fedprox", marks=pytest.mark.slow),
    pytest.param("fednova", marks=pytest.mark.slow),
    pytest.param("fedagc", marks=pytest.mark.slow),
])
def test_algorithm_packed_conv_matches_vmap_lowering(algo, conv_ds,
                                                     fedopt_off_run):
    """The joint MXU form vs the per-lane vmap form, per adaptive
    paradigm, one heterogeneous round (ragged lanes: dead steps, LPT
    tails). Bounds are the fedseg scale — a hook-threading or per-lane
    optimizer-state bug would blow them by orders of magnitude."""
    cls, kw = ALGOS[algo]
    if algo == "fedopt":
        kw = FEDOPT_SGD_KW
        api_off, l_off = fedopt_off_run
    else:
        api_off, l_off = _run_conv(conv_ds, cls, **kw)
    api_on, l_on = _run_conv(conv_ds, cls, packed_conv="blockdiag", **kw)
    assert api_on._packed_steps
    np.testing.assert_allclose(l_on, l_off, rtol=1e-2)
    for a, b in zip(jax.tree.leaves(api_on.variables),
                    jax.tree.leaves(api_off.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=W_RTOL, atol=2 * W_ATOL)


@pytest.mark.slow
def test_adaptive_client_optimizer_packed_parity(conv_ds):
    """Client adam through the joint form's stacked per-lane optax state.
    Weight bounds are DELIBERATELY loose: amsgrad's normalized update is
    ~±lr per element regardless of gradient magnitude, so a single-ULP
    lowering flip in a near-zero gradient flips a whole ±lr step
    (measured: ~0.02 max leaf drift at lr 2e-3 after one round, vs ~1e-4
    for sgd) — the LOSS, which averages the chaos, holds a tight bound,
    and the sgd-family pins above carry the numerical-equivalence
    argument."""
    api_off, l_off = _run_conv(conv_ds, FedAvgAPI,
                               client_optimizer="adam", lr=0.002)
    api_on, l_on = _run_conv(conv_ds, FedAvgAPI, packed_conv="blockdiag",
                             client_optimizer="adam", lr=0.002)
    np.testing.assert_allclose(l_on, l_off, rtol=5e-3)
    for a, b in zip(jax.tree.leaves(api_on.variables),
                    jax.tree.leaves(api_off.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0.05)


def test_dropout_model_packed_parity(cnn_ds):
    """cnn_dropout through the joint form: per-lane masks derive from the
    SAME per-lane batch keys the vmap form consumes (explicit-key
    dropout), so parity is GEMM-summation-order only — bounds far
    TIGHTER than the conv e2e pins (measured ~6e-8 max leaf drift)."""
    def run(**kw):
        kw.setdefault("packed_conv", "off")
        api = FedAvgAPI(cnn_ds, _cfg("cnn_dropout", comm_round=2, lr=0.01,
                                     **kw),
                        create_model("cnn_dropout", 4,
                                     input_shape=(12, 12, 1)))
        return api, [float(api.run_round(r)) for r in (1, 2)]

    api_off, l_off = run()
    api_on, l_on = run(packed_conv="blockdiag")
    assert api_on._packed_steps
    np.testing.assert_allclose(l_on, l_off, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(api_on.variables),
                    jax.tree.leaves(api_off.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_fedopt_packed_schedule_matches_plain(conv_ds, fedopt_off_run):
    """Hook-folding math, weight-level: FedOpt on the packed schedule
    (hooks at lane emit + post-aggregation server update) equals the
    plain unpacked path (FedOptAPI.aggregate) to float-sum tolerance —
    the two differ ONLY in summation order of the weighted mean. The
    FedAvg flag-off arm stays bit-identical to the default config in
    tests/test_packed_conv.py; this pins the refactored tail
    (apply_server_and_rollback + threaded server state) against the
    aggregate() source of truth."""
    api_off, l_off = fedopt_off_run
    cfg = _cfg("resnet20", pack_lanes=0, device_data="off",
               packed_conv="off", **FEDOPT_SGD_KW)
    api_plain = FedOptAPI(conv_ds, cfg,
                          create_model("resnet20", 4, input_shape=(8, 8, 3)))
    l_plain = [float(api_plain.run_round(1))]
    np.testing.assert_allclose(l_off, l_plain, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(api_off.variables),
                    jax.tree.leaves(api_plain.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree.leaves(api_off.server_state),
                    jax.tree.leaves(api_plain.server_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


# -- 3. the packed FedOpt round program's lane ceiling (acceptance pin) -------

@pytest.mark.slow  # ~13 s: the fedavg round-program ceiling pin in
#                    test_packed_conv.py keeps the census in-budget
def test_packed_fedopt_round_program_ceiling():
    """ISSUE 12 acceptance: the packed (blockdiag, K=4) FedOpt flagship
    round program's flop-weighted output-lane ceiling >= 0.8 — the server
    optimizer is elementwise, so the program keeps the sgd packed census
    (census-pinned) and its 0.895-class ceiling; honest useful-FLOPs
    accounting stays intact."""
    ds = make_synthetic_classification(
        "pe-census", (32, 32, 3), 10, 8, records_per_client=8,
        partition_method="homo", partition_alpha=0.5, batch_size=4, seed=0)
    cfg = FedConfig(model="resnet56", dataset="cifar10",
                    client_num_in_total=8, client_num_per_round=4,
                    comm_round=1, batch_size=4, epochs=1, lr=0.1,
                    dtype="bfloat16", frequency_of_the_test=1000, seed=0,
                    pack_lanes=4, packed_conv="blockdiag", device_data="on",
                    server_optimizer="adam", server_lr=0.05)
    bundle = create_model("resnet56", 10, dtype=jnp.bfloat16,
                          input_shape=(32, 32, 3))
    api = FedOptAPI(ds, cfg, bundle)
    round_plan = api._round_plan(1, record=False)
    sampled, plan = round_plan.sampled, round_plan.lanes
    assert plan.n_lanes == 4
    step = api.build_round_step_packed(plan.shape_key)
    hints = getattr(step, "cost_hints", None)
    assert hints == {"packed_conv": "blockdiag", "packing_factor": 4}
    counts = np.asarray(ds.train_counts, np.float32)[sampled]
    plan_arrays = tuple(jnp.asarray(a)
                        for a in packed_mod.plan_arrays_tuple(plan))
    tx, ty, tm, _tc = api._dev_train
    rep = cost.analyze_jitted(step, (
        api.variables, api.server_state, tx, ty, tm,
        jnp.asarray(sampled, jnp.int32), jnp.asarray(counts),
        jax.random.PRNGKey(0), plan_arrays))
    assert rep is not None
    cost.apply_packing(rep["ops"], hints["packing_factor"],
                       hints["packed_conv"])
    s = cost.summarize(rep["ops"], rep["summary"]["unknown_trip_counts"])
    # census: identical block-dot population to the FedAvg packed program
    # (test_packed_conv.py) — FedAdam adds zero GEMMs
    census = {}
    for o in rep["ops"]:
        if o["kind"] != "dot":
            continue
        key = (o["n"], o["packing_factor"])
        census[key] = census.get(key, 0) + 1
    assert census == {(10, 1): 1, (64, 1): 2,
                      (64, 4): 21, (108, 4): 1, (128, 4): 21, (256, 4): 19,
                      (576, 4): 38, (1152, 4): 36, (2304, 4): 34}, census
    # the acceptance bar, same style as the 0.895 flagship pin
    assert s["out_lane_ceiling"] >= 0.8, s["out_lane_ceiling"]
    assert 0.85 < s["out_lane_ceiling"] < 0.93
    assert s["packing"]["max_factor"] == 4
    assert 0.25 < s["packing"]["useful_flops_frac"] < 0.35
    assert not s["unknown_trip_counts"]


# -- 4. fallback accounting: registry lane + per-federation warn keying -------

def test_fallback_counted_and_rewarns_after_reset(caplog):
    from fedml_tpu import obs
    from fedml_tpu.core.tasks import get_task
    from fedml_tpu.obs import default_registry

    obs.reset()
    lr = create_model("lr", 4, input_shape=(6,))
    task = get_task("classification", 4)

    def build():
        packed_mod.make_lanes_train(lr, task, 8, packed_conv="blockdiag",
                                    batch_size=4)

    with caplog.at_level(logging.WARNING, logger="fedml_tpu.parallel.packed"):
        build()
        build()
    warns = [r for r in caplog.records if "falls back" in r.message]
    assert len(warns) == 1, "warn-once per (model, lowering)"
    snap = default_registry().snapshot("packed")
    assert snap.get("fallback:lr:blockdiag") == 2, snap
    # obs.reset => fresh federation: counters drop, the warning re-fires
    obs.reset()
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="fedml_tpu.parallel.packed"):
        build()
    assert any("falls back" in r.message for r in caplog.records)
    assert default_registry().snapshot("packed").get(
        "fallback:lr:blockdiag") == 1


# -- 5. Silo per-client early exit as a masked lane freeze --------------------

def test_masked_plan_structural_noop():
    counts = np.array([37, 5, 80, 16, 3, 64, 22, 9])
    plan = packed_mod.plan_packing(counts, batch_size=8, epochs=2, n_lanes=3)
    active = np.ones((plan.n_lanes, plan.k_max), np.float32)
    # kill one real member
    l, k = next((l, k) for l in range(plan.n_lanes)
                for k in range(plan.k_max) if plan.member_valid[l, k])
    active[l, k] = 0.0
    (slot, epoch, sie, reset, emit, live, member_pos, member_valid,
     steps_real) = packed_mod.plan_arrays_tuple(
         packed_mod.masked_plan(plan, active))
    dead = (plan.slot[l] == k) & (plan.live[l] > 0)
    assert dead.any()
    assert not live[l][dead].any() and not emit[l][dead].any() \
        and not reset[l][dead].any()
    assert member_valid[l, k] == 0.0
    # everything else untouched
    other = ~dead
    np.testing.assert_array_equal(live[l][other], plan.live[l][other])
    others = [i for i in range(plan.n_lanes) if i != l]
    np.testing.assert_array_equal(live[others], plan.live[others])
    np.testing.assert_array_equal(slot, plan.slot)
    np.testing.assert_array_equal(steps_real, plan.steps_real)


def _lr_ds():
    return make_synthetic_classification(
        "pe-silo", (6,), 4, 8, records_per_client=40,
        partition_method="hetero", partition_alpha=0.3, batch_size=8, seed=7)


def _lr_cfg(**kw):
    base = dict(model="lr", dataset="pe-silo", client_num_in_total=8,
                client_num_per_round=8, comm_round=3, batch_size=8, lr=0.2,
                momentum=0.9, epochs=1, frequency_of_the_test=1000, seed=11,
                device_data="on", bucket_quantum_batches=1, pack_lanes=4)
    base.update(kw)
    return FedConfig(**base)


def test_client_active_mask_packed_matches_unpacked():
    """set_client_active through the PACKED schedule (masked lane freeze)
    equals the plain unpacked schedule with the same mask (weight-zero):
    the structural no-op changes which slots compute, never the
    aggregate."""
    ds = _lr_ds()
    mask = np.array([1, 1, 0, 1, 0, 1, 1, 1], np.float32)

    def run(**kw):
        api = FedAvgAPI(ds, _lr_cfg(**kw))
        api.set_client_active(mask)
        return api, [float(api.run_round(r)) for r in range(3)]

    api_p, lp = run()
    assert api_p._packed_steps, "packed path must engage"
    api_u, lu = run(pack_lanes=0, bucket_quantum_batches=0,
                    device_data="off")
    np.testing.assert_allclose(lp, lu, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(api_p.variables),
                    jax.tree.leaves(api_u.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_silo_client_patience_exits_and_freezes():
    """Per-client early stopping: a stalled client exits (recorded in the
    history), the run completes, and the api carries the active mask the
    packed schedule freezes lanes with."""
    ds = _lr_ds()
    runner = SiloRunner(ds, _lr_cfg(comm_round=6, frequency_of_the_test=1),
                        FedAvgAPI, patience=100,
                        client_patience=1, client_min_delta=1.0)
    h = runner.train()
    stopped = [k for k in h if k.endswith("/stopped_round")]
    # min_delta=1.0 on an accuracy metric cannot be beaten: every client
    # stalls immediately and exits after one stalled eval
    assert stopped, h.keys()
    assert len(h["GLOBAL/Train/Loss"]) < 6 or not runner._client_on.all()
