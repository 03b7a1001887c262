"""packed-everywhere: no algorithm silently leaves the packed schedule.

Pinned contracts:
1. coverage matrix: every shipped algorithm x {dropout, no-dropout} x
   {plain, silo}, and every client optimizer, is SCHEDULED on the packed
   lanes (``packed_status()``, ``RoundPlan.path``), and an API that is not
   names one of the documented reasons;
2. the lanes replay each client: a dropout model's masks on the packed
   lanes are its clients' own (the gather round's), and FedOpt's hooks on
   the packed schedule equal the plain path's ``aggregate()``;
3. Silo per-client early EXIT is a masked lane freeze inside the same
   compiled program, equivalent to zero-weighting on every schedule.
"""

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.fedagc import FedAGCAPI
from fedml_tpu.algorithms.fedavg import PATH_PACKED, FedAvgAPI
from fedml_tpu.algorithms.fednova import FedNovaAPI
from fedml_tpu.algorithms.fedopt import FedOptAPI
from fedml_tpu.algorithms.fedprox import FedProxAPI
from fedml_tpu.algorithms.silo import SiloRunner
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.parallel import packed as packed_mod

ALGOS = {
    "fedavg": (FedAvgAPI, {}),
    "fedopt": (FedOptAPI, dict(server_optimizer="adam", server_lr=0.01)),
    "fedprox": (FedProxAPI, dict(fedprox_mu=0.5)),
    "fednova": (FedNovaAPI, dict(momentum=0.9)),
    "fedagc": (FedAGCAPI, {}),
}


def _ds(shape=(12, 12, 1), clients=8, records=16, seed=5):
    return make_synthetic_classification(
        "pe", shape, 4, clients, records_per_client=records,
        partition_method="hetero", partition_alpha=0.4, batch_size=4,
        seed=seed)


def _cfg(model, **kw):
    base = dict(model=model, dataset="pe", client_num_in_total=8,
                client_num_per_round=8, comm_round=1, batch_size=4,
                epochs=1, lr=0.005, momentum=0.0, seed=0,
                frequency_of_the_test=1000, pack_lanes=4, device_data="on")
    base.update(kw)
    return FedConfig(**base)


# -- 1. the coverage matrix ---------------------------------------------------

@pytest.fixture(scope="module")
def cnn_ds():
    return _ds()


def _assert_scheduled(api):
    assert api.packed_status() == {"scheduled": True, "reason": None}
    assert api._round_plan(1, record=False).path == PATH_PACKED


@pytest.mark.parametrize("algo", sorted(ALGOS))
@pytest.mark.parametrize("model", ["cnn", "cnn_dropout"])
@pytest.mark.parametrize("silo", [False, True])
def test_coverage_matrix_no_silent_vmap(algo, model, silo, cnn_ds):
    """Every shipped optimizer x {dropout, no-dropout} x {silo, plain}
    combination is scheduled on the packed lanes: its round plan's path is
    the packed one, not the vmap over the cohort."""
    cls, kw = ALGOS[algo]
    cfg = _cfg(model, **kw)
    bundle = create_model(model, 4, input_shape=(12, 12, 1))
    if silo:
        api = SiloRunner(cnn_ds, cfg, cls, bundle).api
    else:
        api = cls(cnn_ds, cfg, bundle)
    _assert_scheduled(api)


@pytest.mark.parametrize("opt", ["sgd", "adam", "adamw", "adagrad", "yogi"])
def test_coverage_client_optimizers_all_pack(opt, cnn_ds):
    """Every client optimizer make_optimizer ships rides the lanes (its
    state is a lane's own, reset at each client boundary) — none takes the
    round off the packed schedule."""
    _assert_scheduled(FedAvgAPI(
        cnn_ds, _cfg("cnn", client_optimizer=opt),
        create_model("cnn", 4, input_shape=(12, 12, 1))))


def test_coverage_unpackable_models_name_documented_reasons(cnn_ds):
    """An API that runs no packed lanes says why: each of the three
    reasons ``packed_status`` documents, and nothing else."""
    bundle = create_model("cnn", 4, input_shape=(12, 12, 1))

    class Rewired(FedAvgAPI):
        def build_local_train(self):
            return super().build_local_train()

    for cls, kw, why in [
            (FedAvgAPI, dict(pack_lanes=0), "pack_lanes=0"),
            (Rewired, {}, "no packed-lane algorithm mirror"),
            (FedAvgAPI, dict(device_data="off"),
             "round path runs no packed lanes")]:
        st = cls(cnn_ds, _cfg("cnn", **kw), bundle).packed_status()
        assert st["scheduled"] is False and why in st["reason"], st


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


# -- 2. the lanes replay each client ------------------------------------------

@pytest.fixture(scope="module")
def conv_ds():
    return _ds(shape=(8, 8, 3), records=12, seed=3)


def _run_conv(ds, cls, rounds=1, **kw):
    cfg = _cfg("resnet20", **kw)
    api = cls(ds, cfg, create_model("resnet20", 4, input_shape=(8, 8, 3)))
    losses = [float(api.run_round(r)) for r in range(1, rounds + 1)]
    return api, losses


# stateful server (momentum buffer threads through the packed round) but
# NOT adam: normalized server updates amplify one-ULP summation-order drift
# into ±server_lr element flips
FEDOPT_SGD_KW = dict(server_optimizer="sgd", server_momentum=0.9,
                     server_lr=0.05)


@pytest.fixture(scope="module")
def fedopt_off_run(conv_ds):
    """FedOpt on the packed schedule."""
    return _run_conv(conv_ds, FedOptAPI, **FEDOPT_SGD_KW)


def test_dropout_model_lanes_replay_each_clients_masks(cnn_ds):
    """cnn_dropout on the packed lanes against the gather round (one vmap
    over the cohort, each client trained from its own round key): a lane
    draws every step's masks from its member's batch key
    (models/cnn.seed_dropout), so the two schedules train the same clients
    on the same masks and differ in the summation order of the weighted
    mean alone. A lane that drew from a key of its own would be off by a
    mask, not by an ulp."""
    def run(**kw):
        api = FedAvgAPI(cnn_ds, _cfg("cnn_dropout", comm_round=2, lr=0.01,
                                     **kw),
                        create_model("cnn_dropout", 4,
                                     input_shape=(12, 12, 1)))
        return api, [float(api.run_round(r)) for r in (1, 2)]

    api_lanes, l_lanes = run()
    assert api_lanes._packed_steps
    api_gather, l_gather = run(pack_lanes=0, bucket_quantum_batches=0)
    assert api_gather._gather_steps and not api_gather._packed_steps
    np.testing.assert_allclose(l_lanes, l_gather, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(api_lanes.variables),
                    jax.tree.leaves(api_gather.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_fedopt_packed_schedule_matches_plain(conv_ds, fedopt_off_run):
    """Hook-folding math, weight-level: FedOpt on the packed schedule
    (hooks at lane emit + post-aggregation server update) equals the
    plain unpacked path (FedOptAPI.aggregate) to float-sum tolerance —
    the two differ ONLY in summation order of the weighted mean: this
    pins the packed tail (apply_server_and_rollback + threaded server
    state) against the aggregate() source of truth."""
    api_off, l_off = fedopt_off_run
    cfg = _cfg("resnet20", pack_lanes=0, device_data="off",
               **FEDOPT_SGD_KW)
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


# -- 3. Silo per-client early exit as a masked lane freeze --------------------

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
