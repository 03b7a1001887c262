"""End-to-end FedAvg tests, including the reference CI's most important gate:
federated (full participation, full batch, 1 local epoch) == centralized
(CI-script-fedavg.sh:43-47) — an exact-math property of FedAvg."""

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.centralized import CentralizedTrainer
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.pytree import tree_global_norm, tree_sub
from fedml_tpu.data.synthetic import make_synthetic_classification, make_synthetic_lr
from fedml_tpu.models import create_model


def _tiny_dataset(batch_size=0, clients=4, dim=12, classes=3, seed=0):
    return make_synthetic_classification(
        "tiny", (dim,), classes, clients, records_per_client=10,
        partition_method="homo", batch_size=batch_size or 8, seed=seed,
    )


class TestEquivalence:
    def test_fedavg_full_participation_equals_centralized(self):
        ds = _tiny_dataset()
        n_pad = ds.train_x.shape[1]
        fed_cfg = FedConfig(
            model="lr", dataset="tiny", client_num_in_total=ds.num_clients,
            client_num_per_round=ds.num_clients, comm_round=3, epochs=1,
            batch_size=n_pad, lr=0.5, client_optimizer="sgd",
            frequency_of_the_test=1, seed=7,
        )
        bundle = create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:])
        fed = FedAvgAPI(ds, fed_cfg, bundle)
        fed.train()

        total = int(ds.train_counts.sum())
        cen_cfg = fed_cfg.replace(batch_size=total)
        bundle2 = create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:])
        cen = CentralizedTrainer(ds, cen_cfg, bundle2)
        cen.train()

        diff = float(tree_global_norm(tree_sub(fed.variables["params"], cen.variables["params"])))
        scale = float(tree_global_norm(cen.variables["params"]))
        assert diff / max(scale, 1e-9) < 1e-4, f"fed!=centralized: rel diff {diff/scale}"

    def test_fedavg_conv_full_participation_equals_centralized(self):
        """The strongest gate on a CONV architecture (a compensating gate
        for the flagship CIFAR parity that zero-egress cannot validate):
        exact because the cnn model is per-sample deterministic (no BN
        cross-batch coupling), so the weighted mean of per-client full-batch
        gradients IS the centralized full-batch gradient."""
        ds = make_synthetic_classification(
            "convq", (12, 12, 1), 3, 4, records_per_client=8,
            partition_method="homo", batch_size=8, seed=2,
        )
        n_pad = ds.train_x.shape[1]
        fed_cfg = FedConfig(
            model="cnn", dataset="convq", client_num_in_total=4,
            client_num_per_round=4, comm_round=2, epochs=1,
            batch_size=n_pad, lr=0.2, frequency_of_the_test=10, seed=5,
        )
        fed = FedAvgAPI(ds, fed_cfg,
                        create_model("cnn", ds.class_num,
                                     input_shape=ds.train_x.shape[2:]))
        fed.train()
        total = int(ds.train_counts.sum())
        cen = CentralizedTrainer(
            ds, fed_cfg.replace(batch_size=total),
            create_model("cnn", ds.class_num, input_shape=ds.train_x.shape[2:]))
        cen.train()
        diff = float(tree_global_norm(tree_sub(fed.variables["params"],
                                               cen.variables["params"])))
        scale = float(tree_global_norm(cen.variables["params"]))
        assert diff / max(scale, 1e-9) < 1e-4, f"conv fed!=centralized: {diff/scale}"

    def test_weighted_aggregation_respects_sample_counts(self):
        # clients with very different sizes must not contribute equally
        ds = _tiny_dataset()
        cfg = FedConfig(
            model="lr", client_num_in_total=ds.num_clients,
            client_num_per_round=ds.num_clients, comm_round=1, epochs=1,
            batch_size=ds.train_x.shape[1], lr=1.0, seed=0,
        )
        api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]))
        w0 = api.variables
        api.run_round(0)
        assert float(tree_global_norm(tree_sub(api.variables["params"], w0["params"]))) > 0


class TestConvergence:
    def test_synthetic_lr_learns(self):
        ds = make_synthetic_lr(1.0, 1.0, num_clients=20, dim=30, classes=5, batch_size=10, seed=1)
        cfg = FedConfig(
            model="lr", client_num_in_total=20, client_num_per_round=10,
            comm_round=40, epochs=4, batch_size=10, lr=0.3,
            frequency_of_the_test=10, seed=1,
        )
        api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]))
        hist = api.train()
        # LEAF synthetic(1,1) draws a DIFFERENT label model per client, so a
        # single global model plateaus well below 1.0; chance is 0.2.
        assert hist["Test/Acc"][-1] > 0.35, hist["Test/Acc"]
        assert hist["Test/Acc"][-1] > hist["Test/Acc"][0]

    def test_cnn_smoke(self):
        ds = make_synthetic_classification(
            "img", (28, 28, 1), 10, 4, records_per_client=16,
            partition_method="homo", batch_size=8, seed=0,
        )
        cfg = FedConfig(
            model="cnn", client_num_in_total=4, client_num_per_round=2,
            comm_round=2, epochs=1, batch_size=8, lr=0.05, seed=0,
            frequency_of_the_test=1,
        )
        api = FedAvgAPI(ds, cfg, create_model("cnn", 10))
        hist = api.train()
        assert np.isfinite(hist["Test/Loss"][-1])


class TestAsyncRounds:
    def test_async_rounds_match_sync(self):
        """config.async_rounds only defers the host sync — the trained
        variables must be identical to the synchronous path, and the
        returned loss must be a device scalar that floats to the same
        value."""
        ds = _tiny_dataset()
        kw = dict(model="lr", client_num_in_total=4, client_num_per_round=4,
                  comm_round=3, epochs=1, batch_size=8, lr=0.3, seed=9,
                  frequency_of_the_test=100)
        sync = FedAvgAPI(ds, FedConfig(**kw),
                         create_model("lr", ds.class_num,
                                      input_shape=ds.train_x.shape[2:]))
        asyn = FedAvgAPI(ds, FedConfig(async_rounds=True, **kw),
                         create_model("lr", ds.class_num,
                                      input_shape=ds.train_x.shape[2:]))
        for r in range(3):
            l_s = sync.run_round(r)
            l_a = asyn.run_round(r)
            assert isinstance(l_s, float)
            assert not isinstance(l_a, float)   # un-synced device scalar
            assert np.isclose(l_s, float(l_a), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(sync.variables),
                        jax.tree.leaves(asyn.variables)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSampling:
    @pytest.mark.parametrize("pack_lanes", [0, 2])
    def test_partial_participation_deterministic(self, pack_lanes):
        """Two runs of one config agree to the bit: on the host path, and on
        the packed lanes of the resident stack."""
        ds = _tiny_dataset()
        cfg = FedConfig(
            model="lr", client_num_in_total=4, client_num_per_round=2,
            comm_round=2, epochs=1, batch_size=8, lr=0.1, seed=3,
            pack_lanes=pack_lanes, device_data="on" if pack_lanes else "auto",
        )
        a = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]))
        b = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]))
        assert a._path == ("packed" if pack_lanes else "host")
        a.train(); b.train()
        d = float(tree_global_norm(tree_sub(a.variables["params"], b.variables["params"])))
        assert d == 0.0


class TestDeviceResidentData:
    """The device-resident gather path (config.device_data) must produce
    bit-identical rounds to the host-slice path — same gather, same RNG,
    only the residency of the stacked arrays differs."""

    def test_gather_path_matches_host_path(self):
        ds = make_synthetic_classification(
            "tiny-dev", (6,), 3, 6, records_per_client=12,
            partition_method="hetero", partition_alpha=0.5, batch_size=4, seed=3,
        )
        kw = dict(
            model="lr", dataset="tiny-dev", client_num_in_total=ds.num_clients,
            client_num_per_round=3, comm_round=4, epochs=2, batch_size=4,
            lr=0.3, momentum=0.9, frequency_of_the_test=100, seed=11,
        )
        on = FedAvgAPI(ds, FedConfig(device_data="on", **kw))
        off = FedAvgAPI(ds, FedConfig(device_data="off", **kw))
        assert on._dev_train is not None
        assert off._dev_train is None
        for r in range(4):
            l_on = on.run_round(r)
            l_off = off.run_round(r)
            assert np.isclose(l_on, l_off, rtol=1e-6), (r, l_on, l_off)
        for a, b in zip(
            jax.tree.leaves(on.variables), jax.tree.leaves(off.variables)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    def test_auto_respects_budget_and_platform(self):
        ds = _tiny_dataset()
        kw = dict(
            model="lr", dataset="tiny", client_num_in_total=ds.num_clients,
            client_num_per_round=2, comm_round=1, batch_size=8, lr=0.1, seed=0,
        )
        auto = FedAvgAPI(ds, FedConfig(device_data="auto", **kw))
        if jax.default_backend() == "cpu":
            # no transfer to avoid on CPU: auto declines the duplicate copy
            assert auto._dev_train is None
        else:
            assert auto._dev_train is not None
        forced = FedAvgAPI(ds, FedConfig(device_data="on", **kw))
        assert forced._dev_train is not None  # 'on' overrides the heuristic
        capped = FedAvgAPI(
            ds, FedConfig(device_data="on", device_data_max_bytes=1, **kw)
        )
        assert capped._dev_train is not None  # budget only gates 'auto'


class TestCohortBucketing:
    """bucket_quantum_batches: per-round scan truncation to the live cohort's
    max real count (dead padded SGD steps are pure waste under hetero/LDA
    partitions where global n_pad is set by the single biggest client)."""

    def _ragged_ds(self):
        # client sizes 6,6,6,30 with bs 2 -> n_pad 30; quantum 1 batch = 2
        rng = np.random.default_rng(3)
        w_true = rng.normal(0, 1, (6, 3))
        xs = [rng.normal(0, 1, (n, 6)).astype(np.float32) for n in (6, 6, 6, 30)]
        ys = [np.argmax(x @ w_true, axis=1).astype(np.int32) for x in xs]
        from fedml_tpu.data import FedDataset
        from fedml_tpu.data.batching import pad_and_stack_clients, pad_eval_pool

        tx, ty, tm, tc = pad_and_stack_clients(xs, ys, 2)
        ex, ey, em = pad_eval_pool(np.concatenate(xs), np.concatenate(ys), 8)
        return FedDataset(train_x=tx, train_y=ty, train_mask=tm, train_counts=tc,
                          test_x=ex, test_y=ey, test_mask=em, class_num=3,
                          name="ragged")

    def _cfg(self, **kw):
        kw.setdefault("comm_round", 4)
        return FedConfig(model="lr", client_num_in_total=4, client_num_per_round=3,
                         batch_size=2, lr=0.3, frequency_of_the_test=100, **kw)

    def test_round_bucket_math(self):
        ds = self._ragged_ds()
        api = FedAvgAPI(ds, self._cfg(bucket_quantum_batches=1),
                        create_model("lr", 3, input_shape=(6,)))
        # cohort of small clients: bucket = ceil(6/2)*2 = 6
        assert api._round_bucket(np.array([0, 1, 2]), None) == 6
        # the big client drags the bucket to n_pad -> None (nothing to trim)
        assert api._round_bucket(np.array([0, 3]), None) is None
        # failure-masked big client doesn't inflate the bucket
        assert api._round_bucket(np.array([0, 3]), np.array([1.0, 0.0])) == 6
        # quantum 0 disables
        api0 = FedAvgAPI(ds, self._cfg(bucket_quantum_batches=0),
                         create_model("lr", 3, input_shape=(6,)))
        assert api0._round_bucket(np.array([0, 1]), None) is None

    def test_bucketed_training_converges_host_path(self):
        ds = self._ragged_ds()
        api = FedAvgAPI(ds, self._cfg(bucket_quantum_batches=1, comm_round=25),
                        create_model("lr", 3, input_shape=(6,)))
        hist = api.train()
        assert hist["Test/Acc"][-1] > 0.5

    def test_bucketed_gather_path_matches_quality(self):
        # device_data='on' forces the resident-gather path even on CPU
        ds = self._ragged_ds()
        api = FedAvgAPI(ds, self._cfg(bucket_quantum_batches=1, comm_round=25,
                                      device_data="on"),
                        create_model("lr", 3, input_shape=(6,)))
        assert api._dev_train is not None
        hist = api.train()
        assert api._gather_steps, "bucketed rounds should compile bucket programs"
        buckets = [b for b in api._gather_steps if b is not None]  # None: full
        assert buckets and all(b % 2 == 0 and b < ds.train_x.shape[1]
                               for b in buckets)
        assert hist["Test/Acc"][-1] > 0.5


@pytest.mark.parametrize("dataset", ["synthetic_1_1", "synthetic_0_0",
                                     "synthetic_0.5_0.5"])
def test_reference_synthetic_benchmark_parity(dataset):
    """Reference headline benchmark (BASELINE.md / benchmark/README.md:14):
    Synthetic(alpha,beta)+LR FedAvg reaches top-1 > 60 with 30 clients,
    10/round, bs 10, SGD lr 0.01, E=1, >200 rounds — for ALL THREE published
    (alpha,beta) settings: (0,0), (0.5,0.5), (1,1). Reproduced here with
    the LEAF-recipe generator at the reference's exact hyperparameters."""
    from fedml_tpu.data import load_dataset

    ds = load_dataset(dataset, num_clients=30, batch_size=10)
    cfg = FedConfig(model="lr", client_num_in_total=30, client_num_per_round=10,
                    comm_round=220, batch_size=10, lr=0.01, epochs=1,
                    frequency_of_the_test=40)
    api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num,
                                          input_shape=ds.train_x.shape[2:]))
    hist = api.train()
    assert hist["Test/Acc"][-1] > 0.60, (dataset, hist["Test/Acc"])


def test_scan_unroll_is_exact():
    """scan_unroll only changes XLA scheduling (fused adjacent steps), never
    the update sequence: rounds must be identical to the rolled loop."""
    import jax

    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model

    ds = make_synthetic_classification(
        "unroll", (10,), 3, 4, records_per_client=21,
        partition_method="hetero", partition_alpha=0.5, batch_size=4, seed=2)

    def run(unroll):
        cfg = FedConfig(model="lr", client_num_in_total=4,
                        client_num_per_round=4, comm_round=2, epochs=2,
                        batch_size=4, lr=0.2, momentum=0.9, seed=3,
                        frequency_of_the_test=100, scan_unroll=unroll)
        api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num,
                                              input_shape=(10,)))
        losses = [float(api.run_round(r)) for r in range(2)]
        return api, losses

    base, l1 = run(1)
    unrolled, l4 = run(4)
    assert l1 == pytest.approx(l4, rel=1e-6)
    for a, b in zip(jax.tree.leaves(base.variables),
                    jax.tree.leaves(unrolled.variables)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-7)


def test_cohort_vmap_width_is_exact():
    """cohort_vmap_width only reorders independent client programs (lax.map
    over vmapped chunks vs one full vmap): per-round losses and final
    variables must match the full-vmap schedule."""
    import jax

    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model

    ds = make_synthetic_classification(
        "cohortw", (10,), 3, 8, records_per_client=21,
        partition_method="hetero", partition_alpha=0.5, batch_size=4, seed=2)

    def run(width):
        cfg = FedConfig(model="lr", client_num_in_total=8,
                        client_num_per_round=8, comm_round=2, epochs=1,
                        batch_size=4, lr=0.2, momentum=0.9, seed=3,
                        frequency_of_the_test=100, cohort_vmap_width=width,
                        device_data="off")
        api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num,
                                              input_shape=(10,)))
        losses = [float(api.run_round(r)) for r in range(2)]
        return api, losses

    base, l0 = run(0)
    for width in (1, 2):
        chunked, lw = run(width)
        assert l0 == pytest.approx(lw, rel=1e-6), width
        for a, b in zip(jax.tree.leaves(base.variables),
                        jax.tree.leaves(chunked.variables)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-6, atol=1e-7)
