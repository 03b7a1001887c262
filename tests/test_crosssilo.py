"""Cross-silo paradigm tests on the virtual 8-device CPU mesh: the sharded
round must produce numerically the same result as the single-device vmap
simulation (same math, different placement)."""

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI, FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.pytree import tree_global_norm, tree_sub
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.parallel.mesh import client_mesh, hierarchical_mesh


def _ds(clients=8, dim=10, classes=4):
    return make_synthetic_classification(
        "xsilo", (dim,), classes, clients, records_per_client=12,
        partition_method="homo", batch_size=6, seed=0,
    )


class TestCrossSilo:
    def test_matches_simulation(self):
        ds = _ds(8)
        cfg = FedConfig(
            model="lr", client_num_in_total=8, client_num_per_round=8,
            comm_round=3, epochs=1, batch_size=6, lr=0.2, seed=5,
            frequency_of_the_test=10,
        )
        sim = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]))
        dist = CrossSiloFedAvgAPI(
            ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
            mesh=client_mesh(8),
        )
        sim.train()
        dist.train()
        d = float(tree_global_norm(tree_sub(sim.variables["params"], dist.variables["params"])))
        s = float(tree_global_norm(sim.variables["params"]))
        assert d / max(s, 1e-9) < 1e-5, d / s

    def test_multiple_clients_per_device(self):
        ds = _ds(16)
        cfg = FedConfig(
            model="lr", client_num_in_total=16, client_num_per_round=16,
            comm_round=2, epochs=1, batch_size=6, lr=0.2, seed=5,
        )
        dist = CrossSiloFedAvgAPI(
            ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
            mesh=client_mesh(4),
        )
        hist = dist.train()
        assert np.isfinite(hist["Test/Loss"][-1])

    def test_cohort_mesh_mismatch_raises(self):
        ds = _ds(8)
        cfg = FedConfig(
            model="lr", client_num_in_total=8, client_num_per_round=6,
            comm_round=1, batch_size=6, lr=0.1,
        )
        try:
            CrossSiloFedAvgAPI(ds, cfg, create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
                               mesh=client_mesh(4))
            raise AssertionError("expected ValueError")
        except ValueError as e:
            assert "multiple of the mesh 'clients' axis" in str(e)


class TestMeshHelpers:
    def test_hierarchical_mesh_axes(self):
        m = hierarchical_mesh(2, 4)
        assert m.axis_names == ("group", "clients")
        assert m.devices.shape == (2, 4)


class TestHierarchicalMesh:
    """Distributed hierarchical FL on a 2-D ('group','clients') mesh must
    equal the single-device vmap simulator (HierarchicalFedAvgAPI): group
    psum over the client axis == segment_sum per group, global reduce over
    the group axis == weighted mean of group models."""

    def test_mesh_hierarchical_matches_simulator(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvgAPI
        from fedml_tpu.core.config import FedConfig
        from fedml_tpu.data.synthetic import make_synthetic_classification
        from fedml_tpu.parallel.crosssilo import make_hierarchical_round
        from fedml_tpu.parallel.mesh import hierarchical_mesh, replicated

        G, CPG = 2, 4           # 2 groups x 4 clients = 8 devices
        C = G * CPG
        GR = 3                  # group rounds per global round
        ds = make_synthetic_classification(
            "hier-mesh", (6,), 3, C, records_per_client=8,
            partition_method="homo", batch_size=4, seed=5,
        )
        cfg = FedConfig(
            model="lr", dataset="hier-mesh", client_num_in_total=C,
            client_num_per_round=C, comm_round=1, batch_size=4, epochs=1,
            lr=0.3, group_num=G, group_comm_round=GR, seed=17,
            frequency_of_the_test=100,
        )
        sim = HierarchicalFedAvgAPI(ds, cfg)
        sampled = np.arange(C)
        cx, cy, cm, counts = ds.client_slice(sampled)
        counts = np.asarray(counts, np.float32)
        rk = jax.random.fold_in(sim.root_key, 9)
        sim_vars, _, sim_loss = sim._round_step(
            sim.variables, sim.server_state, cx, cy, cm, jnp.asarray(counts), rk)

        # mesh version: row g holds clients {j*G+g} (simulator gid = i % G);
        # per-client keys replicate the simulator's split exactly
        mesh = hierarchical_mesh(G, CPG)
        order = np.array([[j * G + g for j in range(CPG)] for g in range(G)])
        mx = jnp.asarray(cx[order.ravel()]).reshape((G, CPG) + cx.shape[1:])
        my = jnp.asarray(cy[order.ravel()]).reshape((G, CPG) + cy.shape[1:])
        mm = jnp.asarray(cm[order.ravel()]).reshape((G, CPG) + cm.shape[1:])
        mcounts = jnp.asarray(counts[order.ravel()]).reshape((G, CPG))
        gr_keys = jax.random.split(rk, GR)
        keys = jnp.stack([
            jax.random.split(k, C)[order.ravel()].reshape((G, CPG))
            for k in gr_keys
        ])
        round_fn = make_hierarchical_round(sim._local_train, mesh, group_rounds=GR)
        variables = jax.device_put(sim.bundle.init(sim.root_key), replicated(mesh))
        mesh_vars, mesh_loss = round_fn(variables, mx, my, mm, mcounts, keys)

        assert np.isclose(float(sim_loss), float(mesh_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(sim_vars), jax.tree.leaves(mesh_vars)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


class TestCrossSiloResidentData:
    """Full-participation cross-silo with device_data='on' keeps the
    dataset sharded-resident; rounds must be bit-identical to the
    per-round host-slice path."""

    def test_resident_sharded_matches_host_path(self):
        import jax
        import numpy as np

        from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
        from fedml_tpu.core.config import FedConfig
        from fedml_tpu.data.synthetic import make_synthetic_classification
        from fedml_tpu.parallel.mesh import client_mesh

        C = 8
        ds = make_synthetic_classification(
            "silo-res", (6,), 3, C, records_per_client=8,
            partition_method="homo", batch_size=4, seed=3,
        )
        kw = dict(
            model="lr", dataset="silo-res", client_num_in_total=C,
            client_num_per_round=C, comm_round=3, batch_size=4, epochs=1,
            lr=0.3, seed=23, frequency_of_the_test=100,
        )
        mesh = client_mesh(8)
        on = CrossSiloFedAvgAPI(ds, FedConfig(device_data="on", **kw), mesh=mesh)
        off = CrossSiloFedAvgAPI(ds, FedConfig(device_data="off", **kw), mesh=mesh)
        assert on._dev_sharded is not None
        assert off._dev_sharded is None
        for r in range(3):
            l_on = on.run_round(r)
            l_off = off.run_round(r)
            assert np.isclose(l_on, l_off, rtol=1e-6), (r, l_on, l_off)
        for a, b in zip(jax.tree.leaves(on.variables), jax.tree.leaves(off.variables)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7)

    def test_partial_participation_declines_with_warning(self, caplog):
        import logging as _logging

        from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
        from fedml_tpu.core.config import FedConfig
        from fedml_tpu.data.synthetic import make_synthetic_classification
        from fedml_tpu.parallel.mesh import client_mesh

        ds = make_synthetic_classification(
            "silo-part", (6,), 3, 16, records_per_client=8,
            partition_method="homo", batch_size=4, seed=3,
        )
        cfg = FedConfig(
            model="lr", dataset="silo-part", client_num_in_total=16,
            client_num_per_round=8, comm_round=1, batch_size=4,
            lr=0.3, seed=2, device_data="on",
        )
        with caplog.at_level(_logging.WARNING):
            api = CrossSiloFedAvgAPI(ds, cfg, mesh=client_mesh(8))
        assert api._dev_sharded is None
        assert any("partial" in r.message for r in caplog.records)
