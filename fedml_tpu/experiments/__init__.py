"""Experiment entry points (L5).

Counterpart of reference fedml_experiments/: per-algorithm argparse mains
(standalone/distributed/centralized trees) plus the unified ``fed_launch``
launcher (fedml_experiments/distributed/fed_launch/main.py:52-68). Here one
dispatcher serves every algorithm; the per-algorithm ``main_*`` modules are
thin aliases, so ``python -m fedml_tpu.experiments.main_fedavg --dataset
mnist --model lr`` mirrors the reference's invocation shape 1:1 while
``python -m fedml_tpu.experiments.run --algorithm X`` is the fed_launch
form. The --ci fast path shrinks rounds/epochs like the reference CI
scripts (CI-script-fedavg.sh:34-38).
"""

from __future__ import annotations

import json
import logging

from fedml_tpu.core.config import FedConfig

log = logging.getLogger(__name__)

ALGORITHMS = (
    "fedavg", "crosssilo_fedavg", "fedopt", "fedprox", "fednova", "fedagc",
    "fedavg_robust", "hierarchical", "decentralized", "turboaggregate",
    "fedgkt", "fednas", "fedseg", "splitnn", "vfl", "centralized",
    "silo_fedavg", "silo_fedopt", "silo_fednova", "silo_fedagc",
    "crosssilo_fedopt", "crosssilo_fednova", "crosssilo_fedagc",
    "crosssilo_fedavg_robust", "crosssilo_fedprox", "crosssilo_decentralized",
    "crosssilo_fedseg", "crosssilo_hierarchical", "crosssilo_fednas",
    "streaming_fedavg", "fedavg_edge",
)


def _bundle_for(config: FedConfig, ds):
    import jax.numpy as jnp

    from fedml_tpu.models import create_model

    # the module computes in the config's dtype (--dtype bfloat16 builds the
    # bf16 module, not an f32 module fed bf16 batches); factories without a
    # dtype knob swallow the keyword
    return create_model(
        config.model, ds.class_num,
        input_shape=ds.train_x.shape[2:] or None,
        dtype=jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32,
    )


def _load(config: FedConfig):
    from fedml_tpu.data import load_dataset

    # loader parameter names vary (client_num_in_total vs num_clients);
    # every loader ignores unknown kwargs, so pass both spellings
    return load_dataset(
        config.dataset,
        data_dir=config.data_dir,
        client_num_in_total=config.client_num_in_total,
        num_clients=config.client_num_in_total,
        partition_method=config.partition_method,
        partition_alpha=config.partition_alpha,
        batch_size=config.batch_size,
        seed=config.seed,
    )


def run_experiment(config: FedConfig, algorithm: str) -> dict:
    """Build data + model + API for `algorithm`, run it, return its final
    history/metrics dict (also JSON-logged, wandb-style keys). On
    successful completion, signals any sweep orchestrator listening on
    FEDML_SWEEP_PIPE (reference fedavg/utils.py:19-26 posts the same from
    the server manager at end of run) — exactly once per experiment."""
    result = _run_experiment(config, algorithm)
    from fedml_tpu.utils.metrics import notify_sweep_complete

    notify_sweep_complete()
    return result


def _run_experiment(config: FedConfig, algorithm: str) -> dict:
    algorithm = algorithm.lower()
    if algorithm not in ALGORITHMS:
        raise KeyError(f"unknown algorithm {algorithm!r}; known: {ALGORITHMS}")
    if config.rank is not None and algorithm != "fedavg_edge":
        # silently running the full single-process simulation on N machines
        # would be N-fold redundant work and no federation at all
        raise ValueError(
            "--rank/--world_size start one process of a multi-process "
            "deployment, which only the fedavg_edge algorithm supports "
            f"(got --algorithm {algorithm})"
        )

    if algorithm == "vfl":
        from fedml_tpu.algorithms.vfl import VFLAPI
        from fedml_tpu.data.vertical import (
            load_lending_club, load_nus_wide, load_uci_credit,
            make_synthetic_vertical,
        )

        loaders = {
            "lending_club": load_lending_club,
            "nus_wide": load_nus_wide,
            "uci_credit": load_uci_credit,
        }
        vds = loaders.get(
            config.dataset,
            lambda d, seed=0, **_: make_synthetic_vertical(seed=seed),
        )(config.data_dir, seed=config.seed)
        api = VFLAPI(vds, lr=config.lr, batch_size=config.batch_size, seed=config.seed)
        result = api.fit(epochs=config.comm_round, seed=config.seed)
        log.info("result %s", json.dumps(result))
        return result

    ds = _load(config)

    if algorithm == "fedavg_edge":
        # the message-driven deployment (reference mpirun path): 1 server +
        # N workers over the in-process router, or real gRPC loopback with
        # --backend grpc — with optional payload compression (--wire_codec)
        # and error-feedback delta uploads (--wire_delta)
        from fedml_tpu.distributed.fedavg_edge import run_fedavg_edge

        if config.rank is not None:
            # TRUE multi-process deployment: this process is ONE rank of a
            # gRPC federation (reference: mpirun starts N processes, each
            # branching on its rank — FedAvgAPI.py:20-28). Start it with
            # experiments.launch_edge or by hand on each machine.
            from fedml_tpu.distributed.fedavg_edge import run_fedavg_edge_rank

            agg = run_fedavg_edge_rank(ds, config)
            if agg is None:       # worker rank: nothing to report
                return {"rank": config.rank, "role": "worker"}
            hist = agg.test_history
            return {"rank": 0, "role": "server",
                    "round": [h["round"] for h in hist],
                    "Test/Acc": [h["acc"] for h in hist],
                    "Test/Loss": [h["loss"] for h in hist]}

        workers = min(config.client_num_per_round, ds.num_clients)
        if config.backend.lower() == "grpc":
            import socket

            from fedml_tpu.comm.grpc_backend import GRPCCommManager

            # an ephemeral-port probe only suggests a free BLOCK base; the
            # block can be raced before the ranks bind, so retry with a
            # fresh base on bind failure (run_ranks tears down partial
            # setups, so a retry starts clean)
            last_err = None
            for _ in range(3):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    base = s.getsockname()[1]
                try:
                    agg = run_fedavg_edge(
                        ds, config, worker_num=workers,
                        comm_factory=lambda r: GRPCCommManager(
                            r, workers + 1, base_port=base, host="127.0.0.1",
                            codec=config.wire_codec))
                    break
                except OSError as e:
                    last_err = e
            else:
                raise last_err
        else:
            agg = run_fedavg_edge(ds, config, worker_num=workers)
        hist = agg.test_history
        result = {"round": [h["round"] for h in hist],
                  "Test/Acc": [h["acc"] for h in hist],
                  "Test/Loss": [h["loss"] for h in hist]}
        log.info("result %s", json.dumps({"rounds": len(hist)}))
        return result

    if algorithm == "fedgkt":
        from fedml_tpu.algorithms.fedgkt import FedGKTAPI

        from fedml_tpu.models.gkt import gkt_blocks_from_names

        blocks = (1, 2) if config.ci else gkt_blocks_from_names(
            config.model_client, config.model_server)
        # multi-chip: shard the server phase over all chips (the reference
        # auto-uses nn.DataParallel when GPUs allow, GKTServerTrainer.py:28-29).
        # Auto only on real accelerators — GSPMD-partitioning the server scan
        # is a large compile that virtual CPU meshes pay for with no speedup
        # (pass server_mesh explicitly to FedGKTAPI to force it anywhere).
        server_mesh = None
        import jax as _jax
        n_dev = len(_jax.devices())
        if (n_dev > 1 and ds.num_clients % n_dev == 0
                and _jax.default_backend() != "cpu"):
            from fedml_tpu.parallel.dataparallel import batch_mesh

            server_mesh = batch_mesh(n_dev)
        api = FedGKTAPI(ds, config, client_blocks=blocks[0],
                        server_blocks_per_stage=blocks[1],
                        server_mesh=server_mesh)
        return api.train()
    if algorithm in ("fednas", "crosssilo_fednas"):
        from fedml_tpu.algorithms.fednas import CrossSiloFedNASAPI, FedNASAPI

        size = dict(channels=4, layers=2, steps=2, multiplier=2) if config.ci \
            else dict(channels=16, layers=8, steps=4, multiplier=4)
        cls = CrossSiloFedNASAPI if algorithm == "crosssilo_fednas" else FedNASAPI
        return cls(ds, config, **size).train()
    if algorithm == "splitnn":
        from fedml_tpu.algorithms.split_nn import SplitNNAPI
        from fedml_tpu.models.split import create_split_cnn, create_split_mlp

        if len(ds.train_x.shape) == 5:  # [C, n, H, W, ch] image data
            cb, sb = create_split_cnn(ds.class_num, input_shape=ds.train_x.shape[2:])
        else:
            cb, sb = create_split_mlp(ds.class_num, input_shape=ds.train_x.shape[2:])
        return SplitNNAPI(ds, config, cb, sb).train()

    from fedml_tpu.algorithms.centralized import CentralizedTrainer
    from fedml_tpu.algorithms.decentralized import (
        DecentralizedFedAPI, MeshDecentralizedFedAPI,
    )
    from fedml_tpu.algorithms.fedagc import CrossSiloFedAGCAPI, FedAGCAPI
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI, FedAvgAPI
    from fedml_tpu.algorithms.fednova import CrossSiloFedNovaAPI, FedNovaAPI
    from fedml_tpu.algorithms.fedopt import CrossSiloFedOptAPI, FedOptAPI
    from fedml_tpu.algorithms.fedprox import CrossSiloFedProxAPI, FedProxAPI
    from fedml_tpu.algorithms.fedseg import CrossSiloFedSegAPI, FedSegAPI
    from fedml_tpu.algorithms.hierarchical import (
        CrossSiloHierarchicalFedAvgAPI, HierarchicalFedAvgAPI,
    )
    from fedml_tpu.algorithms.robust import CrossSiloFedAvgRobustAPI, FedAvgRobustAPI
    from fedml_tpu.algorithms.silo import SiloRunner
    from fedml_tpu.algorithms.streaming_fedavg import StreamingFedAvgAPI
    from fedml_tpu.algorithms.turboaggregate import TurboAggregateAPI

    simple = {
        "fedavg": FedAvgAPI,
        "streaming_fedavg": StreamingFedAvgAPI,
        "crosssilo_fedavg": CrossSiloFedAvgAPI,
        "crosssilo_fedopt": CrossSiloFedOptAPI,
        "crosssilo_fednova": CrossSiloFedNovaAPI,
        "crosssilo_fedagc": CrossSiloFedAGCAPI,
        "crosssilo_fedavg_robust": CrossSiloFedAvgRobustAPI,
        "crosssilo_fedprox": CrossSiloFedProxAPI,
        "fedopt": FedOptAPI,
        "fedprox": FedProxAPI,
        "fednova": FedNovaAPI,
        "fedagc": FedAGCAPI,
        "fedavg_robust": FedAvgRobustAPI,
        "hierarchical": HierarchicalFedAvgAPI,
        "crosssilo_hierarchical": CrossSiloHierarchicalFedAvgAPI,
        "decentralized": DecentralizedFedAPI,
        "crosssilo_decentralized": MeshDecentralizedFedAPI,
        "turboaggregate": TurboAggregateAPI,
        "fedseg": FedSegAPI,
        "crosssilo_fedseg": CrossSiloFedSegAPI,
        "centralized": CentralizedTrainer,
    }
    bundle = _bundle_for(config, ds)
    if algorithm in simple:
        result = simple[algorithm](ds, config, bundle).train()
    elif algorithm.startswith("silo_"):
        silo_cls = {
            "silo_fedavg": FedAvgAPI,
            "silo_fedopt": FedOptAPI,
            "silo_fednova": FedNovaAPI,
            "silo_fedagc": FedAGCAPI,
        }[algorithm]
        result = SiloRunner(ds, config, api_cls=silo_cls, bundle=bundle).train()
    else:  # pragma: no cover
        raise AssertionError(algorithm)
    log.info("result %s", json.dumps({k: v for k, v in dict(result).items()
                                      if isinstance(v, (int, float, str))}))
    return result
