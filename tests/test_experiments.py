"""Experiment-launcher tests (reference fedml_experiments/ + fed_launch).

Smoke the unified dispatcher over a spread of algorithms with --ci sized
configs — the reference's CI strategy (CI-script-fedavg.sh:34-38) of tiny
real runs through the actual entry points.
"""

import json

import pytest

from fedml_tpu.core.config import FedConfig
from fedml_tpu.experiments import run_experiment
from fedml_tpu.experiments.run import main


def _argv(algorithm, **over):
    base = {
        "--dataset": "synthetic_1_1", "--model": "lr", "--comm_round": "2",
        "--epochs": "1", "--client_num_in_total": "6",
        "--client_num_per_round": "6", "--batch_size": "10", "--lr": "0.3",
        "--frequency_of_the_test": "1", "--ci": "1",
    }
    base.update({f"--{k}": str(v) for k, v in over.items()})
    out = ["--algorithm", algorithm]
    for k, v in base.items():
        out += [k, v]
    return out


@pytest.mark.parametrize("algo", ["fedavg", "fedopt", "fedprox", "fednova",
                                  "centralized", "turboaggregate"])
def test_launcher_lr_family(algo, capsys):
    main(_argv(algo))
    line = capsys.readouterr().out.strip().splitlines()[-1]
    blob = json.loads(line)
    assert blob["algorithm"] == algo


def test_launcher_vfl(capsys):
    main(_argv("vfl", dataset="lending_club", comm_round="3", batch_size="32"))
    blob = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "Test/Acc" in blob and blob["Test/Acc"] > 0.5


@pytest.mark.slow  # 59 s: two-model GKT protocol run (tier-1 tail, ISSUE 6)
def test_launcher_fedgkt():
    cfg = FedConfig(
        model="lr", dataset="synthetic_1_1", client_num_in_total=2,
        client_num_per_round=2, comm_round=2, epochs=1, batch_size=10,
        lr=0.05, ci=1, frequency_of_the_test=1,
    )
    # GKT needs image data; dispatcher handles dataset choice — use cifar
    cfg = cfg.replace(dataset="cifar10", batch_size=8)
    out = run_experiment(cfg, "fedgkt")
    assert "Test/Acc" in out


def test_launcher_rejects_unknown():
    with pytest.raises(KeyError):
        run_experiment(FedConfig(), "not_an_algorithm")


@pytest.mark.parametrize("algo", ["fedagc", "fedavg_robust", "hierarchical",
                                  "decentralized", "silo_fedavg", "silo_fedopt",
                                  "silo_fednova", "silo_fedagc"])
def test_dispatcher_covers_remaining_standalone_algorithms(algo):
    """Every remaining --algorithm value must wire through the unified
    dispatcher end-to-end (tiny --ci configs, reference CI strategy)."""
    kw = {}
    if algo == "hierarchical":
        kw = dict(group_num="2", group_comm_round="1")
    out = main(_argv(algo, **kw))
    assert isinstance(out, dict) and out


@pytest.mark.parametrize("algo", ["crosssilo_fedavg", "crosssilo_fedopt",
                                  "crosssilo_fednova", "crosssilo_fedagc",
                                  "crosssilo_fedavg_robust", "crosssilo_fedprox",
                                  "crosssilo_decentralized"])
def test_dispatcher_covers_crosssilo(algo):
    # 8 virtual devices; full participation, cohort == mesh size
    out = main(_argv(algo, client_num_in_total="8",
                     client_num_per_round="8"))
    assert isinstance(out, dict) and out


@pytest.mark.slow  # 244 s: structured-mesh zoo compiles (tier-1 tail, ISSUE 6)
def test_dispatcher_covers_crosssilo_structured():
    """The structured mesh algorithms (VERDICT r2 #5) drive through the
    unified dispatcher end-to-end on the 8-device virtual mesh (the cohort
    must fill the default client_mesh(), so 8 silos; one round — the smoke
    is the dispatcher wiring + SPMD compile, not convergence)."""
    out = main(_argv("crosssilo_hierarchical", client_num_in_total="8",
                     client_num_per_round="8", group_num="2",
                     group_comm_round="1", comm_round="1"))
    assert isinstance(out, dict) and out
    out = main(_argv("crosssilo_fedseg", dataset="pascal_voc",
                     model="deeplab_lite", client_num_in_total="8",
                     client_num_per_round="8", batch_size="2",
                     comm_round="1"))
    assert isinstance(out, dict) and out
    out = main(_argv("crosssilo_fednas", dataset="cifar10",
                     client_num_in_total="8", client_num_per_round="8",
                     batch_size="4", comm_round="1"))
    assert isinstance(out, dict) and out


def test_dispatcher_covers_fedavg_edge():
    """The message-driven deployment is reachable from the launcher, with
    payload compression + delta uploads on."""
    out = main(_argv("fedavg_edge", dataset="synthetic_1_1",
                     client_num_in_total="4", client_num_per_round="2",
                     batch_size="10", comm_round="2",
                     wire_codec="q8", wire_delta="1"))
    assert isinstance(out, dict) and out["Test/Acc"]


def test_dispatcher_covers_splitnn():
    out = main(_argv("splitnn", dataset="mnist", model="cnn",
                     client_num_in_total="2", client_num_per_round="2",
                     batch_size="4"))
    assert isinstance(out, dict) and out


@pytest.mark.slow  # 100 s: DARTS search + fedseg runs (tier-1 tail, ISSUE 6)
def test_dispatcher_covers_fednas_and_fedseg_and_nothing_is_missed():
    """Close the loop on 'every algorithm drives through the dispatcher':
    fednas + fedseg smoke here, and a completeness assertion derived from
    the ALGORITHMS registry so a future addition cannot silently go
    untested."""
    from fedml_tpu.experiments import ALGORITHMS

    out = main(_argv("fednas", dataset="cifar10",
                     client_num_in_total="2", client_num_per_round="2",
                     batch_size="4", comm_round="1"))
    assert isinstance(out, dict) and out
    out = main(_argv("fedseg", dataset="pascal_voc", model="deeplab_lite",
                     client_num_in_total="2", client_num_per_round="2",
                     batch_size="2", comm_round="1"))
    assert isinstance(out, dict) and out

    covered = {
        # test_dispatcher_smoke parametrize
        "fedavg", "fedopt", "fedprox", "fednova", "centralized",
        "turboaggregate",
        # dedicated launcher tests in this file
        "vfl", "fedgkt", "crosssilo_fedavg", "crosssilo_fedopt",
        "crosssilo_fednova", "crosssilo_fedagc", "crosssilo_fedavg_robust",
        "crosssilo_fedprox", "crosssilo_decentralized", "crosssilo_fedseg",
        "crosssilo_hierarchical", "crosssilo_fednas", "splitnn", "fednas",
        "fedseg", "fedavg_edge",
        # dedicated test module: tests/test_streaming_fedavg.py
        "streaming_fedavg",
        # remaining-standalone parametrize
        "fedagc", "fedavg_robust", "hierarchical", "decentralized",
        "silo_fedavg", "silo_fedopt", "silo_fednova", "silo_fedagc",
    }
    assert set(ALGORITHMS) == covered, (
        f"dispatcher tests out of sync with ALGORITHMS: "
        f"missing={set(ALGORITHMS) - covered} stale={covered - set(ALGORITHMS)}"
    )


def test_every_algorithm_has_a_main_alias():
    """Reference parity: one main per algorithm dir (fedml_experiments/).
    Each alias module must exist, import, and default to its algorithm."""
    import importlib
    import pathlib

    import fedml_tpu.experiments
    from fedml_tpu.experiments import ALGORITHMS

    exp_dir = pathlib.Path(fedml_tpu.experiments.__file__).parent
    mains = {p.stem.removeprefix("main_")
             for p in exp_dir.glob("main_*.py")}
    # data-loader aliases and silo variants route through their base main
    expected = {a for a in ALGORITHMS
                if a not in {"lending_club", "nus_wide", "uci_credit"}
                and not a.startswith(("silo_", "crosssilo_"))}
    missing = expected - mains
    assert not missing, f"algorithms without a main_*.py alias: {missing}"
    for m in sorted(mains):
        mod = importlib.import_module(f"fedml_tpu.experiments.main_{m}")
        assert hasattr(mod, "main")


@pytest.mark.slow  # ~27 s: full bench.py tiny run; the committed BENCH_r*
#                    artifacts + test_bench_report pin the contract in-budget
def test_bench_tiny_smoke(monkeypatch, capsys):
    """bench.py is the driver's per-round artifact — its tiny CPU smoke must
    emit one JSON line with the contract keys (metric/value/unit/vs_baseline)."""
    import bench

    monkeypatch.setenv("BENCH_SCALE", "tiny")
    monkeypatch.setenv("BENCH_MODEL", "lr")
    bench.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert {"metric", "value", "unit", "vs_baseline",
            "model_flops_per_image", "mfu"} <= set(out)
    assert out["value"] > 0
    # XLA cost-model FLOP accounting must be live (mfu itself is None off-TPU)
    assert out["model_flops_per_image"] and out["model_flops_per_image"] > 0
    # fedcost roofline block (ISSUE 6): the tail must carry the per-program
    # static lane table — a silently-failing attribution regresses here
    roof = out["roofline"]
    assert roof and roof["programs"], roof
    prog = next(iter(roof["programs"].values()))
    assert prog["gemm_gflops_per_invocation"] > 0
    assert prog["out_lane_ceiling"] is not None


def test_bundle_for_builds_the_module_in_the_config_dtype():
    """--dtype bfloat16 through the CLI builds the bf16 module (the one
    bench.py builds), not an f32 module fed bf16 batches."""
    import types

    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.experiments import _bundle_for

    ds = types.SimpleNamespace(class_num=10,
                               train_x=np.zeros((1, 1, 32, 32, 3)))
    bf16 = _bundle_for(FedConfig(dtype="bfloat16", model="resnet56"), ds)
    assert bf16.module.dtype == jnp.bfloat16
    f32 = _bundle_for(FedConfig(model="resnet56"), ds)
    assert f32.module.dtype == jnp.float32
    # a factory with no dtype knob swallows the keyword
    assert _bundle_for(FedConfig(dtype="bfloat16", model="lr"), ds)

