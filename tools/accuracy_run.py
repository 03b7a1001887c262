"""Scaled accuracy run v2: centralized vs fed-IID vs fed-non-IID.

The reference's §6 headline is an accuracy TABLE with structure — IID beats
non-IID at a fixed round budget (CIFAR-10 + ResNet-56: 93.19 vs 87.12,
benchmark/README.md:105). This image has zero network egress (DNS + direct-
IP attempts recorded in docs/accuracy.md), so the real rows cannot be
reproduced; round 4's fallback run saturated at 100% by round 30 —
demonstrating parity at a trivial operating point (its own doc flagged it).

v2 calibrates the synthetic task so it CANNOT saturate: ``--separation``
shrinks the class-mean spread (convergence speed knob) and
``--label_noise`` resamples a fraction of observed labels uniformly — an
irreducible test-accuracy ceiling of (1-rho) + rho/C. At that operating
point the three curves can actually differ, and the reference's structural
gap (IID > non-IID under a fixed budget) is reproduced and pinned by
tests/test_accuracy_artifact.py.

All three arms train the flagship config (ResNet-56, CIFAR-10 shapes,
bf16, bs 64) on the SAME generated features/labels; only the partition
changes: pooled (centralized), homo (fed-IID), hetero LDA alpha
(fed-non-IID).

Usage: python tools/accuracy_run.py [out.json] [--rounds N] [--ci]
                                    [--separation S] [--label_noise R]
                                    [--alpha A]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _arg(argv, flag, default, cast=float):
    if flag in argv:
        return cast(argv[argv.index(flag) + 1])
    return default


def main(argv):
    out_path = argv[0] if argv and not argv[0].startswith("-") else "accuracy_run.json"
    rounds = _arg(argv, "--rounds", 120, int)
    ci = "--ci" in argv
    # defaults MUST match the committed accuracy_run.json's provenance
    # (difficulty block: separation=0.3, label_noise=0.12) — regenerating
    # with defaults has to land on the same operating point the pinned
    # assertions in tests/test_accuracy_artifact.py were calibrated for
    separation = _arg(argv, "--separation", 0.3)
    label_noise = _arg(argv, "--label_noise", 0.12)
    alpha = _arg(argv, "--alpha", 0.5)

    import jax
    import jax.numpy as jnp

    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from fedml_tpu.algorithms.centralized import CentralizedTrainer
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model

    clients = 4 if ci else 32
    records = 32 if ci else 1562
    rounds = 2 if ci else rounds
    batch = 16 if ci else 64

    def ds_for(partition):
        # name carries the difficulty + partition so the cached Dirichlet
        # maps never collide across operating points
        return make_synthetic_classification(
            f"cifar10-acc2-{partition}-s{separation}-n{label_noise}",
            (32, 32, 3), 10, clients, records_per_client=records,
            partition_method=partition, partition_alpha=alpha,
            batch_size=batch, seed=0, separation=separation,
            label_noise=label_noise,
        )

    common = dict(
        model="resnet56", dataset="cifar10", client_num_in_total=clients,
        client_num_per_round=clients, comm_round=rounds, batch_size=batch,
        epochs=1, lr=0.1, momentum=0.9, dtype="bfloat16",
        frequency_of_the_test=max(1, rounds // 12), seed=0,
    )

    arms = {}
    for arm, partition in (("fed_iid", "homo"), ("fed_noniid", "hetero"),
                           ("centralized", "homo")):
        ds = ds_for(partition)
        bundle = create_model("resnet56", 10, dtype=jnp.bfloat16,
                              input_shape=ds.train_x.shape[2:])
        t0 = time.time()
        if arm == "centralized":
            hist = CentralizedTrainer(ds, FedConfig(**common), bundle).train()
        else:
            hist = FedAvgAPI(ds, FedConfig(**common), bundle).train()
        arms[arm] = {
            "round": hist.get("round"),
            "Test/Acc": hist.get("Test/Acc"),
            "Test/Loss": hist.get("Test/Loss"),
            "wall_seconds": round(time.time() - t0, 1),
        }
        print(json.dumps({"arm": arm,
                          "final_acc": (hist.get("Test/Acc") or [None])[-1]}),
              flush=True)

    ceiling = (1.0 - label_noise) + label_noise / 10.0
    result = {
        "config": dict(common),
        "difficulty": {"separation": separation, "label_noise": label_noise,
                       "partition_alpha": alpha,
                       "noise_ceiling_acc": round(ceiling, 4)},
        **arms,
        "device": str(jax.devices()[0]),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "cen": arms["centralized"]["Test/Acc"][-1],
        "iid": arms["fed_iid"]["Test/Acc"][-1],
        "noniid": arms["fed_noniid"]["Test/Acc"][-1],
        "ceiling": ceiling, "rounds": rounds, "out": out_path}))


if __name__ == "__main__":
    main(sys.argv[1:])
