#!/usr/bin/env python3
"""Share of (token, choice) pairs on which the program's router and the
plain reference's chose another expert, at the cell's own size.

    python3 benchmarks/routing_agreement.py --workload <cell> --seeds 3

A sparse layer's selection is a step function of its scores: two
computations of the same layer that differ by rounding pick another expert
for the tokens whose ``top_k``-th and next score lie closer than that
rounding, and a token that changes expert changes its output by a whole
expert's worth. The check's numbers carry that; this prints how many pairs
it is, per sparse layer, for the program's own module (the cell's model at
the configuration's precision, kernels and all) against the reference's
``stated`` and ``reference`` variants on the same seeded weights and the
same batch (the first sampled client's first batch of round ``first``). Not
part of a benchmark run; needs the cell's chips like one.
"""

from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.harness import protocol  # noqa: E402
from benchmarks.harness.device import require_tpu  # noqa: E402
from benchmarks.harness.spec import Spec  # noqa: E402


def differing_share(a, b) -> float:
    """``a, b [N, k]`` chosen experts -> share of pairs of ``a`` whose expert
    ``b`` did not choose for that token."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    same = (a[:, :, None] == b[:, None, :]).any(-1)
    return float(1.0 - same.mean())


def main(argv=None, *, spec: Spec = None, device_check=require_tpu) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=7001)
    args = p.parse_args(argv)
    spec = spec or Spec()
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    ref = spec.module("references", config["reference"])
    generator = spec.module("traffic", config["generator"])

    import jax
    import jax.numpy as jnp

    device_check(int(cell["chips"]))
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dtype = (jnp.bfloat16 if config["precision"]["module"] == "bfloat16"
             else jnp.float32)
    batch = int(config["recipe"]["batch_size"])
    first = int(cell["rounds"]["first"])
    ids = protocol.sample_cohort(
        first, int(cell["clients"]),
        int(cell["fed_config"]["client_num_per_round"]),
        int(cell["sampling_seed"]))

    def program(bundle):
        def choices(variables, x):
            _, seen = bundle.module.apply(variables, x, train=False,
                                          mutable=["intermediates"])
            return {layer: v["mlp"]["choices"][0]
                    for layer, v in seen["intermediates"].items()}
        return jax.jit(choices)

    run = None
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        dataset, rows = generator.make(config, cell, seed)
        if run is None:
            run = program(create_model(
                config["model"]["program_name"], dataset.class_num,
                input_shape=dataset.train_x.shape[2:], dtype=dtype))
        x = jnp.asarray(rows(ids[:1])[0][0][:batch])
        variables = jax.jit(lambda k: ref.init(k, config))(
            jax.random.fold_in(protocol.run_key(seed), 0x1417))
        ours = jax.device_get(run(variables, x))
        for variant in ("stated", "reference"):
            theirs = jax.device_get(ref.choices(config, variables, x, variant))
            print(f"seed {seed} program against {variant}: " + "  ".join(
                f"{layer} {differing_share(ours[layer], theirs[layer]):.5f}"
                for layer in sorted(ours)) + "  (share of (token, choice) "
                f"pairs, {ours[sorted(ours)[0]].size} pairs a layer)",
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
