#!/usr/bin/env python3
"""The readings a cell's limits are set from, at the cell's own size.

    python3 benchmarks/readings.py --workload <cell> --seeds 12 \
        --control-seeds 3 --first-seed 7001 --out chiprun_out/readings.json

One process, because set-up is long: the program is built once, on the data
of the first seed, and driven through the cell's check rounds from each
seed's weights and shuffles; then, the program freed, the plain reference
follows every seed, and on the first ``--control-seeds`` of them so does
each other variant of the reference (the stated precision, and the controls
one step below it), compared in the program's place. Every number of
``harness/check.py`` is printed per seed and written to ``--out`` with the
per-leaf norms they are made of; the limits in the cell's file are then
set by hand from the two ends, as ``PERF.md`` section 2 records. Not part
of a benchmark run; needs the cell's chips like one.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.harness import check  # noqa: E402
from benchmarks.harness.device import memory_report, require_tpu  # noqa: E402
from benchmarks.harness.spec import Spec  # noqa: E402


def numbers_of(prog, refd, init) -> dict:
    """The check's numbers, unjudged, with the first update's leaf norms."""
    out = check.compare(*prog, *refd, init, {})
    row = {name: value for name, value, *_ in out["numbers"]}
    row["notes"] = {name: note for name, _v, _l, _ok, note in out["numbers"]}
    row["leaves"] = check.leaf_norms(prog[1][0], refd[1][0], init)
    return row


def main(argv=None, *, spec: Spec = None, device_check=require_tpu) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=7001)
    p.add_argument("--variants", default="",
                   help="comma-separated; default: all but the reference")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    spec = spec or Spec()
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    ref = spec.module("references", config["reference"])
    generator = spec.module("traffic", config["generator"])

    import jax

    devices = device_check(int(cell["chips"]))
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.harness.cell import build_api, seed_program

    seeds = [args.first_seed + i for i in range(args.seeds)]
    first = int(cell["rounds"]["first"])
    rounds = list(range(first, first + int(cell["check_rounds"])))
    t0 = time.perf_counter()
    dataset, rows = generator.make(config, cell, seeds[0])
    api = build_api(config, cell, dataset)
    programs = {}
    for seed in seeds:
        init = seed_program(api, ref, config, seed)
        losses, states = [], []
        for r in rounds:
            losses.append(float(jax.block_until_ready(api.run_round(r))))
            states.append(jax.device_get(api.variables))
        programs[seed] = (init, (losses, states))
        print(f"program seed {seed}: losses {losses}  "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    print(memory_report(devices), flush=True)
    api.close()
    del api, dataset
    gc.collect()

    out = {"workload": args.workload, "rounds": rounds, "data_seed": seeds[0],
           "program": {}, "variants": {}}
    others = ([v for v in args.variants.split(",") if v]
              or [v for v in ref.VARIANTS if v != "reference"])
    for n, seed in enumerate(seeds):
        init, prog = programs.pop(seed)
        t0 = time.perf_counter()
        base = check.reference_rounds(ref, config, cell, rows, init, seed, rounds)
        took = time.perf_counter() - t0
        row = numbers_of(prog, base, init)
        out["program"][str(seed)] = row
        print(f"seed {seed} program ({took:.1f} s of reference): " + "  ".join(
            f"{k} {v:.4g}" for k, v in row.items()
            if k not in ("notes", "leaves")) + f"  [{row['notes']['update_norm_gap']}]",
            flush=True)
        if n >= args.control_seeds:
            continue
        for variant in others:
            t0 = time.perf_counter()
            low = check.reference_rounds(ref, config, cell, rows, init, seed,
                                         rounds, variant)
            row = numbers_of(low, base, init)
            out["variants"].setdefault(variant, {})[str(seed)] = row
            print(f"seed {seed} {variant} ({time.perf_counter() - t0:.1f} s): "
                  + "  ".join(f"{k} {v:.4g}" for k, v in row.items()
                              if k not in ("notes", "leaves"))
                  + f"  [{row['notes']['update_norm_gap']}]", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(f"wrote {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
