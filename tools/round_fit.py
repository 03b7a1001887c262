#!/usr/bin/env python3
"""Does a cell's round program fit one chip? Compiled for a DESCRIBED v5e,
no chip needed:

    JAX_PLATFORMS=cpu python tools/round_fit.py --workload ling3_sim_c2

Builds the cell as ``benchmarks/run.py`` does (the configuration's model at
its published shapes, the cell's data and plan), lowers the packed round
program of the cell's first round for ``topologies.get_topology_desc("tpu",
"v5e:2x2")``'s first device and prints ``memory_analysis()``: arguments,
results, temporaries and program text, and their sum against the chip's
16 GiB. Nothing runs: no time, no result (``PERF.md``, PR 30). The model's
parameters are initialised on the host (3.3 GB for 822 M), so this takes a
few minutes; it also prints what the program's Python trace and lowering
took here. ``--seq-len`` / ``--batch`` override the configuration's;
``--hlo FILE`` also writes the compiled program's text there and prints how
many times a step the compiler issues each module's matmuls, by pass (the
first forward, the forward again under ``nn.remat``, the backward): more
than the layers ask for is XLA's OWN rematerialisation (``PERF.md``, PR 38:
the state-space mixer's joint ``in_proj`` 84 times where 36 were asked for).
``--lowered FILE`` writes the LOWERED program's text and stops: two
checkouts' files, with the Pallas kernels' serialized payloads masked (they
embed the callers' file names and line numbers), say whether a change left
a cell's round program alone (``PERF.md``, PR 44, PR 45).
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def products(text: str) -> dict:
    """{(module path after its ``fedml.*`` name, pass): matmuls} of a compiled
    program's text, from the ``op_name`` of every ``convolution``."""
    out = collections.Counter()
    for op in re.findall(r" convolution\(.*op_name=\"([^\"]*)\"", text):
        if "fedml.lm." not in op:
            continue
        owner = re.search(r"layer_\d+/(\w+)/", op)
        tail = re.split(r"fedml\.[\w.]+/", op)[-1].replace("/dot_general", "")
        which = ("remat-fwd" if "rematted_computation" in op
                 else "bwd" if "transpose(" in op else "fwd")
        out[(f"{owner.group(1)}/" if owner else "") + tail, which] += 1
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seq-len", type=int)
    p.add_argument("--batch", type=int)
    p.add_argument("--hlo")
    p.add_argument("--lowered", help="write the lowered program's text there "
                   "and stop before the compile")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.harness.cell import build_api
    from benchmarks.harness.spec import Spec
    from fedml_tpu.core.rng import round_key
    from fedml_tpu.parallel.packed import plan_arrays_tuple

    # the program asks jax.default_backend() which attention, which
    # delta-rule scan and which grouped matmul to take; here that is the
    # CPU's, and the chip's kernels, compiled and not interpreted, are what
    # has to fit (the package exports the function under the attention
    # module's name)
    import importlib

    def on_the_chip(impl):
        return "pallas" if impl == "auto" else impl

    importlib.import_module("fedml_tpu.ops.attention")._pick_impl = on_the_chip
    for name in ("fedml_tpu.ops.kda", "fedml_tpu.ops.grouped_matmul"):
        kernels = importlib.import_module(name)
        kernels._pick_impl, kernels.interpret = on_the_chip, lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    spec = Spec()
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    if args.seq_len:
        config["data"]["seq_len"] = config["model"]["seq_len"] = args.seq_len
    if args.batch:
        config["recipe"]["batch_size"] = args.batch
    dataset, _rows = spec.module("traffic", config["generator"]).make(
        config, cell, 1)
    api = build_api(config, cell, dataset)
    r = int(cell["rounds"]["first"])
    plan = api._round_plan(r)
    lanes = plan.lanes
    step = api.build_round_step_packed(lanes.shape_key)
    tx, ty, tm, _tc = api._dev_train
    n = len(plan.sampled)
    concrete = (api.variables, api.server_state, tx, ty, tm,
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32),
                round_key(api.root_key, r),
                tuple(jnp.asarray(a) for a in plan_arrays_tuple(lanes)))
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        concrete)
    t0 = time.perf_counter()
    traced = step.trace(*shapes)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    if args.lowered:
        with open(args.lowered, "w") as f:
            f.write(lowered.as_text())
        print(f"  trace {t1 - t0:.1f} s, lower {t2 - t1:.1f} s; lowered "
              f"text in {args.lowered}")
        return 0
    compiled = lowered.compile()
    # the Python part of a first call, which no compile cache skips
    # (``round_trace_s``, ``round_lower_s`` on the chip's host)
    print(f"  trace {t1 - t0:.1f} s, lower {t2 - t1:.1f} s, compile "
          f"{time.perf_counter() - t2:.1f} s (this host, the kernels' "
          f"lowering in both)")
    if args.hlo:
        text = compiled.as_text()
        with open(args.hlo, "w") as f:
            f.write(text)
        count = products(text)
        for name in sorted({k[0] for k in count}):
            print(f"  matmuls of {name:24s}" + "".join(
                f"  {which} {count[name, which]:3d}"
                for which in ("fwd", "remat-fwd", "bwd")))
    m = compiled.memory_analysis()
    parts = {"arguments": m.argument_size_in_bytes,
             "results": m.output_size_in_bytes,
             "aliased": -m.alias_size_in_bytes,
             "temporaries": m.temp_size_in_bytes,
             "program text": m.generated_code_size_in_bytes}
    total = sum(parts.values())
    params = sum(int(np.prod(a.shape))
                 for a in jax.tree.leaves(api.variables["params"]))
    print(f"{args.workload}: {params / 1e6:.1f} M parameters, "
          f"{config['recipe']['batch_size']} x {config['data']['seq_len']} "
          f"tokens a step, shape key {lanes.shape_key}")
    for k, v in parts.items():
        print(f"  {k:14s} {v / 1e6:10.1f} MB")
    print(f"  {'sum':14s} {total / 1e6:10.1f} MB = {total / 2**30:.2f} GiB "
          f"({100 * total / (16 * 2**30):.1f}% of 16 GiB)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
