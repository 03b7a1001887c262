#!/usr/bin/env python3
r"""trace_ops: a traced cell's device time by operation group, for the split
of a part by what is inside it (``dense_mm_ms`` by projection: ``PERF.md``
section 5).

    python3 benchmarks/run.py --workload granite4h_sim_c2 --seed 1 \
        --seconds 10 --trace 1
    python tools/trace_ops.py .bench_out/trace/granite4h_sim_c2 \
        [--scope fedml.lm.dense] [--top 60] [--tag NAME]

The same reduction as the benchmark's readers (``benchmarks/trace/scopes.py``:
the busiest chip's ops, exclusive time, each op under its innermost
``fedml.*`` name), grouped by scope, by pass (``fwd``: the first forward;
``remat-fwd``: the forward again under ``nn.remat``; ``bwd``), by the
fusion's name without its number (a ``remat`` in it is XLA's OWN
rematerialisation: the compiler computing an op again rather than keeping
its result) and by the op's JAX path after its innermost ``fedml.*`` name.
One line a group: ms a round (a round is one run of the heaviest module),
ops a round. ``--scope`` keeps one scope and adds the sums by the
module that issued the op (``in_proj``, ``mlp/gate`` ...). Needs no chip;
where ``chiprun_out/`` exists (a chip call) the table is written there too.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from collections import defaultdict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def groups(path: str):
    """-> (rounds, busy seconds, {(scope, pass, fusion, tail): [s, ops]})."""
    from benchmarks.trace import opmeta, scopes
    from benchmarks.trace.reduce import find_xplane

    path = find_xplane(path)
    data = scopes.read_trace(path)
    dev = max(data["devices"], key=lambda d: sum(
        e - s for s, e, _n in data["devices"][d]["XLA Ops"]))
    ops = sorted(data["devices"][dev]["XLA Ops"], key=lambda v: (v[0], -v[1]))
    table = opmeta.read(path).get(dev, {})
    mods = defaultdict(lambda: [0.0, 0])     # the round program is the heaviest
    for s, e, name in data["devices"][dev]["XLA Modules"]:
        cell = mods[name.split("(")[0]]
        cell[0] += e - s
        cell[1] += 1
    rounds = max(mods.values(), default=[0.0, 1])[1]
    out = defaultdict(lambda: [0.0, 0])
    for (secs, scope, _k), (_s, _e, name) in zip(
            scopes.device_scopes(ops, table), ops):
        tf_op = table.get(name, {}).get("tf_op") or ""
        which = ("remat-fwd" if "rematted_computation" in tf_op
                 else "bwd" if "transpose(" in tf_op else "fwd")
        fusion = re.sub(r"\.\d+", "", name.split(" ")[0])
        steps = tf_op.partition(":")[0].split("/")
        named = [i for i, step in enumerate(steps)
                 if step.startswith("fedml.")]
        tail = "/".join(steps[named[-1] + 1:] if named else steps[-2:])
        cell = out[(scope or "unscoped", which, fusion, tail)]
        cell[0] += secs
        cell[1] += 1
    return rounds, sum(v[0] for v in out.values()), out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace")
    p.add_argument("--scope")
    p.add_argument("--top", type=int, default=60)
    p.add_argument("--tag", default="")
    args = p.parse_args(argv)
    rounds, busy, table = groups(args.trace)
    lines = [f"busy {busy:.4f} s over {rounds} rounds"]
    rows = sorted(((k, v) for k, v in table.items()
                   if args.scope in (None, k[0])), key=lambda kv: -kv[1][0])
    for (scope, which, fusion, tail), (secs, n) in rows[:args.top]:
        lines.append(f"{secs / rounds * 1e3:9.2f} ms/round {n / rounds:7.0f} "
                     f"ops/round  {scope:20s} {which:9s} {fusion:28s} {tail}")
    if args.scope:
        by = defaultdict(lambda: [0.0, 0])
        for (_scope, _which, _fusion, tail), (secs, n) in rows:
            cell = by[tail.replace("/dot_general", "")]
            cell[0] += secs
            cell[1] += n
        lines.append(f"{args.scope} by module:")
        for tail, (secs, n) in sorted(by.items(), key=lambda kv: -kv[1][0]):
            lines.append(f"{secs / rounds * 1e3:9.2f} ms/round "
                         f"{n / rounds:7.0f} ops/round  {tail}")
    print("\n".join(lines))
    out = os.path.join(_ROOT, "chiprun_out")
    if os.path.isdir(out):
        name = f"trace_ops{'_' + args.tag if args.tag else ''}.txt"
        with open(os.path.join(out, name), "w") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
