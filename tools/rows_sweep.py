#!/usr/bin/env python3
"""rows_sweep: the sparse layer's row work alone on the chip, at the LM
cell's shapes, at each row capacity; and, from a trace of the cell, how many
layer-steps ran at each capacity.

``SharedRoutedMoe`` (``fedml_tpu/models/moe.py``) walks a static capacity of
sorted row slots that it chooses each step from the router's own count
(``row_rungs``). The benchmark reads that work through a whole round
(``moe_route_ms``, ``expert_mm_ms``, by scope). This tool times the parts by
themselves, each jitted alone, at ``[8192, 2048]`` bf16 tokens, 6 choices of
128 experts, 16 held experts of 768: the fan-out gather, the row mask, the
experts' grouped matmuls forward and forward + backward on the float32
matrices as the program runs them (``ops/grouped_matmul.py``: the kernels of
the repo's own) and as it ran them before PR 45 (``incumbent_*``:
``lax.ragged_dot`` on the matrices cast to bf16 and the weight gradients
cast back, the casts counted), the two forms of the
weighted add-back (today's gather of one row a PAIR and float32 sum, and a
scatter-add of the ``C`` weighted rows into ``[n, d]`` float32), and a whole
rung forward and backward (``moe._rung``, ``moe._rung_vjp``). One row a
capacity: the layer's rungs, the last of which is every pair, or the
capacities named. Tokens are Zipf(1) draws over a random embedding and the
router is random, as in the cell, and the 16 held experts are the window of
16 with the fewest rows, so that every capacity that holds them moves the
same rows (a smaller one is skipped). Wall-clock ms a call over ``--iters``
calls, and the device's own time for a traced call.

    python tools/rows_sweep.py [capacity ...]       # the parts, on the chip
    python tools/rows_sweep.py --width 2048 --choices 1 --routed 16 --held 8 \
        4096 5120 8192       # another layer's sizes (these: zaya1_sim_c2's)
    python tools/rows_sweep.py --form relu2 --tokens 4096 --dim 1024 \
        --width 2688 --choices 22 --routed 512 --held 8 11264
                             # two matrices an expert (nemotron3s_sim_c2's)
    python tools/rows_sweep.py --trace-dir .bench_out/trace/kanana2_sim_c2

With ``--trace-dir`` (a profiler trace of the cell, as ``benchmarks/run.py
--trace 1`` leaves it) it also prints the share of the sparse layers'
conditionals that ran under each ``moe_rows_<C>`` name, and the grouped
matmuls' calls by kernel (``_gmm_fwd``, ``_gmm_dx``, ``_gmm_dw``, and the
compiler's ``%ragged-dot`` where any is left); that part needs no chip. Fails at once without a TPU unless ``--trace-only``. Writes
``chiprun_out/rows_sweep.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time
from collections import Counter

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

TOKENS, DIM, WIDTH, CHOICES, ROUTED, HELD, VOCAB = 8192, 2048, 768, 6, 128, 16, 16032
FORM = "swiglu"
RUNG_NAME = re.compile(r"moe_rows_(\d+)")
#: a grouped matmul's call in a trace: the kernels of the repo's own carry
#: the name of the jitted function that makes the call (``%_gmm_fwd.7``),
#: the compiler's its bare name (``%ragged-dot-none``)
KERNEL_NAME = re.compile(r"^%(_gmm_fwd|_gmm_dx|_gmm_dw|ragged-dot)")


def _device_ms(fn, args, calls: int = 3) -> float:
    """The device's time over ``calls`` traced calls, ms a call (top-level
    operations: a nested one is inside its parent's time)."""
    import jax

    from benchmarks.trace import scopes

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        data = scopes.read_trace(scopes.find_xplane(d))
    ops = sorted(next(iter(data["devices"].values()))["XLA Ops"],
                 key=lambda v: (v[0], -v[1]))
    par = scopes.parents(ops)
    return sum(e - s for (s, e, _n), p in zip(ops, par) if p < 0) * 1e3 / calls


def _routing(seed: int):
    """-> the nine operands of a rung, drawn as the cell draws them, with the
    window of held experts that has the fewest rows."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models import moe
    from fedml_tpu.models.moe import route

    ks = jax.random.split(jax.random.key(seed), 8)
    ranks = jnp.arange(1, VOCAB + 1, dtype=jnp.float32)
    tokens = jax.random.choice(ks[0], VOCAB, (TOKENS,), p=(1 / ranks) / jnp.sum(1 / ranks))
    xf = jnp.take(jax.random.normal(ks[1], (VOCAB, DIM), jnp.float32), tokens, axis=0)
    scores = jax.nn.sigmoid(xf @ (0.02 * jax.random.normal(ks[2], (DIM, ROUTED))))
    idx, weights = route(scores, 0.01 * jax.random.normal(ks[3], (ROUTED,)),
                         CHOICES, 2.448)
    # the window of held experts with the fewest rows
    first = min(range(0, ROUTED, HELD), key=lambda f: int(
        ((idx >= f) & (idx < f + HELD)).sum()))
    local = (idx - first).T
    mine = (local >= 0) & (local < HELD)
    key = jnp.where(mine, local, HELD).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, HELD + 1, dtype=jnp.int32), axis=0)[:HELD]
    # the form's matrices: all but the last into the width, the last out
    into = len(moe.EXPERT_FORMS[FORM][0]) - 1
    w = [0.02 * jax.random.normal(ks[4 + i], shape, jnp.float32)
         for i, shape in enumerate([(HELD, DIM, WIDTH)] * into
                                   + [(HELD, WIDTH, DIM)])]
    return (xf.astype(jnp.bfloat16), order, jnp.argsort(order), sizes, mine,
            weights, *w)


def measure(operands, capacity: int, iters: int, trace: bool = True) -> dict:
    """One capacity's parts -> {part: {"ms", "device_ms"}} and the scatter
    form's largest difference from the gather form."""
    import jax
    import jax.numpy as jnp

    import fedml_tpu.ops.grouped_matmul as gm
    from fedml_tpu.models import moe
    from fedml_tpu.ops.grouped_matmul import fan_out_rows, permute_rows

    xf, order, inv, sizes, mine, weights, *w = operands
    n, d = xf.shape
    k = mine.shape[0]
    head = order[:capacity]
    wt = jnp.where(mine, weights.T, 0.0)
    live = (jnp.arange(capacity) < jnp.sum(sizes))[:, None]

    def mask(rows, sizes):
        return jnp.where((jnp.arange(capacity) < jnp.sum(sizes))[:, None], rows, 0)

    def experts(rows, sizes, *ws):
        return moe.EXPERT_FORMS[FORM][1](rows, sizes, live, *ws)[0]

    def experts_both(rows, sizes, ct, *ws):
        y, vjp = jax.vjp(lambda r, *ws: experts(r, sizes, *ws), rows, *ws)
        return y, vjp(ct)

    def incumbent(fn):
        """``fn`` traced with the grouped matmul as it was before PR 45."""
        def traced(*args):
            ours, moe.grouped_matmul = moe.grouped_matmul, gm._plain
            try:
                return fn(*args)
            finally:
                moe.grouped_matmul = ours
        return traced

    def add_back_gather(y, inv, head, wt):
        back = permute_rows(y, inv, head).reshape(k, n, d)
        return jnp.einsum("knd,kn->nd", back.astype(jnp.float32), wt)

    def add_back_scatter(y, head, wt):
        scale = jnp.take(wt.reshape(-1), head)[:, None]
        return jnp.zeros((n, d), jnp.float32).at[head % n].add(
            y.astype(jnp.float32) * scale)

    rows = jax.jit(mask)(jax.jit(fan_out_rows)(xf, head, inv), sizes)
    y = jax.jit(mask)(jax.jit(experts)(rows, sizes, *w), sizes)
    ct = jax.random.normal(jax.random.key(1), (n, d), jnp.float32)
    stats = jax.eval_shape(moe._rung(capacity, FORM), *operands)[1]
    parts = {
        "fan_out": (jax.jit(fan_out_rows), (xf, head, inv)),
        "mask": (jax.jit(mask), (rows, sizes)),
        "experts_fwd": (jax.jit(experts), (rows, sizes, *w)),
        "experts_fwd_bwd": (jax.jit(experts_both), (rows, sizes, y, *w)),
        "incumbent_fwd": (jax.jit(incumbent(experts)), (rows, sizes, *w)),
        "incumbent_fwd_bwd": (jax.jit(incumbent(experts_both)),
                              (rows, sizes, y, *w)),
        "add_back_gather": (jax.jit(add_back_gather), (y, inv, head, wt)),
        "add_back_scatter": (jax.jit(add_back_scatter), (y, head, wt)),
        "rung_fwd": (moe._rung(capacity, FORM), operands),
        "rung_bwd": (moe._rung_vjp(capacity, FORM), (operands, (
            ct, jax.tree_util.tree_map(jnp.zeros_like, stats)))),
    }
    row = {"capacity": capacity, "rows_filled": int(jnp.sum(sizes))}
    for name, (fn, args) in parts.items():
        out = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        row[name] = {"ms": (time.perf_counter() - t0) * 1e3 / iters}
        if trace:
            row[name]["device_ms"] = _device_ms(fn, args)
    a, b = parts["add_back_gather"], parts["add_back_scatter"]
    a, b = a[0](*a[1]), b[0](*b[1])
    row["scatter_gap_to_gather"] = float(jnp.max(jnp.abs(a - b))
                                         / jnp.max(jnp.abs(a)))
    a, b = parts["experts_fwd"], parts["incumbent_fwd"]
    a, b = (fn(*args).astype(jnp.float32) for fn, args in (a, b))
    row["experts_gap_to_incumbent"] = float(jnp.max(jnp.abs(a - b))
                                            / jnp.max(jnp.abs(b)))
    return row


def rung_shares(trace_dir: str) -> dict:
    """Share of the sparse layers' conditionals that ran at each capacity,
    from a profiler trace of the cell. An operation whose path holds
    ``moe_rows_<C>`` ran in that capacity's branch; the branch's executions
    are counted by the conditional operations that enclose such operations
    or, where the trace shows no conditional, by the runs of them that
    nothing of another path interrupts (an operation without a path, as
    the compiler's grouped kernels are, interrupts nothing). Forward and
    backward conditionals of one layer-step take the same branch, so the
    shares are the layer-steps'. The grouped matmuls' calls are counted by
    kernel, wherever they ran (:data:`KERNEL_NAME`). -> {"conditionals", "counted_by", "shares": {C: share},
    "device_ms": {C: ms inside those branches}, "kernel_calls": {kernel:
    calls}, "kernel_ms": {kernel: ms}}."""
    from benchmarks.trace import opmeta, scopes

    path = scopes.find_xplane(trace_dir)
    data, meta = scopes.read_trace(path), opmeta.read(path)
    dev = max(data["devices"], key=lambda d: len(data["devices"][d]["XLA Ops"]))
    ops = sorted(data["devices"][dev]["XLA Ops"], key=lambda v: (v[0], -v[1]))
    table, par = meta.get(dev, {}), scopes.parents(ops)

    def path_of(i):
        return table.get(ops[i][2], {}).get("tf_op") or ""

    def rung_of(i):
        found = RUNG_NAME.findall(path_of(i))
        return int(found[-1]) if found else None

    rungs = [rung_of(i) for i in range(len(ops))]
    calls, call_ms = Counter(), Counter()
    for start, end, name in ops:
        found = KERNEL_NAME.match(name)
        if found:
            calls[found.group(1)] += 1
            call_ms[found.group(1)] += (end - start) * 1e3
    enclosing, runs, ms, last = {}, Counter(), Counter(), None
    for i, c in enumerate(rungs):
        if c is None:
            # a path of its own (not the bare name of a kernel) ends a run
            if "/" in path_of(i):
                last = None
            continue
        p = par[i]
        if p >= 0 and rungs[p] is not None:
            continue                    # inside a loop of its branch
        ms[c] += (ops[i][1] - ops[i][0]) * 1e3
        if p >= 0 and "conditional" in ops[p][2]:
            enclosing[p] = c
        if last != (p, c):
            runs[c] += 1
        last = (p, c)
    counts = Counter(enclosing.values()) or runs
    total = sum(counts.values())
    return {"conditionals": total,
            "counted_by": "conditional" if enclosing else "runs",
            "shares": {c: counts[c] / total for c in sorted(counts)},
            "device_ms": {c: ms[c] for c in sorted(ms)},
            "kernel_calls": dict(sorted(calls.items())),
            "kernel_ms": dict(sorted(call_ms.items()))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("capacities", nargs="*", type=int,
                    help="row capacities to time (default: the layer's rungs)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=29)
    for name in ("tokens", "dim", "width", "choices", "routed", "held"):
        ap.add_argument(f"--{name}", type=int, default=globals()[name.upper()],
                        help="the layer's size (default: kanana2_sim_c2's)")
    ap.add_argument("--form", default=FORM, choices=("swiglu", "relu2"),
                    help="what an expert is (models/moe.py: EXPERT_FORMS)")
    ap.add_argument("--trace-dir", help="a profiler trace of the cell")
    ap.add_argument("--trace-only", action="store_true",
                    help="read --trace-dir and time nothing (needs no chip)")
    args = ap.parse_args(argv)
    globals().update(TOKENS=args.tokens, DIM=args.dim, WIDTH=args.width,
                     CHOICES=args.choices, ROUTED=args.routed, HELD=args.held,
                     FORM=args.form)
    out = {}
    if args.trace_dir:
        out["rungs_in_trace"] = rung_shares(args.trace_dir)
        print(json.dumps({"rungs_in_trace": out["rungs_in_trace"]}), flush=True)
    if not args.trace_only:
        import jax

        from fedml_tpu.models.moe import row_rungs

        if jax.default_backend() != "tpu":
            print("rows_sweep: no TPU", file=sys.stderr)
            return 1
        rungs = sorted(args.capacities) or row_rungs(TOKENS * CHOICES)
        operands = _routing(args.seed)
        filled = int(operands[3].sum())
        out["device"] = jax.devices()[0].device_kind
        out["sizes"] = [TOKENS, DIM, WIDTH, CHOICES, ROUTED, HELD, FORM]
        out["rows"] = []
        for capacity in rungs:
            if capacity < filled:
                print(json.dumps({"capacity": capacity, "skipped":
                                  f"{filled} rows are filled"}), flush=True)
                continue
            row = measure(operands, capacity, args.iters)
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    # a later call (the trace's shares after the parts) adds to the file
    os.makedirs("chiprun_out", exist_ok=True)
    if os.path.exists("chiprun_out/rows_sweep.json"):
        with open("chiprun_out/rows_sweep.json") as f:
            out = {**json.load(f), **out}
    with open("chiprun_out/rows_sweep.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
