"""A decoder LM's round program, by the names its blocks give their work.

Beside ``scopes.py`` (not edited): the same reduction, read by other parts.
``fedml.lm.*`` scopes (``fedml_tpu/obs/tracer.py``) sit inside
``fedml.step.train``; the last ``fedml.*`` name of an op's ``tf_op`` path
decides (``scopes.device_scopes``). One pass of its own over the ops,
because the compiler's grouped-matmul kernels carry no path at all. Six
parts partition the busiest chip's busy time:

    attn          fedml.lm.attn      scores, softmax, values (the kernels)
    experts       fedml.lm.experts   the grouped matmuls
    route         fedml.lm.route     router, selection, sort, fan-out, add-back
    dense         fedml.lm.dense     every other matmul
    state_update  fedml.step.reset / .opt / .emit, fedml.aggregate,
                  fedml.server       every pass over the parameter tree
    other         all the rest       norms, rotary, residual adds, loss,
                                     prologue, gather, the scan's own time

A trace of a program without the ``fedml.lm.*`` names reduces to None.
"""

from __future__ import annotations

import functools
import os
from collections import defaultdict

from benchmarks.trace import opmeta, scopes

PARTS = {
    "attn": ("fedml.lm.attn",),
    "experts": ("fedml.lm.experts",),
    "route": ("fedml.lm.route",),
    "dense": ("fedml.lm.dense",),
    "state_update": ("fedml.step.reset", "fedml.step.opt", "fedml.step.emit",
                     "fedml.aggregate", "fedml.server"),
}


#: the compiler's grouped-matmul kernels arrive as custom calls whose
#: ``tf_op`` is the bare name (``ragged-dot-none:``), which ``scopes.py``
#: reads as the relayout of an argument and puts under the prologue
EXPERT_KERNELS = ("%ragged-dot",)


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime: float):
    """-> {"by_scope_s", "busy_s", "xla": {scope: counts}} of the busiest
    chip, each op under the last ``fedml.*`` name of its path
    (``scopes.device_scopes``), the grouped-matmul kernels under
    ``fedml.lm.experts``; None without a device plane or the LM's names."""
    data = scopes.read_trace(path)
    if not data["devices"]:
        return None
    meta = opmeta.read(path)
    bench = [r for rows in data["threads"].values() for r in rows
             if r[2].startswith(scopes.BENCH_PREFIX)]
    all_ops = [o for d in data["devices"].values() for o in d["XLA Ops"]]
    t0 = min(r[0] for r in (bench or all_ops))
    t1 = max(r[1] for r in (bench or all_ops))
    best = None
    for dev in sorted(data["devices"]):
        ops = sorted(((max(s, t0), min(e, t1), n)
                      for s, e, n in data["devices"][dev]["XLA Ops"]
                      if e > t0 and s < t1), key=lambda v: (v[0], -v[1]))
        table = meta.get(dev, {})
        by_scope = defaultdict(float)
        xla = defaultdict(lambda: {"flops": 0.0, "bytes_accessed": 0.0,
                                   "ops": 0})
        for (secs, sc, _k), (_s, _e, name) in zip(
                scopes.device_scopes(ops, table), ops):
            if name.startswith(EXPERT_KERNELS):
                sc = PARTS["experts"][0]
            by_scope[sc or "unscoped"] += secs
            m, x = table.get(name, {}), xla[sc]
            x["flops"] += m.get("flops") or 0
            x["bytes_accessed"] += m.get("bytes_accessed") or 0
            x["ops"] += 1
        busy = sum(by_scope.values())
        if best is None or busy > best["busy_s"]:
            best = {"by_scope_s": dict(by_scope), "busy_s": busy,
                    "xla": dict(xla)}
    if not any(k.startswith("fedml.lm.") for k in best["by_scope_s"]):
        return None
    return best


def reduce_ctx(ctx):
    path = scopes.trace_path(ctx)
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path))


def parts_s(ctx):
    """{part: seconds over the traced window} with ``other``, or None."""
    red = reduce_ctx(ctx)
    if red is None:
        return None
    out = {part: sum(red["by_scope_s"].get(n, 0.0) for n in names)
           for part, names in PARTS.items()}
    out["other"] = red["busy_s"] - sum(out.values())
    return out


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])


def xla_count(ctx, scope: str) -> dict:
    """XLA's own ``flops`` / ``bytes_accessed`` of the executed ops under
    ``scope`` (a kernel call may carry none: then 0)."""
    return reduce_ctx(ctx)["xla"].get(
        scope, {"flops": 0.0, "bytes_accessed": 0.0, "ops": 0})


def roofline_pct(ctx, part: str, cost_fn: str, name: str, **cost_kw):
    """``max(FLOPs / peak, bytes / peak)`` of the executed slots' work, from
    shapes (``benchmarks/flops/<config>.py: <cost_fn>``, which also takes
    ``cost_kw``), over the part's device time. Over 105% raises."""
    parts = parts_s(ctx)
    if parts is None or not parts[part] or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    flops, nbytes = getattr(spec.module("flops", config["flops"]),
                            cost_fn)(config, **cost_kw)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if peak_flops is None:
        return None
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / parts[part]
    xla = xla_count(ctx, PARTS[part][0])
    print(f"{name}: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'} "
          f"({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms at "
          f"the byte peak, {parts[part] * 1e3:.3f} ms taken); from shapes "
          f"{slots * flops:.6g} FLOPs, {slots * nbytes:.6g} bytes (recomputed "
          f"and padded work not counted); by XLA's own count over {xla['ops']} "
          f"executed ops {xla['flops']:.6g} FLOPs, {xla['bytes_accessed']:.6g} "
          "bytes accessed", flush=True)
    if share > 105.0:
        raise RuntimeError(f"{name} {share:.1f} is over 105%: the operations "
                           "or bytes are counted too high, or the time leaves "
                           "out part of the work")
    return share
