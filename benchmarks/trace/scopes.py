"""The traced run's time, by the names the program gives its own work.

The program annotates itself into the profiler trace (the names' table is
in ``fedml_tpu/obs/tracer.py``): ``jax.named_scope`` names (``fedml.step.
train``, ``fedml.aggregate``, ...) arrive in each device op's ``tf_op`` path,
which only the file's event-metadata table holds (``opmeta.py``), and
``fedml/...`` host spans (``TraceAnnotation``) lie on the host plane, on the
device's clock. This module joins the two with ``reduce.py``'s own pieces:

- device self time by scope: the last ``fedml.*`` name in an op's ``tf_op``.
  An op without one (an async copy, a ``while``, whose entries carry no
  ``tf_op``) takes the scope of the op it is nested in; a ``while`` without
  one takes the scope its body's ops name before their ``/while``; an op
  that XLA names by a bare argument of the program (``tf_op`` ``tx:``: the
  relayout of an input for its first consumer) belongs to the prologue,
  which consumes the inputs; a copy XLA puts between the program's ops (no
  ``tf_op``, nested in nothing) goes with the named op that follows it.
  What is left is ``unscoped``. Self time here
  is exclusive time (every instant goes to the op that started last and
  still runs), so the parts sum to the busy time exactly:
  ``reduce.self_times`` over-counts a ``while`` whose body's ops overlap
  each other by a few nanoseconds (18,776 such pairs, 108 ms of 3,122, in
  the flagship's trace);
- inside ``fedml.step.train`` by kind: a convolution (``tf_op`` ends in
  ``conv_general_dilated`` or ``dot_general``, or ``hlo_category`` names a
  convolution: XLA fuses the reductions behind a convolution into it), a
  normalisation (a ``...Norm`` module or ``fedml.norm`` in the path), other;
- the six parts ``prologue + conv + norm + optimizer + step_other +
  aggregate`` (+ ``unscoped``) partition the busy time of the round program;
- host self time by ``fedml/...`` span: a span's duration less its
  children's, per thread;
- every idle gap of the busiest chip, put down to the ``fedml/...`` span
  whose own time covers most of it, or to "outside the program" (the caller
  blocking, the benchmark's loop).

A trace of a program without the names (the parent of the PR that added
them, the CPU) reduces to ``None``: the metric readers then report nothing.

``python benchmarks/trace/scopes.py <dir-or-file>`` prints the reduction.
"""

from __future__ import annotations

import functools
import os
import re
import sys
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.trace import opmeta
from benchmarks.trace.reduce import (SPAN_PREFIX as BENCH_PREFIX, TraceError,
                                     find_xplane, idle_gaps, load, self_times,
                                     short_name, union_seconds)

SCOPE = re.compile(r"fedml\.[a-z_]+(?:\.[a-z_]+)*")
NORM = re.compile(r"(?:^|[/(])(?:[A-Za-z]*Norm(?:_\d+)?|fedml\.norm)(?:[/)]|$)")
CONV_TAILS = ("conv_general_dilated", "dot_general")
SPAN_PREFIX = "fedml/"
ROUND_SPAN = "fedml/round"
OUTSIDE = "outside the program"
#: the parts of the round program, in the order they are printed
PARTS = ("prologue", "conv", "norm", "optimizer", "step_other", "aggregate")
_PART_OF = {"fedml.prologue": "prologue", "fedml.step.opt": "optimizer",
            "fedml.aggregate": "aggregate", "fedml.server": "aggregate"}


def trace_path(ctx):
    """The traced run's file, where ``run.py`` puts it, or None. (A later
    ``benchmark`` issue should pass the path in ``ctx``: see the README.)"""
    if not ctx.get("trace"):
        return None
    try:
        return find_xplane(os.path.join(ctx["spec"].root, ".bench_out",
                                        "trace", ctx["cell"]["name"]))
    except TraceError:
        return None


def own_scope(tf_op):
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def kind(meta: dict) -> str:
    """conv / norm / other, from one op's metadata."""
    tf_op = (meta.get("tf_op") or "").partition(":")[0]
    if (tf_op.endswith(CONV_TAILS)
            or "convolution" in str(meta.get("hlo_category", ""))):
        return "conv"
    return "norm" if NORM.search(tf_op) else "other"


def part(scope, knd: str) -> str:
    if scope is None:
        return "unscoped"
    if scope == "fedml.step.train":
        return knd if knd in ("conv", "norm") else "step_other"
    if scope in _PART_OF:
        return _PART_OF[scope]
    return "step_other" if scope.startswith("fedml.step") else "unscoped"


def exclusive_times(events: list) -> list:
    """``events``: ``(start, end, ...)`` sorted by ``(start, -end)``. -> per
    event the seconds in which it was the last-started event still running.
    The values sum to the union of the intervals, however siblings overlap."""
    out, stack, t = [0.0] * len(events), [], 0.0
    for i, ev in enumerate(events):
        s = ev[0]
        while stack and events[stack[-1]][1] <= s:
            top = stack.pop()
            if events[top][1] > t:
                out[top] += events[top][1] - t
                t = events[top][1]
        if stack and s > t:
            out[stack[-1]] += s - t
        t = max(t, s) if stack else s
        stack.append(i)
    while stack:
        top = stack.pop()
        if events[top][1] > t:
            out[top] += events[top][1] - t
            t = events[top][1]
    return out


def parents(events: list) -> list:
    """``events``: ``(start, end, ...)`` sorted by ``(start, -end)``. -> for
    each the index of the event it is nested in, or -1."""
    out, stack = [], []
    for i, ev in enumerate(events):
        while stack and ev[0] >= events[stack[-1]][1]:
            stack.pop()
        out.append(stack[-1] if stack else -1)
        stack.append(i)
    return out


def device_scopes(ops: list, meta: dict) -> list:
    """``ops``: ``(start, end, full name)`` of one chip, sorted by ``(start,
    -end)``. -> per op ``(self seconds, scope or None, kind)``."""
    par = parents(ops)
    selfs = exclusive_times(ops)
    per_name = {}

    def of(name):
        if name not in per_name:
            m = meta.get(name, {})
            tf_op = m.get("tf_op") or ""
            sc = own_scope(tf_op)
            if sc is None and tf_op and "/" not in tf_op:
                sc = "fedml.prologue"     # XLA's relayout of an argument
            per_name[name] = (sc, kind(m), tf_op)
        return per_name[name]

    scope = [of(n)[0] for _s, _e, n in ops]
    # a while (or any op) with no name of its own: what its body's ops name
    # before their first "/while", weighted by their time
    votes: dict = {}
    for i, (_s, _e, n) in enumerate(ops):
        p = par[i]
        while p >= 0 and scope[p] is not None:
            p = par[p]
        if p < 0:
            continue
        head, sep, _ = of(n)[2].partition("/while")
        outer = own_scope(head) if sep else None
        if outer:
            votes.setdefault(p, defaultdict(float))[outer] += selfs[i]
    for p, v in votes.items():
        scope[p] = max(v, key=v.get)
    # XLA's own copies between the program's ops (no tf_op, nested in
    # nothing) feed what follows: the next named op at their level, or the
    # last one before them
    top = [i for i, p in enumerate(par) if p < 0]
    for order in (reversed(top), top):
        near = None
        for i in order:
            if scope[i] is None and meta.get(ops[i][2], {}).get("tf_op") is None:
                scope[i] = near
            near = scope[i] or near
    out = []
    for i, (_s, _e, n) in enumerate(ops):
        sc, p = scope[i], par[i]
        while sc is None and p >= 0:
            sc, p = scope[p], par[p]
        out.append((selfs[i], sc, of(n)[1]))
    return out


def read_trace(path: str) -> dict:
    """-> device ops by plane (full names), host spans by thread."""
    profile = load(path)
    devices, threads = {}, {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    rows = devices.setdefault(
                        plane.name, {"XLA Ops": [], "XLA Modules": []})[line.name]
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        rows.append((s, s + ev.duration_ns * 1e-9, ev.name))
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                rows = [(ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events
                        if ev.name.startswith((SPAN_PREFIX, BENCH_PREFIX))]
                if rows:
                    threads[(plane.name, line.name)] = sorted(
                        rows, key=lambda v: (v[0], -v[1]))
    return {"devices": devices, "threads": threads}


def host_self_times(threads: dict) -> dict:
    """``fedml/...`` span name -> self seconds, summed over threads."""
    out = defaultdict(float)
    for rows in threads.values():
        for name, secs in self_times(
                [r for r in rows if r[2].startswith(SPAN_PREFIX)]):
            out[name] += secs
    return dict(out)


def label_gaps(gaps: list, threads: dict) -> list:
    """-> ``[(label, seconds, seconds inside a fedml/round span)]`` per gap:
    the ``fedml/...`` span whose own time (less its children's) covers most
    of the gap, or OUTSIDE when the uncovered part is larger."""
    spans = []      # (start, end, name, [children's (start, end)])
    for rows in threads.values():
        rows = [r for r in rows if r[2].startswith(SPAN_PREFIX)]
        par = parents(rows)
        kids = defaultdict(list)
        for i, p in enumerate(par):
            if p >= 0:
                kids[p].append(rows[i][:2])
        spans += [(s, e, n, kids[i]) for i, (s, e, n) in enumerate(rows)]
    spans.sort(key=lambda v: v[0])
    out = []
    for gs, ge in gaps:
        cover, in_round, in_any = defaultdict(float), [], []
        for s, e, name, kids in spans:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov <= 0:
                continue
            in_any.append((max(s, gs), min(e, ge)))
            if name == ROUND_SPAN:
                in_round.append(in_any[-1])
            cover[name] += ov - sum(max(0.0, min(ke, ge) - max(ks, gs))
                                    for ks, ke in kids)
        cover[OUTSIDE] = (ge - gs) - union_seconds(in_any)
        out.append((max(cover, key=cover.get), ge - gs,
                    union_seconds(in_round)))
    return out


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime: float):
    data = read_trace(path)
    if not data["devices"]:
        return None
    meta = opmeta.read(path)
    bench = [r for rows in data["threads"].values() for r in rows
             if r[2].startswith(BENCH_PREFIX)]
    all_ops = [o for d in data["devices"].values() for o in d["XLA Ops"]]
    if bench:
        t0, t1 = min(r[0] for r in bench), max(r[1] for r in bench)
    else:
        t0, t1 = min(o[0] for o in all_ops), max(o[1] for o in all_ops)

    def busy(dev):
        return union_seconds([(max(s, t0), min(e, t1)) for s, e, _n in
                              data["devices"][dev]["XLA Ops"]
                              if e > t0 and s < t1])

    dev = max(sorted(data["devices"]), key=busy)
    ops = sorted(((max(s, t0), min(e, t1), n)
                  for s, e, n in data["devices"][dev]["XLA Ops"]
                  if e > t0 and s < t1), key=lambda v: (v[0], -v[1]))
    table = meta.get(dev, {})
    if not any(own_scope(table.get(n, {}).get("tf_op"))
               for n in {o[2] for o in ops}):
        return None                      # a program without the names
    rows = device_scopes(ops, table)
    parts, by_scope = defaultdict(float), defaultdict(float)
    by_op = defaultdict(lambda: [0.0, None, None])
    conv_flops = conv_bytes = 0.0
    for (secs, sc, knd), (_s, _e, name) in zip(rows, ops):
        p = part(sc, knd)
        parts[p] += secs
        by_scope[sc or "unscoped"] += secs
        cell = by_op[name]
        cell[0], cell[1], cell[2] = cell[0] + secs, sc, knd
        if p == "conv":
            m = table.get(name, {})
            conv_flops += m.get("flops") or 0
            conv_bytes += m.get("bytes_accessed") or 0
    mods = defaultdict(float)
    for s, e, name in data["devices"][dev]["XLA Modules"]:
        if e > t0 and s < t1:
            mods[name.split("(")[0]] += min(e, t1) - max(s, t0)
    gaps = idle_gaps([(s, e) for s, e, _n in ops], t0, t1)
    labelled = label_gaps(gaps, data["threads"])
    idle_by = defaultdict(float)
    for label, secs, _in in labelled:
        idle_by[label] += secs
    top = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:10]
    out = {
        "device": dev, "window_s": t1 - t0,
        "busy_s": sum(parts.values()),
        "parts_s": {k: parts.get(k, 0.0) for k in PARTS + ("unscoped",)},
        "by_scope_s": dict(by_scope),
        "module_s": max(mods.values(), default=0.0),
        "conv_xla": {"flops": conv_flops, "bytes_accessed": conv_bytes},
        "host_self_s": host_self_times(data["threads"]),
        "idle_s": sum(g[1] for g in labelled),
        "idle_in_round_s": sum(g[2] for g in labelled),
        "idle_by_span_s": dict(idle_by),
        "long_gaps": [(label, secs) for label, secs, _in in labelled
                      if secs > 1e-3],
        "top_ops": [(short_name(n), secs, sc, knd,
                     table.get(n, {}).get("source"),
                     table.get(n, {}).get("tf_op"))
                    for n, (secs, sc, knd) in top],
    }
    return out


def reduce_path(path: str):
    """The reduction of one trace file (parsed once per process), or None
    where the trace has no device plane or the program no ``fedml.*`` name."""
    path = find_xplane(path)
    return _reduce_file(path, os.path.getmtime(path))


def reduce_ctx(ctx):
    """What the metric readers call: the traced run's reduction, or None.
    The call that parses the file prints it, as earlier lines of the run."""
    path = trace_path(ctx)
    if path is None:
        return None
    parsed = _reduce_file.cache_info().misses
    red = reduce_path(path)
    if red is not None and _reduce_file.cache_info().misses != parsed:
        describe(red)
    return red


def per_round_ms(ctx, seconds):
    """Seconds over the traced window -> milliseconds a round."""
    n = len(ctx["window"].rounds)
    return None if not n or seconds is None else seconds / n * 1e3


def part_ms(ctx, *names):
    """The per-layer readers' one line: device self time of the named parts
    of the round program, ms a round; None without a trace."""
    red = reduce_ctx(ctx)
    if red is None:
        return None
    return per_round_ms(ctx, sum(red["parts_s"][n] for n in names))


def host_span_ms(ctx, name: str):
    red = reduce_ctx(ctx)
    if red is None or name not in red["host_self_s"]:
        return None
    return per_round_ms(ctx, red["host_self_s"][name])


def describe(red: dict) -> None:
    def say(msg):
        print(msg, flush=True)

    parts, total = red["parts_s"], sum(red["parts_s"][k] for k in PARTS)
    say(f"scopes: {red['device']}, busy {red['busy_s']:.4f} s of "
        f"{red['window_s']:.4f} s; round program (module line) "
        f"{red['module_s']:.4f} s; the six parts sum to {total:.4f} s "
        f"({100.0 * total / red['module_s'] if red['module_s'] else 0:.2f}% "
        f"of it), unscoped {parts['unscoped']:.4f} s")
    say("scopes: parts  " + "  ".join(
        f"{k} {parts[k]:.4f} s" for k in PARTS + ("unscoped",)))
    say("scopes: by scope  " + "  ".join(
        f"{k} {v:.4f}" for k, v in sorted(red["by_scope_s"].items(),
                                          key=lambda kv: -kv[1])))
    for name, secs, sc, knd, source, tf_op in red["top_ops"]:
        say(f"scopes: op {secs:.4f} s  {name}  scope {sc}  kind {knd}  "
            f"source {source}  tf_op {tf_op}")
    say(f"scopes: convolutions by XLA's own count: "
        f"{red['conv_xla']['flops']:.6g} FLOPs, "
        f"{red['conv_xla']['bytes_accessed']:.6g} bytes accessed")
    say("scopes: host self time  " + "  ".join(
        f"{k} {v * 1e3:.3f} ms" for k, v in sorted(red["host_self_s"].items())))
    say(f"scopes: device idle {red['idle_s'] * 1e3:.3f} ms, "
        f"{red['idle_in_round_s'] * 1e3:.3f} ms of it inside {ROUND_SPAN}; "
        "by span  " + "  ".join(
            f"{k} {v * 1e3:.3f} ms" for k, v in sorted(
                red["idle_by_span_s"].items(), key=lambda kv: -kv[1])))
    for label, secs in red["long_gaps"]:
        say(f"scopes: idle gap {secs * 1e3:.3f} ms: {label}")


if __name__ == "__main__":
    _red = reduce_path(sys.argv[1])
    if _red is None:
        print("no device plane, or no fedml.* scope in the trace")
    else:
        describe(_red)
