"""From a profiler trace (``.xplane.pb``) to numbers, with ``jax.profiler.
ProfileData`` and nothing else.

What is read, per device plane (``/device:TPU:<n>``):

- line ``XLA Ops``: every operation that ran on the chip. Busy time is the
  union of their intervals; an operation's *self* time is its duration less
  the operations nested in it (a ``while`` spans its body's ops), so sums by
  name count nothing twice. The events carry no category: a collective is
  known by its instruction's name (``%all-reduce.3``); a convolution is not
  (XLA names its conv fusions ``%multiply_add_fusion.N`` or ``%fusion.N``),
  so nothing here claims to know the convolutions' time;
- line ``XLA Modules``: one event per execution of a jitted module.

and from the host plane the benchmark's own spans (``TraceAnnotation`` named
``bench/...``), which are on the same clock: the traced window runs from the
first span's start to the last span's end, and each long idle gap of the
device is named by the span that covers most of it.

A trace with no device plane (the CPU, in the tests) is read the same way
from the host threads' events that carry an ``hlo_op`` stat, as one device.

``python benchmarks/trace/reduce.py <dir-or-file>`` prints what a trace
holds, for reading one by hand.
"""

from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict

SPAN_PREFIX = "bench/"
#: an op is a cross-chip collective when its instruction's name has one of
COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")


class TraceError(RuntimeError):
    pass


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def union_seconds(intervals: list) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: list, t0: float, t1: float) -> list:
    """``(start, end)`` of every stretch of ``[t0, t1]`` no interval covers."""
    gaps, at = [], t0
    for s, e in sorted(intervals):
        if e <= t0:
            continue
        if s >= t1:
            break
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def self_times(events: list) -> list:
    """``events``: ``(start, end, name)``. Returns ``(name, self_seconds)``
    with nested events' time taken out of the event that contains them."""
    out, stack = [], []         # stack of [end, name, self]
    for s, e, name in sorted(events, key=lambda v: (v[0], -v[1])):
        while stack and s >= stack[-1][0]:
            out.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][2] -= (min(e, stack[-1][0]) - s)
        stack.append([e, name, e - s])
    while stack:
        out.append(tuple(stack.pop()[1:]))
    return out


def short_name(name: str) -> str:
    """The TPU's op events are named by their whole HLO instruction
    (``%fusion.119 = f32[...] fusion(...), kind=kOutput, ...``): keep the
    instruction's name and, for a fusion, its kind."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    kind = rest.rpartition("kind=")[2].split(",")[0] if "kind=" in rest else ""
    return f"{head} ({kind})" if kind else head


def is_collective(name: str) -> bool:
    return any(k in name for k in COLLECTIVE)


def read_planes(profile) -> dict:
    """-> {"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [(start, end, name)]}; times in seconds."""
    devices, spans, host_ops = {}, [], []
    for plane in profile.planes:
        is_dev = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                rows = devices.setdefault(plane.name, {"ops": [], "modules": []})[key]
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    rows.append((s, s + ev.duration_ns * 1e-9,
                                 short_name(ev.name)))
            elif not is_dev:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
                    elif ev.duration_ns > 0:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            s = ev.start_ns * 1e-9
                            host_ops.append((s, s + ev.duration_ns * 1e-9,
                                             ev.name,
                                             str(stats.get("hlo_module", ""))))
    if not devices and host_ops:
        mods = defaultdict(lambda: [float("inf"), 0.0])
        for s, e, _n, m in host_ops:
            mods[m][0], mods[m][1] = min(mods[m][0], s), max(mods[m][1], e)
        devices["/host:XLA"] = {
            "ops": [v[:3] for v in host_ops],
            "modules": [(s, e, m) for m, (s, e) in mods.items()]}
    return {"devices": devices, "spans": sorted(spans)}


def reduce_profile(profile, n_devices: int) -> dict:
    data = read_planes(profile)
    devices, spans = data["devices"], data["spans"]
    if not devices:
        raise TraceError("the trace holds no device operation")
    if len(devices) != n_devices:
        raise TraceError(f"the trace holds {len(devices)} device plane(s), "
                         f"the run used {n_devices}")
    all_ops = [o for d in devices.values() for o in d["ops"]]
    if spans:
        t0, t1 = spans[0][0], max(e for _s, e, _n in spans)
    else:
        t0, t1 = min(o[0] for o in all_ops), max(o[1] for o in all_ops)
    window = t1 - t0
    busy, coll, modules, by_name = [], [], [], []
    for dev in sorted(devices):
        ops = [o for o in devices[dev]["ops"] if o[1] > t0 and o[0] < t1]
        clipped = [(max(s, t0), min(e, t1)) for s, e, _n in ops]
        b = union_seconds(clipped)
        if b > window * 1.05:
            raise TraceError(f"{dev}: busy {b:.4f} s is over 105% of the "
                             f"window {window:.4f} s")
        busy.append(b)
        names = defaultdict(float)
        for name, secs in self_times(ops):
            names[name] += secs
        coll.append(sum(v for k, v in names.items() if is_collective(k)))
        by_name.append(names)
        mods = defaultdict(float)
        for s, e, name in devices[dev]["modules"]:
            if e > t0 and s < t1:
                mods[name.split("(")[0]] += min(e, t1) - max(s, t0)
        modules.append(mods)
    top = max(range(len(busy)), key=lambda i: busy[i])
    dev0 = sorted(devices)[top]
    gaps = idle_gaps([(s, e) for s, e, _n in devices[dev0]["ops"]], t0, t1)
    labelled = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover = defaultdict(float)
        for s, e, name in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                cover[name] += ov
        label = (max(cover, key=cover.get) if cover
                 else "outside the benchmark's spans")
        labelled.append([label, ge - gs])
    mod_tot = defaultdict(float)
    for mods in modules:
        for name, secs in mods.items():
            mod_tot[name] = max(mod_tot[name], secs)
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "busy_by_device": busy,
        "collective_s": max(coll) if n_devices > 1 else None,
        "modules": sorted(mod_tot.items(), key=lambda kv: -kv[1]),
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                by_name[top].items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": labelled},
    }


def reduce_dir(path: str, n_devices: int) -> dict:
    return reduce_profile(load(path), n_devices)


def describe(path: str, top: int = 25) -> None:
    """Print planes, lines, event counts and the heaviest events with their
    stats: what to read before trusting the reduction on a new device."""
    profile = load(path)
    for plane in profile.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            tot = sum(e.duration_ns for e in events) * 1e-9
            print(f"  LINE {line.name!r}: {len(events)} events, {tot:.4f} s")
            agg = defaultdict(lambda: [0, 0.0, None])
            for e in events:
                a = agg[e.name]
                a[0] += 1
                a[1] += e.duration_ns * 1e-9
                if a[2] is None:
                    a[2] = {k: (v if len(str(v)) < 80 else str(v)[:80] + "...")
                            for k, v in dict(e.stats).items()}
            for name, (n, secs, stats) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"    {secs:10.6f} s  x{n:<6} {name[:70]}  {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
