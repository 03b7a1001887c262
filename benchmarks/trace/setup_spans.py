"""Set-up by named part, from the program's own always-on set-up log.

The program keeps one bounded in-memory log of its set-up (``fedml_tpu.obs.
setup_log()``; names in ``fedml_tpu/obs/tracer.py``): a record for each
``fedml/setup/*`` span of a round driver's constructor, for both intervals of
every program build (``fedml/round/build``, ``phase=construct|first_call``)
and for each of the compiler's own events (``fedml/build/lower``,
``fedml/build/load`` with JAX's ``fun_name``, ``cache=hit|miss|none`` and,
where the program's code asked for the compile, ``by=<module>:<function>``),
each with its parent: the set-up span open on the same thread when it
started. Start and end are on ``time.perf_counter``, the clock of ``run.py``'s
``Clock`` and of ``harness/loop.Window``, so this module keeps what ENDED
BEFORE ``ctx["window"].t0`` (the check's reference compiles come after it)
and lays the parts beside the ``set-up:`` line's four marks:

- a span's seconds are its record's; ``fedml/setup/api`` nests inside itself
  where a subclass constructor wraps its base's, so spans are counted by
  their outermost record of a name;
- a build's Python trace is the SELF time of its ``first_call`` record: its
  seconds less the union of the records inside it (JAX's own
  ``jaxpr_trace_duration`` fires for every inner ``jit`` inside the outer
  one's interval and cannot be summed);
- a compile under a ``first_call`` is the round program's; one under another
  set-up span, or asked for by the program's code outside any (an eager op
  of ``run_round`` is a program), is a HELPER program; one with neither is
  the caller's (the benchmark's ``jit(ref.init)``).

The log is the PROCESS's, and ``run.py`` is one run a process: every record
that ended before the window counts (a test that makes several runs in one
process hands ``reduce`` its own run's records).

``summary(ctx)`` computes all of it once a run, prints one table on earlier
lines, and hands the eight readers under ``benchmarks/metrics/`` their
numbers. On a program without the log (the parent of the PR that added it)
it is None and every reader reports nothing.
"""

from __future__ import annotations

from collections import Counter, defaultdict

API = "fedml/setup/api"
INIT = "fedml/setup/init_variables"
LOCAL_TRAIN = "fedml/setup/local_train"
PLACE = "fedml/setup/place_data"
BUILD = "fedml/round/build"
LOWER = "fedml/build/lower"
LOAD = "fedml/build/load"
_KEY = "_setup_spans"


def fetch():
    """(records, dropped) of the program's set-up log, or None where the
    program has none."""
    try:
        from fedml_tpu.obs import setup_log
    except ImportError:
        return None
    log = setup_log()
    return log.records(), log.dropped


def union_seconds(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def reduce(records, dropped: int, t_cut: float) -> dict:
    """The records that ended by ``t_cut``, by part (the module docstring)."""
    kept = [r for r in records if r.t1 <= t_cut]
    by_id = {r.id: r for r in kept}
    children = defaultdict(list)
    for r in kept:
        children[r.parent].append(r)

    def ancestors(r):
        while r.parent in by_id:
            r = by_id[r.parent]
            yield r

    def outermost(name):
        return [r for r in kept if r.name == name
                and not any(a.name == name for a in ancestors(r))]

    def first_call_above(r):
        return next((a for a in ancestors(r) if a.name == BUILD
                     and a.ids.get("phase") == "first_call"), None)

    programs = []
    for r in kept:
        if r.name != BUILD or r.ids.get("phase") != "first_call":
            continue
        inside = children[r.id]
        lower = [c for c in inside if c.name == LOWER]
        load = [c for c in inside if c.name == LOAD]
        main = max(load, key=lambda c: c.seconds, default=None)
        programs.append({
            "program": r.ids.get("program"), "shape_key": r.ids.get("shape_key"),
            "first_call_s": r.seconds,
            "trace_s": r.seconds - union_seconds(
                (max(c.t0, r.t0), min(c.t1, r.t1)) for c in inside),
            "lower_s": sum(c.seconds for c in lower),
            "load_s": sum(c.seconds for c in load),
            "loads": len(load),
            "cache": main.ids.get("cache") if main else None,
            "fun_name": main.ids.get("fun_name") if main else None})
    constructs = [r for r in kept if r.name == BUILD
                  and r.ids.get("phase") == "construct"]

    def is_helper(r):
        return r.parent in by_id or "by" in r.ids

    compiles = [r for r in kept if r.name in (LOWER, LOAD)
                and first_call_above(r) is None]
    helpers = [r for r in compiles if is_helper(r)]
    callers = [r for r in compiles if not is_helper(r)]

    def span_rows(name):
        rows = []
        for r in outermost(name):
            under = [h for h in helpers if r in ancestors(h)]
            rows.append({"ids": r.ids, "seconds": r.seconds,
                         "helper_programs": sum(h.name == LOAD for h in under),
                         "helper_s": sum(h.seconds for h in under)})
        return rows

    spans = {name: span_rows(name)
             for name in (API, INIT, LOCAL_TRAIN, PLACE)}

    def seconds(name):
        return sum(row["seconds"] for row in spans[name])

    return {
        "records": len(kept), "dropped": dropped, "spans": spans,
        "programs": programs,
        "construct_s": sum(r.seconds for r in constructs),
        "helpers": helpers, "callers": callers,
        "helper_in_api_s": sum(row["helper_s"] for row in spans[API]),
        "metrics": {
            "api_init_s": seconds(API),
            "init_variables_s": seconds(INIT),
            "place_data_s": seconds(PLACE),
            "round_trace_s": sum(p["trace_s"] for p in programs),
            "round_lower_s": sum(p["lower_s"] for p in programs),
            "round_load_s": sum(p["load_s"] for p in programs),
            "helper_programs_built": sum(h.name == LOAD for h in helpers),
            "helper_build_s": sum(h.seconds for h in helpers)}}


def _ids(ids: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in ids.items())


def _by_name(compiles, key: str, top: int = 16) -> str:
    """``<name> x<programs> <seconds> s`` of the costliest ``top`` names."""
    count, secs = Counter(), Counter()
    for c in compiles:
        name = c.ids.get(key) or "(the caller)"
        count[name] += c.name == LOAD
        secs[name] += c.seconds
    rows = sorted(secs, key=lambda n: -secs[n])
    more = f"; {len(rows) - top} more" if len(rows) > top else ""
    return "; ".join(f"{n} x{count[n]} {secs[n]:.3f} s"
                     for n in rows[:top]) + more


def describe(cell: str, red: dict, say=print) -> None:
    m = red["metrics"]
    say(f"set-up spans: {cell}: {red['records']} record(s) ended before the "
        f"window, {red['dropped']} dropped off the log")
    for name, rows in red["spans"].items():
        for row in rows:
            say(f"set-up spans: span {name} {row['seconds']:.3f} s  "
                f"{_ids(row['ids'])}  helper programs under it "
                f"{row['helper_programs']} ({row['helper_s']:.3f} s)")
    say(f"set-up spans: programs constructed in {red['construct_s']:.3f} s")
    for p in red["programs"]:
        say(f"set-up spans: program {p['program']} {p['shape_key']}: first "
            f"call {p['first_call_s']:.3f} s = trace {p['trace_s']:.3f} + "
            f"lower {p['lower_s']:.3f} + load {p['load_s']:.3f} "
            f"({p['loads']} program(s); {p['fun_name']} cache "
            f"{p['cache']})")
    helpers, callers = red["helpers"], red["callers"]
    say(f"set-up spans: helper programs {m['helper_programs_built']:.0f}, "
        f"lower + load {m['helper_build_s']:.3f} s (inside fedml/setup/api "
        f"{red['helper_in_api_s']:.3f} s), cache "
        + _ids(Counter(h.ids["cache"] for h in helpers if h.name == LOAD)))
    if helpers:
        say("set-up spans: helpers by asker: " + _by_name(helpers, "by"))
        say("set-up spans: helpers by name: " + _by_name(helpers, "fun_name"))
    say(f"set-up spans: the caller's own compiles "
        f"{sum(c.name == LOAD for c in callers)}, lower + load "
        f"{sum(c.seconds for c in callers):.3f} s"
        + (": " + _by_name(callers, "fun_name") if callers else ""))


def summary(ctx):
    """The run's set-up by part (``reduce``'s dict), computed and printed
    once a run; None on a program without the log."""
    if _KEY not in ctx:
        got = fetch()
        ctx[_KEY] = None
        if got is not None:
            ctx[_KEY] = reduce(*got, t_cut=ctx["window"].t0)
            describe(ctx["cell"]["name"], ctx[_KEY],
                     say=lambda msg: print(msg, flush=True))
    return ctx[_KEY]


def metric(ctx, name: str):
    red = summary(ctx)
    return None if red is None else red["metrics"][name]
