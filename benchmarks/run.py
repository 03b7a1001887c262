#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails at once (exit 3, no result) unless JAX finds exactly the cell's chips
as TPUs; selects no platform itself. The last line of standard output is the
result, one JSON object; everything else the run has to say is on earlier
lines. See ``benchmarks/README.md``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is counted from here

import argparse                      # noqa: E402
import gc                            # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.harness import check, loop  # noqa: E402
from benchmarks.harness.device import (memory_brief, memory_report,  # noqa: E402
                                       memory_split, require_tpu, stamp)
from benchmarks.harness.spec import Spec  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


class Clock:
    """Seconds of each named stage of set-up, in order."""

    def __init__(self, t0: float):
        self.last, self.stages = t0, []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.stages.append((name, now - self.last))
        self.last = now


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, spec: Spec = None, device_check=require_tpu,
         t_start: float = None) -> int:
    """``spec`` and ``device_check`` are for the tests (a tiny cell on the
    CPU); the command line has no such option."""
    args = parse(argv)
    t_start = _T_START if t_start is None else t_start
    clock = Clock(t_start)
    spec = spec or Spec()
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    ref = spec.module("references", config["reference"])
    generator = spec.module("traffic", config["generator"])

    import jax
    import numpy as np

    devices = device_check(int(cell["chips"]))
    from fedml_tpu.utils.compile_cache import (count_cache_events,
                                               enable_compile_cache)

    cache_dir = enable_compile_cache()
    events = count_cache_events()
    compiles = {"n": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.__setitem__(
            "n", compiles["n"] + (
                event == "/jax/core/compile/backend_compile_duration")))
    say(f"device: {stamp(devices)}  jax {jax.__version__}  "
        f"compile cache: {cache_dir}")
    clock.mark("import")

    from benchmarks.harness import protocol
    from benchmarks.harness.cell import build_api, seed_program

    dataset, rows = generator.make(config, cell, args.seed)
    clock.mark("data")
    api = build_api(config, cell, dataset)
    init_host = seed_program(api, ref, config, args.seed)
    clock.mark("place")
    say(f"device memory after place: {memory_brief(devices)}")

    # warm-up: every round index the window will replay, once, from the
    # seeded weights; the first ``check_rounds`` of them are the rounds the
    # reference follows afterwards
    rounds_spec = cell["rounds"]
    n_check = int(cell["check_rounds"])
    n_warm = int(rounds_spec.get("cycle") or max(n_check, 2))
    first = int(rounds_spec["first"])
    warm = list(range(first, first + n_warm))
    before = dict(events)
    prog_losses, prog_states = [], []
    for r in warm:
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(api.run_round(r)))
        say(f"warm-up round {r}: {time.perf_counter() - t0:.2f} s  "
            f"loss {loss:.6g}  device memory: {memory_brief(devices)}")
        if len(prog_states) < n_check:
            prog_losses.append(loss)
            prog_states.append(jax.device_get(api.variables))
    built = {k: events[k] - before[k] for k in events}
    programs_built = compiles["n"]
    # tracing leaves millions of objects behind; collect them here and take
    # the survivors out of the collector's sight, so that no full
    # collection of set-up's garbage lands in the window
    gc.collect()
    gc.freeze()
    clock.mark("warm-up")
    setup_s = clock.last - t_start

    # -- the window ---------------------------------------------------------
    annotate, trace_dir = None, None
    max_rounds = None
    if args.trace:
        trace_dir = os.path.join(_ROOT, ".bench_out", "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        max_rounds = int(cell["trace_rounds"])
        annotate = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    stage_rows = getattr(api, "_stage_rows", None)
    if stage_rows is not None:
        stage_rows.clear()
    compiles_before = compiles["n"]
    indices = loop.round_indices(rounds_spec, skip=n_warm)
    try:
        window = loop.run_window(api.run_round, indices, args.seconds,
                                 max_rounds=max_rounds, annotate=annotate)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    compiles_in_window = compiles["n"] - compiles_before
    live, scratch = memory_split(devices)
    peak = live + scratch
    memory_line = memory_report(devices)
    stage_rows = list(stage_rows or [])
    api.close()

    # what the window trained, by the program's own plan and by the
    # benchmark's count of the same cohorts
    real = padded = own_real = 0
    counts_memo: dict = {}
    all_counts = np.asarray(dataset.train_counts)
    n_round = int(cell["fed_config"]["client_num_per_round"])
    for r, *_ in window.rounds:
        if r not in counts_memo:
            ids = protocol.sample_cohort(r, int(cell["clients"]), n_round,
                                         int(cell["sampling_seed"]))
            counts_memo[r] = (*api.round_counts(r), int(all_counts[ids].sum()))
        a, b, c = counts_memo[r]
        real, padded, own_real = real + a, padded + b, own_real + c

    # -- the output check, with the program's state freed -------------------
    del api, dataset
    gc.collect()
    verdict = check.run(ref, config, cell, rows, init_host, args.seed,
                        warm[:n_check], prog_losses, prog_states)
    window_ok = [
        ("compiles_in_window", compiles_in_window, 0),
        ("failed_rounds", window.failed, 0),
        ("real_samples_vs_own_count", abs(real - own_real), 0)]

    # -- earlier lines -------------------------------------------------------
    say("set-up: " + "  ".join(f"{n} {s:.2f} s" for n, s in clock.stages)
        + f"  (of it JAX trace {built['trace_secs']:.2f} + lower "
          f"{built['lower_secs']:.2f} + compile-or-read "
          f"{built['compile_secs']:.2f} s)")
    say(memory_line)
    say(f"compile cache: {built['requests']} request(s), {built['hits']} "
        f"hit(s), {built['misses']} miss(es); programs built in set-up "
        f"{programs_built}, in the window {compiles_in_window}")
    gaps = window.gaps_ms()
    if gaps:
        say("round gaps (ms): " + "  ".join(
            f"p{q} {loop.percentile(gaps, q):.2f}" for q in (10, 50, 90, 99))
            + f"  max {max(gaps):.2f}")
    say(f"window: {window.elapsed:.3f} s, {len(window.rounds)} round(s) "
        f"completed of {window.attempted} dispatched, {window.failed} failed; "
        f"{len(gaps)} round gap(s) for the percentile; real samples {real} "
        f"(own count {own_real}), padded {padded}")
    if len(gaps) > 1:
        # the longest gaps, split: between the completions of rounds i and
        # i+1 the host dispatches round i+2 and then blocks on round i+1
        rs = window.rounds
        worst = sorted(range(len(gaps) - 1), key=lambda i: -gaps[i])[:3]
        say("longest gaps: " + "; ".join(
            f"{gaps[i]:.0f} ms (dispatch {(rs[i + 2][2] - rs[i + 2][1]) * 1e3:.0f}"
            f" ms, blocked {(rs[i + 1][3] - rs[i + 2][2]) * 1e3:.0f} ms)"
            for i in worst))
    if stage_rows:
        waits = [row["wait_ms"] for row in stage_rows]
        say(f"prefetcher: the loop waited for inputs {sum(waits) / len(waits):.2f} "
            f"ms a round on average, {max(waits):.2f} ms at most "
            f"({len(waits)} rounds)")
    for name, value, limit in window_ok:
        say(f"check {name}: {value} (limit {limit}) "
            f"{'ok' if value <= limit else 'FAILED'}")
    for name, value, limit, ok, note in verdict["numbers"]:
        judged = ("(no limit: not judged)" if limit is None
                  else f"(limit {limit:g}) {'ok' if ok else 'FAILED'}")
        say(f"check {name}: {value:.6g} {judged}  [{note}]")
    say(f"check_s: {verdict['check_s']:.2f}")
    correct = bool(verdict["ok"] and all(v <= lim for _n, v, lim in window_ok))

    # -- the result -----------------------------------------------------------
    ctx = {
        "spec": spec, "cell": cell, "config": config, "window": window,
        "setup_s": setup_s, "peak_bytes": peak, "live_bytes": live,
        "scratch_bytes": scratch, "real_samples": real,
        "padded_samples": padded, "stage_rows": stage_rows,
        "compile_events": built, "programs_built": programs_built,
        "devices": stamp(devices), "trace": None,
    }
    device = dict(stamp(devices), memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed}
    if args.trace:
        from benchmarks.trace import reduce as trace_reduce

        t0 = time.perf_counter()
        ctx["trace"] = trace_reduce.reduce_dir(trace_dir, len(devices))
        say(f"trace reduced in {time.perf_counter() - t0:.2f} s")
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = ctx["trace"]["breakdown"]
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in spec.metric_entries(section, args.workload):
        value = spec.module("metrics", entry["name"]).read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    result.update(metrics=metrics, device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
