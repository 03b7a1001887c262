#!/usr/bin/env python
"""bench_report: the BENCH_r*.json series as a trajectory + regression gate.

Driver-captured bench artifacts need a tool that reads them — a
throughput or MFU regression between PRs would otherwise ship silently.
This tool parses the series (each artifact's ``tail`` field
holds the one-line bench JSON; the pre-parsed ``parsed`` key is the
fallback) into a per-round trajectory table of the headline metrics:

    python tools/bench_report.py BENCH_r*.json
    python tools/bench_report.py --dir .          # same, globbed
    python tools/bench_report.py --dir . --check  # gate mode (tier-1 smoke)

and applies thresholded regression detection: for each tracked metric, the
LAST artifact that carries it is compared against the PREVIOUS artifact
that carries it; a drop of more than ``--threshold`` (default 10%) is a
regression. Metrics appear and disappear across the series (mfu starts at
r02, crossdevice at r05) — comparison only ever pairs artifacts where the
metric is present. Trajectory-only columns (the fedsketch p99 train-ms /
staleness tails, which are lower-is-better) render in the table but never
feed the gate.

Since r06 every artifact carries a ``host_basis`` stamp (device, cpu
count, flagship model): throughput is only comparable on the same basis,
so the gate pairs consecutive artifacts ONLY when their bases match — a
bench captured on a different container re-bases the trajectory (noted on
stderr, exit 0) instead of reading as a 16,000x "regression". Artifacts
without the stamp (r01-r05, taken before PR 1 on an installation that no
longer exists and since deleted from the repo) form their own legacy
lineage and keep gating against each other.

Exit codes: 0 trajectory clean; 1 regression(s) detected (listed on
stderr); 2 nothing to analyze — no artifacts, or none parseable.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

def _sketch(j: dict, lane: str, q: str):
    """Missing-key-tolerant reach into the tail's fedsketch block (the
    flagship profiler aggregates); r01-r05 artifacts predate it -> None."""
    return (((j.get("profiler") or {}).get("sketches") or {})
            .get(lane) or {}).get(q)


def _lens(j: dict, lane: str, q: str):
    """Missing-key-tolerant reach into the tail's fedlens block (bench.py
    arms the lens for the measured pass); falls back to the profiler
    sketch lanes, None on pre-lens artifacts (r01-r07) -> "-"."""
    v = ((j.get("lens") or {}).get(lane) or {}).get(q)
    return v if v is not None else _sketch(j, lane, q)


#: metric -> (extractor over the bench JSON, short label, gated). Gated
#: metrics are higher-is-better; regression = relative drop beyond the
#: threshold. gated=False rows are TRAJECTORY-ONLY columns (the fedsketch
#: latency/staleness tails are lower-is-better, so a drop-based gate would
#: invert their meaning — they render for the reader, never flake the gate).
METRICS = {
    "img_per_sec": (lambda j: j.get("value"), "flagship img/s", True),
    "vs_baseline": (lambda j: j.get("vs_baseline"), "vs_baseline", True),
    "mfu": (lambda j: j.get("mfu"), "mfu", True),
    "crosssilo_img_per_sec": (
        lambda j: (j.get("crosssilo") or {}).get("images_per_sec"),
        "cross-silo img/s", True),
    "clients_per_sec": (
        lambda j: (j.get("crossdevice") or {}).get("clients_per_sec"),
        "cross-device clients/s", True),
    # MAC-basis MFU over the fedcost lane ceiling (in the tail since the
    # PR-6 roofline block): the schedule-quality headline — a drop means
    # the round program stopped filling the lanes the model shapes allow
    "mfu_vs_lane_ceiling": (
        lambda j: j.get("mfu_vs_lane_ceiling"), "mfu/ceiling", True),
    # fedsketch distribution tails from the profiler block (ISSUE 10):
    # per-client p99 train-ms and the p99 rounds-behind staleness spread
    "p99_train_ms": (
        lambda j: _sketch(j, "train_ms", "p99"), "p99 train-ms", False),
    "p99_staleness": (
        lambda j: _sketch(j, "staleness", "p99"), "p99 staleness", False),
    # fedbuff (ISSUE 14): the async-vs-sync A/B under injected stragglers.
    # async clients/s is higher-is-better and gates like the sync column;
    # version-lag p99 is the staleness trajectory — context, never gated
    # (a lag change reads with the buffer_k/delay context, not as a
    # regression). Absent on pre-ISSUE-14 artifacts (chained .get()s
    # return None; missing keys never flake the gate).
    "fedbuff_async_clients_per_sec": (
        lambda j: ((j.get("crossdevice") or {}).get("fedbuff") or {})
        .get("async_clients_per_sec"),
        "async clients/s", True),
    "fedbuff_version_lag_p99": (
        lambda j: ((j.get("crossdevice") or {}).get("fedbuff") or {})
        .get("version_lag_p99"),
        "version lag p99", False),
    # fedgate (ISSUE 16): the multi-tenant gateway block at its top tenant
    # count. Per-tenant rounds/s is higher-is-better and gates; the p99
    # upload latency a healthy tenant saw under the noisy neighbor and the
    # flow-control push-back count (busy + shed) are trajectory context —
    # a latency/shed change reads with the cap/tenant-count context, never
    # as a bare regression. Absent on pre-ISSUE-16 artifacts (chained
    # .get()s return None; missing keys never flake the gate).
    "gateway_rounds_per_sec": (
        lambda j: ((j.get("crossdevice") or {}).get("gateway") or {})
        .get("rounds_per_sec_per_tenant"),
        "gw rounds/s", True),
    "gateway_upload_p99": (
        lambda j: ((j.get("crossdevice") or {}).get("gateway") or {})
        .get("healthy_upload_p99_ms"),
        "gw upload p99", False),
    "gateway_pushback": (
        lambda j: (lambda g: (g.get("busy_sent", 0) + g.get("shed_stale", 0))
                   if g else None)(
            (j.get("crossdevice") or {}).get("gateway")),
        "gw busy+shed", False),
    # fedlens (ISSUE 20): the learning-signal distribution tails at the
    # flagship operating point — p99 raw-update norm, p99 drift (1 -
    # cosine vs the round aggregate; higher = clients pulling against
    # it). Both read with the data-heterogeneity/lr context, never as a
    # bare regression — trajectory-only. Absent on r01-r07 artifacts
    # (chained .get()s return None -> "-"; missing keys never flake the
    # gate).
    "lens_update_norm_p99": (
        lambda j: _lens(j, "update_norm", "p99"), "p99 update norm", False),
    "lens_drift_p99": (
        lambda j: _lens(j, "drift", "p99"), "drift p99", False),
    # fedsched (ISSUE 13): the cross-device block's cohort size and cohort
    # policy — context columns for the clients/s trajectory (the r06 jump
    # reads as "1000-client scheduled cohorts", not as free speed). Absent
    # on r01-r05 artifacts; `policy` is a STRING column (trajectory-only —
    # strings never reach the drop gate). They stay LAST: the
    # committed-series golden pins the r06 row ending on its policy string.
    "xdev_cohort": (
        lambda j: (j.get("crossdevice") or {}).get("clients_per_round"),
        "cohort size", False),
    "xdev_policy": (
        lambda j: (j.get("crossdevice") or {}).get("policy"),
        "policy", False),
}

_RUN_RE = re.compile(r"BENCH_r(\d+)\.json$")


def parse_artifact(path: str):
    """One BENCH artifact -> (run number, bench-JSON dict) or None when the
    file is unreadable/malformed. The authoritative source is the LAST
    JSON line of the ``tail`` field (the bench's own stdout as the driver
    captured it); ``parsed`` is accepted as fallback."""
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(art, dict):
        return None
    n = art.get("n")
    if n is None:
        m = _RUN_RE.search(os.path.basename(path))
        n = int(m.group(1)) if m else None
    bench = None
    tail = art.get("tail")
    if isinstance(tail, str):
        for line in tail.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(cand, dict) and "metric" in cand:
                    bench = cand   # last JSON line wins
    if bench is None and isinstance(art.get("parsed"), dict):
        bench = art["parsed"]
    if bench is None or n is None:
        return None
    return int(n), bench


def load_series(paths: list[str]) -> list[dict]:
    """Parse and order the artifact series by run number."""
    rows = []
    for p in paths:
        parsed = parse_artifact(p)
        if parsed is None:
            print(f"bench_report: skipping unparseable {p}", file=sys.stderr)
            continue
        n, bench = parsed
        row = {"n": n, "path": os.path.basename(p)}
        # the comparability stamp (None on pre-r06 artifacts — the legacy
        # lineage); kept off the METRICS table, used only by the gate
        hb = bench.get("host_basis")
        row["_basis"] = (json.dumps(hb, sort_keys=True)
                         if isinstance(hb, dict) else None)
        for key, (fn, _label, _gated) in METRICS.items():
            try:
                v = fn(bench)
            except Exception:
                v = None
            # numbers feed the gate; strings (e.g. the policy column) are
            # trajectory-only annotations; anything else renders as absent
            row[key] = (float(v) if isinstance(v, (int, float))
                        else v if isinstance(v, str) else None)
        rows.append(row)
    rows.sort(key=lambda r: r["n"])
    return rows


def detect_regressions(rows: list[dict], threshold: float) -> list[str]:
    """Last-present vs previous-present comparison per metric, paired only
    within one host basis (module docstring). Returns regressions; basis
    breaks are reported as notes on stderr, never as failures."""
    regressions = []
    rebased = set()
    for key, (_fn, label, gated) in METRICS.items():
        if not gated:
            continue
        present = [(r["n"], r[key], r.get("_basis")) for r in rows
                   if isinstance(r[key], float)]
        if len(present) < 2:
            continue
        (prev_n, prev, prev_b), (last_n, last, last_b) = \
            present[-2], present[-1]
        if prev_b != last_b:
            rebased.add((prev_n, last_n))
            continue
        if prev <= 0:
            continue
        drop = 1.0 - last / prev
        if drop > threshold:
            regressions.append(
                f"{label}: r{last_n:02d} {last:g} is {drop:.1%} below "
                f"r{prev_n:02d} {prev:g} (threshold {threshold:.0%})")
    for prev_n, last_n in sorted(rebased):
        print(f"bench_report: r{last_n:02d} runs on a different host basis "
              f"than r{prev_n:02d} — trajectory re-based, not gated",
              file=sys.stderr)
    return regressions


def format_table(rows: list[dict]) -> str:
    heads = ["run"] + [label for _k, (_f, label, _g) in METRICS.items()]
    widths = [max(len(h), 10) for h in heads]
    out = ["  ".join(h.rjust(w) for h, w in zip(heads, widths))]
    for r in rows:
        cells = [f"r{r['n']:02d}"]
        for key in METRICS:
            v = r[key]
            cells.append("-" if v is None
                         else v if isinstance(v, str) else f"{v:g}")
        out.append("  ".join(c.rjust(w) for c, w in zip(cells, widths)))
    # per-metric delta line: last vs previous present value (numeric only)
    deltas = ["delta"]
    for key in METRICS:
        present = [r[key] for r in rows if isinstance(r[key], float)]
        if len(present) < 2 or present[-2] == 0:
            deltas.append("-")
        else:
            deltas.append(f"{present[-1] / present[-2] - 1.0:+.1%}")
    out.append("  ".join(c.rjust(w) for c, w in zip(deltas, widths)))
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("artifacts", nargs="*",
                    help="BENCH_r*.json files (or use --dir)")
    ap.add_argument("--dir", help="glob BENCH_r*.json under this directory")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative drop that counts as a regression "
                         "(default 0.10)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: one summary line instead of the table "
                         "(same exit codes)")
    args = ap.parse_args(argv)

    paths = list(args.artifacts)
    if args.dir:
        paths.extend(sorted(glob.glob(os.path.join(args.dir,
                                                   "BENCH_r*.json"))))
    # positional args overlapping --dir must not list an artifact twice:
    # a duplicate pairs a run against itself in the last-vs-previous
    # comparison and masks a real regression
    paths = list(dict.fromkeys(os.path.abspath(p) for p in paths))
    if not paths:
        print("bench_report: no artifacts given (pass files or --dir)",
              file=sys.stderr)
        return 2
    rows = load_series(paths)
    if not rows:
        print("bench_report: no parseable bench artifacts", file=sys.stderr)
        return 2
    regressions = detect_regressions(rows, args.threshold)
    if args.json:
        print(json.dumps({"trajectory": rows, "regressions": regressions},
                         indent=2))
    elif args.check:
        print(f"bench trajectory: {len(rows)} artifact(s) "
              f"r{rows[0]['n']:02d}..r{rows[-1]['n']:02d}, "
              f"{len(regressions)} regression(s)")
    else:
        print(format_table(rows))
    for r in regressions:
        print(f"REGRESSION: {r}", file=sys.stderr)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
