#!/usr/bin/env python
"""t1_report: digest a tier-1 pytest log into the numbers the budget cares
about.

The tier-1 gate (ROADMAP.md) runs the suite under a hard wall-clock budget
and counts progress DOTS from the tee'd log; when the budget regresses, the
log alone doesn't say WHERE the time went. ``tests/conftest.py`` now emits
two machine-parseable ``[t1]`` lines at session end — per-file wall seconds
and the XLA compile-cache hit/miss counts — and this tool parses them back
out next to the dot count, so each PR can see its budget profile:

    python tools/t1_report.py /tmp/_t1.log

Report: DOTS (passed-in-window, the gate's own regex), outcome summary
line, failure/error names, the slowest-10 test files, the compile-cache
line, the obs-overhead line (the pinned full-plane-on vs off wall
delta from the fedsketch budget test), the fedlint line (rule count
plus unsuppressed/suppressed finding counts over the real tree), the
lens line (fedlens learning folds / client observations / suspects
ranked during the session), and the incidents line (fedflight bundles
dumped during the session — a green run's count is stable: only the
flight tests' own expected dumps).
``--json`` emits the same as one JSON object.

Exit codes: 0 parsed; 2 when the file has no pytest progress output at all
(wrong file / empty log).
"""

from __future__ import annotations

import argparse
import json
import re
import sys

#: the ROADMAP tier-1 gate's own progress-line shape — keep identical so
#: this tool and the gate can never disagree about DOTS
DOTS_RE = re.compile(r"^[.FEsx]+( *\[ *[0-9]+%\])?$")
#: passed-in-window baseline the ROADMAP gate tracks: the PR-6 GREEN state
#: (397 passed / 6 xfailed inside the 870s budget — the slow-mark + xfail
#: pass that first made the gate exit 0). PR 4's 214 was the pre-green
#: compile-cache waypoint; deltas against it read as phantom progress. A
#: count BELOW this baseline is flagged as a regression in the report.
BASELINE_DOTS = 397
SUMMARY_RE = re.compile(
    r"^=+ .*(passed|failed|error|no tests ran).* =+$"
    r"|^\d+ (passed|failed|error)[^=]*in [0-9.]+m?s.*$")
FAIL_RE = re.compile(r"^(FAILED|ERROR) (\S+)")
FILE_SECONDS_RE = re.compile(r"^\[t1\] file-seconds: (\[.*\])\s*$")
CACHE_RE = re.compile(r"^\[t1\] compile-cache: (.*)$")
OBS_OVERHEAD_RE = re.compile(r"^\[t1\] obs-overhead: (.*)$")
FEDLINT_RE = re.compile(r"^\[t1\] fedlint: (.*)$")
LENS_RE = re.compile(r"^\[t1\] lens: (.*)$")
INCIDENTS_RE = re.compile(r"^\[t1\] incidents: (.*)$")


def parse_log(text: str) -> dict:
    dots = 0
    progress_lines = 0
    failures: list[str] = []
    summary = None
    file_seconds: list = []
    cache_line = None
    obs_overhead = None
    fedlint = None
    lens = None
    incidents = None
    for line in text.splitlines():
        line = line.rstrip()
        if DOTS_RE.match(line):
            progress_lines += 1
            dots += line.count(".")
            continue
        m = FAIL_RE.match(line)
        if m:
            failures.append(f"{m.group(1)} {m.group(2)}")
            continue
        if SUMMARY_RE.match(line):
            summary = line.strip("= ")
            continue
        m = FILE_SECONDS_RE.match(line)
        if m:
            try:
                file_seconds = json.loads(m.group(1))
            except json.JSONDecodeError:
                pass
            continue
        m = CACHE_RE.match(line)
        if m:
            cache_line = m.group(1)
            continue
        m = OBS_OVERHEAD_RE.match(line)
        if m:
            obs_overhead = m.group(1)
            continue
        m = FEDLINT_RE.match(line)
        if m:
            fedlint = m.group(1)
            continue
        m = LENS_RE.match(line)
        if m:
            lens = m.group(1)
            continue
        m = INCIDENTS_RE.match(line)
        if m:
            incidents = m.group(1)
    return {
        "dots": dots,
        "dots_baseline": BASELINE_DOTS,
        "dots_delta": dots - BASELINE_DOTS,
        "dots_regression": dots < BASELINE_DOTS,
        "progress_lines": progress_lines,
        "summary": summary,
        "failures": failures,
        "slowest_files": file_seconds[:10],
        "compile_cache": cache_line,
        "obs_overhead": obs_overhead,
        "fedlint": fedlint,
        "lens": lens,
        "incidents": incidents,
    }


def format_report(rep: dict) -> str:
    lines = [f"tier-1 log digest: DOTS={rep['dots']}"
             f" ({rep['dots_delta']:+d} vs the {rep['dots_baseline']} "
             f"baseline, over {rep['progress_lines']} progress line(s))"]
    if rep.get("dots_regression"):
        lines.append(
            f"DOTS REGRESSION: {rep['dots']} is below the PR-6 green "
            f"baseline of {rep['dots_baseline']} — the gate lost passing "
            "tests (budget overrun or new failures); see slowest files "
            "and failures below")
    if rep["summary"]:
        lines.append(f"summary: {rep['summary']}")
    if rep["compile_cache"]:
        lines.append(f"compile-cache: {rep['compile_cache']}")
    if rep.get("obs_overhead"):
        lines.append(f"obs-overhead: {rep['obs_overhead']}")
    if rep.get("fedlint"):
        lines.append(f"fedlint: {rep['fedlint']}")
    if rep.get("lens"):
        lines.append(f"lens: {rep['lens']}")
    if rep.get("incidents"):
        lines.append(f"incidents: {rep['incidents']}")
    if rep["slowest_files"]:
        lines.append("slowest files (wall seconds in this session):")
        for path, secs in rep["slowest_files"]:
            lines.append(f"  {secs:>8.1f}s  {path}")
    else:
        lines.append("slowest files: not recorded (log predates the "
                     "conftest [t1] lines, or the session was killed "
                     "before sessionfinish)")
    if rep["failures"]:
        lines.append(f"failures/errors ({len(rep['failures'])}):")
        lines.extend(f"  {f}" for f in rep["failures"])
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log", help="tee'd tier-1 pytest log (e.g. /tmp/_t1.log)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    with open(args.log, errors="replace") as f:
        rep = parse_log(f.read())
    if not rep["progress_lines"] and not rep["summary"]:
        print(f"{args.log}: no pytest progress output found", file=sys.stderr)
        return 2
    print(json.dumps(rep, indent=2) if args.json else format_report(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
