"""tools/bench_report.py: the BENCH_r*.json trajectory + regression gate.

The parser and the gate are pinned on a small synthetic legacy-lineage
series (five stamp-less artifacts whose metrics appear mid-series, written
into tmp_path by ``_legacy_series``) followed by the two artifacts still
committed at the root (r06/r07, each on its own ``host_basis``) — so a PR
that breaks the artifact schema fails here, not silently.

Pure-text tests: no jax import, no model build — safe at any point in the
tier-1 budget.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_report", os.path.join(REPO, "tools", "bench_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


br = _load()

COMMITTED = sorted(
    os.path.join(REPO, f) for f in os.listdir(REPO)
    if f.startswith("BENCH_r") and f.endswith(".json"))


def _legacy_series(tmp_path, mutate_last=None):
    """Write r01..r05 as the driver would have captured them before the
    ``host_basis`` stamp existed: the bench JSON is the last line of
    ``tail`` (after a log line), metrics appear mid-series (mfu at r02,
    cross-silo at r03, cross-device at r05). Values are synthetic.
    ``mutate_last`` edits r05's bench dict before it is written."""
    benches = [
        {"value": 100.0, "vs_baseline": 1.0},
        {"value": 120.0, "vs_baseline": 1.2, "mfu": 0.100},
        {"value": 125.0, "vs_baseline": 1.25, "mfu": 0.103,
         "crosssilo": {"images_per_sec": 90.0}},
        {"value": 140.0, "vs_baseline": 1.4, "mfu": 0.106,
         "crosssilo": {"images_per_sec": 150.0}},
        {"value": 139.0, "vs_baseline": 1.39, "mfu": 0.105,
         "crosssilo": {"images_per_sec": 149.0},
         "crossdevice": {"clients_per_sec": 40.0, "clients_per_round": 50}},
    ]
    if mutate_last is not None:
        mutate_last(benches[-1])
    paths = []
    for n, bench in enumerate(benches, start=1):
        bench = {"metric": "synthetic img/s", "unit": "images/sec", **bench}
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps(
            {"n": n, "rc": 0,
             "tail": "WARNING: a log line\n" + json.dumps(bench)}))
        paths.append(str(p))
    return paths


def test_committed_artifacts_exist():
    assert len(COMMITTED) >= 2, COMMITTED


def test_series_parses_and_exits_0(tmp_path, capsys):
    rc = br.main(_legacy_series(tmp_path) + COMMITTED)
    out = capsys.readouterr()
    assert rc == 0, out.err
    # the trajectory table carries every run and the headline columns
    assert "r01" in out.out and "r05" in out.out and "r07" in out.out
    assert "vs_baseline" in out.out and "mfu" in out.out


def test_committed_series_check_mode(capsys):
    rc = br.main(["--dir", REPO, "--check"])
    out = capsys.readouterr()
    assert rc == 0, out.err
    assert "0 regression(s)" in out.out


def test_trajectory_values(tmp_path):
    """Pin the parsed trajectory itself: legacy rows parse from the tail's
    last JSON line, committed rows carry their later blocks."""
    rows = br.load_series(_legacy_series(tmp_path) + COMMITTED)
    assert [r["n"] for r in rows] == [1, 2, 3, 4, 5, 6, 7]
    traj = {r["n"]: r for r in rows}
    assert traj[1]["vs_baseline"] == pytest.approx(1.0)
    assert traj[1]["mfu"] is None          # mfu starts at r02
    assert traj[5]["vs_baseline"] == pytest.approx(1.39)
    assert traj[5]["mfu"] == pytest.approx(0.105)
    assert traj[5]["clients_per_sec"] == pytest.approx(40.0)
    assert traj[4]["crosssilo_img_per_sec"] == pytest.approx(150.0)
    # r06 (fedsched, ISSUE 13): 1M-client scheduled streaming block on a
    # stamped host basis (1-core CPU container) — the fedsched context
    # columns appear and the basis stamp starts the gated lineage
    assert traj[6]["xdev_cohort"] == pytest.approx(1000)
    assert traj[6]["xdev_policy"] == "speed"
    assert traj[6]["_basis"] is not None and traj[5]["_basis"] is None
    assert traj[5]["xdev_cohort"] == pytest.approx(50)  # key predates r06
    # r07: tiny-scale resnet56 is a new host basis vs r06's full-scale lr
    # run, so throughput re-bases rather than gating.
    assert traj[7]["_basis"] is not None


def test_mfu_drop_over_threshold_exits_1(tmp_path, capsys):
    def drop_mfu(bench):
        bench["mfu"] = round(bench["mfu"] * 0.85, 4)   # -15% > 10% threshold

    rc = br.main(_legacy_series(tmp_path, drop_mfu))
    out = capsys.readouterr()
    assert rc == 1
    assert "REGRESSION" in out.err and "mfu" in out.err


def test_vs_baseline_drop_over_threshold_exits_1(tmp_path, capsys):
    def drop_vs(bench):
        bench["vs_baseline"] = round(bench["vs_baseline"] * 0.8, 3)
        bench["value"] = round(bench["value"] * 0.8, 1)

    rc = br.main(_legacy_series(tmp_path, drop_vs))
    out = capsys.readouterr()
    assert rc == 1
    assert "vs_baseline" in out.err


def test_small_drop_within_threshold_exits_0(tmp_path, capsys):
    def nudge(bench):
        bench["mfu"] = round(bench["mfu"] * 0.95, 4)   # -5% < 10%

    rc = br.main(_legacy_series(tmp_path, nudge))
    capsys.readouterr()
    assert rc == 0


def test_empty_dir_exits_2(tmp_path, capsys):
    rc = br.main(["--dir", str(tmp_path)])
    out = capsys.readouterr()
    assert rc == 2
    assert "no artifacts" in out.err


def test_malformed_artifacts_exit_2(tmp_path, capsys):
    (tmp_path / "BENCH_r01.json").write_text("{not json")
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({"tail": "no bench"}))
    rc = br.main(["--dir", str(tmp_path)])
    out = capsys.readouterr()
    assert rc == 2
    assert "no parseable" in out.err


def test_tail_last_json_line_wins(tmp_path):
    """A tail that carries two bench JSON lines (a command that ran the
    bench twice): the LAST is the artifact."""
    art = {"n": 9, "tail": "\n".join([
        json.dumps({"metric": "x", "value": 1.0, "vs_baseline": 0.1}),
        "a log line between the two runs",
        json.dumps({"metric": "x", "value": 5.0, "vs_baseline": 0.5}),
    ])}
    p = tmp_path / "BENCH_r09.json"
    p.write_text(json.dumps(art))
    n, bench = br.parse_artifact(str(p))
    assert n == 9 and bench["value"] == 5.0


def test_missing_metric_never_pairs_across_gaps(tmp_path):
    """Metrics that appear mid-series (mfu at r02, clients_per_sec at r05)
    never pair across their gaps, and the r05->r06 host-basis break
    re-bases instead of regressing — the whole series gates clean."""
    rows = br.load_series(_legacy_series(tmp_path) + COMMITTED)
    regs = br.detect_regressions(rows, threshold=0.10)
    assert regs == []


# -- fedsketch trajectory columns (ISSUE 10 satellite) ----------------------

def test_sketch_columns_render_dash_on_presketch_artifacts(tmp_path, capsys):
    """Legacy artifacts predate the profiler sketch block AND the fedsched
    columns: p99 train-ms / staleness / cohort-policy all render '-'
    (missing-key tolerant), r06 fills the policy column, and the series
    still gates clean."""
    rc = br.main(_legacy_series(tmp_path) + COMMITTED)
    out = capsys.readouterr()
    assert rc == 0
    assert "p99 train-ms" in out.out and "p99 staleness" in out.out
    assert "cohort size" in out.out and "policy" in out.out
    header, *rows = [l for l in out.out.splitlines() if l.strip()]
    for row in rows:
        if row.lstrip().startswith(("r06", "r07")):
            assert row.rstrip().endswith("speed")  # fedsched arms
        elif row.lstrip().startswith("r0"):
            assert row.rstrip().endswith("-")      # policy column empty


def test_sketch_columns_parse_and_never_gate(tmp_path, capsys):
    """Artifacts that DO carry sketch summaries populate the columns; a
    worsening (rising) p99 is rendered but never a regression — the
    latency/staleness tails are lower-is-better, display-only."""
    def art(n, p99_train, p99_stale):
        bench = {"metric": "x", "value": 100.0,
                 "profiler": {"sketches": {
                     "train_ms": {"count": 10, "p50": 1.0, "p90": 2.0,
                                  "p99": p99_train},
                     "staleness": {"count": 10, "p50": 0.0, "p90": 1.0,
                                   "p99": p99_stale}}}}
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps({"n": n, "tail": json.dumps(bench)}))
        return str(p)

    paths = [art(1, 5.0, 0.0), art(2, 500.0, 9.0)]   # 100x worse tails
    rows = br.load_series(paths)
    assert rows[0]["p99_train_ms"] == pytest.approx(5.0)
    assert rows[1]["p99_train_ms"] == pytest.approx(500.0)
    assert rows[1]["p99_staleness"] == pytest.approx(9.0)
    assert br.detect_regressions(rows, threshold=0.10) == []
    rc = br.main(paths)
    out = capsys.readouterr()
    assert rc == 0 and "500" in out.out


# -- t1_report: the [t1] obs-overhead session line (ISSUE 10 satellite) -----

def test_t1_report_parses_obs_overhead_line(tmp_path, capsys):
    t1 = importlib.util.spec_from_file_location(
        "t1_report", os.path.join(REPO, "tools", "t1_report.py"))
    mod = importlib.util.module_from_spec(t1)
    t1.loader.exec_module(mod)
    log = (
        "....s..x [ 12%]\n"
        "========= 8 passed in 3.21s =========\n"
        "[t1] compile-cache: 4 hit(s) / 1 miss(es) this session, "
        "9 persistent entries in .jax_cache\n"
        "[t1] obs-overhead: +1.92% wall, full plane on vs off (budget 5%)\n")
    p = tmp_path / "t1.log"
    p.write_text(log)
    rep = mod.parse_log(log)
    assert rep["obs_overhead"] == \
        "+1.92% wall, full plane on vs off (budget 5%)"
    assert mod.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "obs-overhead: +1.92% wall" in out
    # logs predating the line parse to None and render without it
    rep2 = mod.parse_log("....\n========= 4 passed in 1s =========\n")
    assert rep2["obs_overhead"] is None
    assert "obs-overhead" not in mod.format_report(rep2)


# -- host_basis re-basing (ISSUE 13 satellite) ------------------------------

def _series_with_bases(tmp_path, *specs):
    """Write a minimal artifact per (n, value, host_basis) spec."""
    paths = []
    for n, value, basis in specs:
        bench = {"metric": "x", "value": value, "vs_baseline": value / 10}
        if basis is not None:
            bench["host_basis"] = basis
        p = tmp_path / f"BENCH_r{n:02d}.json"
        p.write_text(json.dumps({"n": n, "tail": json.dumps(bench)}))
        paths.append(str(p))
    return paths


def test_host_basis_change_rebases_instead_of_regressing(tmp_path, capsys):
    """A bench captured on a different container (r01-r05's host no longer
    exists) must RE-BASE the trajectory, not read as a 90% regression; the
    break is noted on stderr and the table still renders both runs."""
    big = {"device": "TFRT_CPU_0", "cpus": 64, "model": "resnet56"}
    small = {"device": "TFRT_CPU_0", "cpus": 1, "model": "lr"}
    paths = _series_with_bases(tmp_path, (1, 1000.0, big), (2, 50.0, small))
    rc = br.main(paths)
    out = capsys.readouterr()
    assert rc == 0
    assert "re-based" in out.err and "REGRESSION" not in out.err
    # legacy artifacts (no stamp at all) keep gating against each other
    paths = _series_with_bases(tmp_path, (1, 1000.0, None), (2, 50.0, None))
    rc = br.main(paths)
    out = capsys.readouterr()
    assert rc == 1 and "REGRESSION" in out.err
    # ...and so do two runs on the SAME stamped basis
    paths = _series_with_bases(tmp_path, (1, 1000.0, small), (2, 50.0, small))
    rc = br.main(paths)
    out = capsys.readouterr()
    assert rc == 1 and "REGRESSION" in out.err
