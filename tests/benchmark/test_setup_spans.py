"""Set-up by named part (ISSUE 35): ``benchmarks/trace/setup_spans.py`` over
made records, and the eight readers on the tiny CPU cell, held against the
run's own ``set-up:`` line."""

import contextlib
import io
import json
import os
import re
import time
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec
from benchmarks.trace import setup_spans as ss

from .conftest import HERE, relaxed_device_check

METRICS = ("api_init_s", "init_variables_s", "place_data_s", "round_trace_s",
           "round_lower_s", "round_load_s", "helper_programs_built",
           "helper_build_s")


def _rec(rec_id, name, t0, t1, parent=None, **ids):
    return SimpleNamespace(id=rec_id, name=name, t0=t0, t1=t1, parent=parent,
                           ids=ids, seconds=t1 - t0)


def _made_log():
    """A constructor of 10 s (a subclass's around its base's) with an op-by-op
    init, one round program's first call, an eager op of run_round, the
    caller's seeding, and the check's reference after the window (t 100)."""
    return [
        _rec(1, ss.API, 0.0, 10.0, api="Sub"),
        _rec(2, ss.API, 0.5, 9.0, parent=1, api="Sub"),
        _rec(3, ss.INIT, 1.0, 5.0, parent=2, model="m", jitted=False),
        _rec(4, ss.LOWER, 1.0, 1.5, parent=3, fun_name="jit(add)", by="p.m:init"),
        _rec(5, ss.LOAD, 1.5, 2.5, parent=3, fun_name="jit(add)", by="p.m:init",
             cache="none"),
        _rec(6, ss.PLACE, 6.0, 6.5, parent=2, bytes=12),
        _rec(7, ss.BUILD, 7.0, 7.1, parent=2, program="step", phase="construct",
             shape_key="k"),
        # the caller's seeding, no span above it and nobody of the program's
        _rec(8, ss.LOWER, 11.0, 11.5, fun_name="jit(<lambda>)"),
        _rec(9, ss.LOAD, 11.5, 13.0, fun_name="jit(<lambda>)", cache="hit"),
        # an eager op of the round's plan: the program's code asked for it
        _rec(10, ss.LOAD, 20.0, 20.25, fun_name="jit(fold_in)", cache="none",
             by="p.algorithms:_round_plan"),
        # the first call: two overlapping records inside count once
        _rec(11, ss.BUILD, 21.0, 31.0, program="step", phase="first_call",
             shape_key="k"),
        _rec(12, ss.LOAD, 22.0, 22.5, parent=11, fun_name="jit(eager)",
             cache="none", by="p.parallel:step"),
        _rec(13, ss.LOWER, 25.0, 27.0, parent=11, fun_name="jit(round_step)"),
        _rec(14, ss.LOAD, 26.5, 30.0, parent=11, fun_name="jit(round_step)",
             cache="hit"),
        # after the window's start: the check's reference, a late program
        _rec(15, ss.LOAD, 101.0, 105.0, fun_name="jit(reference)", cache="miss"),
        _rec(16, ss.BUILD, 99.0, 100.5, program="late", phase="first_call"),
    ]


def test_reduce_names_every_part_once_and_cuts_at_the_window():
    red = ss.reduce(_made_log(), dropped=3, t_cut=100.0)
    m = red["metrics"]
    assert red["records"] == 14 and red["dropped"] == 3
    assert m["api_init_s"] == 10.0            # the outermost of the two
    assert m["init_variables_s"] == 4.0 and m["place_data_s"] == 0.5
    # first call 10 s less the union of [22, 22.5] and [25, 30]
    assert m["round_trace_s"] == pytest.approx(10.0 - 0.5 - 5.0)
    assert m["round_lower_s"] == 2.0 and m["round_load_s"] == 0.5 + 3.5
    # the init's op and the plan's eager op; not the first call's own eager op
    assert m["helper_programs_built"] == 2
    assert m["helper_build_s"] == pytest.approx(0.5 + 1.0 + 0.25)
    assert red["helper_in_api_s"] == pytest.approx(1.5)
    assert [c.id for c in red["callers"]] == [8, 9]
    (prog,) = red["programs"]
    assert prog["program"] == "step" and prog["cache"] == "hit"
    assert prog["fun_name"] == "jit(round_step)" and prog["loads"] == 2
    (api_row,) = red["spans"][ss.API]
    assert api_row["helper_programs"] == 1 and api_row["helper_s"] == 1.5
    # nothing of the check's (after the cut) is in any number
    early = ss.reduce(_made_log(), dropped=0, t_cut=15.0)
    assert early["programs"] == [] and early["metrics"]["round_trace_s"] == 0
    assert early["metrics"]["helper_programs_built"] == 1


def test_describe_prints_programs_spans_helpers_and_the_callers_compiles():
    lines = []
    ss.describe("made", ss.reduce(_made_log(), 3, 100.0), say=lines.append)
    text = "\n".join(lines)
    assert all(line.startswith("set-up spans: ") for line in lines)
    for token in ("14 record(s)", "3 dropped", "span fedml/setup/api 10.000 s",
                  "bytes=12", "program step k: first call 10.000 s = trace "
                  "4.500 + lower 2.000 + load 4.000", "jit(round_step) cache hit",
                  "helper programs 2", "p.m:init x1 1.500 s",
                  "p.algorithms:_round_plan x1", "jit(add) x1",
                  "the caller's own compiles 1, lower + load 2.000 s",
                  "jit(<lambda>) x1 2.000 s"):
        assert token in text, (token, text)


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_reports_nothing_on_a_program_without_the_log(
        monkeypatch, real_spec, name):
    monkeypatch.setattr(ss, "fetch", lambda: None)
    ctx = {"window": SimpleNamespace(t0=1.0), "cell": {"name": "x"}}
    assert real_spec.module("metrics", name).read(ctx) is None
    assert ctx[ss._KEY] is None


@pytest.fixture(scope="module")
def traced_tiny_run():
    """One traced run of the tiny CPU cell under the tiny specification with
    the eight set-up metrics listed; -> (lines, result, records after it)."""
    from fedml_tpu.obs import setup_log

    spec = Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_setup.json"))
    last = max((r.id for r in setup_log().records()), default=0)
    fetch = ss.fetch

    def fetch_this_runs():
        # the log is the process's, and run.py is one run a process; a test
        # worker has other tests' constructors in it
        records, dropped = fetch()
        return [r for r in records if r.id > last], dropped

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(ss, "fetch", fetch_this_runs)
        rc = run.main(["--workload", "tiny_sim", "--seed", str(2**31 + 35),
                       "--seconds", "0.3", "--trace", "1"], spec=spec,
                      device_check=relaxed_device_check,
                      t_start=time.perf_counter())
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return lines, json.loads(lines[-1]), [
        r for r in setup_log().records() if r.id > last]


@pytest.mark.parametrize("name", METRICS)
def test_each_reader_gives_a_number_on_the_tiny_cell(traced_tiny_run, name):
    _lines, res, _recs = traced_tiny_run
    assert res["correct"] is True
    value = res["metrics"][name]["value"]
    # a worker that has built the model before finds its eager programs in
    # the process: no helper is then built again, and the count says 0
    assert value > 0 or (name.startswith("helper_") and value == 0)
    assert res["metrics"][name]["unit"] in ("s", "count")
    # what the benchmark had is reported as before
    assert {"compile_s", "programs_built", "dispatch_ms"} <= set(res["metrics"])


def test_the_named_parts_fit_inside_the_clocks_stages(traced_tiny_run):
    lines, res, recs = traced_tiny_run
    (setup_line,) = [ln for ln in lines if ln.startswith("set-up: ")]
    clock = {k: float(v) for k, v in
             re.findall(r"(import|data|place|warm-up) ([0-9.]+) s", setup_line)}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    slack = 0.011                     # the line prints hundredths
    assert m["api_init_s"] <= clock["place"] + slack
    assert m["init_variables_s"] + m["place_data_s"] <= m["api_init_s"]
    builds = (m["round_trace_s"] + m["round_lower_s"] + m["round_load_s"]
              + m["helper_build_s"])
    assert builds <= clock["place"] + clock["warm-up"] + slack
    # one round program, every helper counted once, beside the old counter
    loads = [r for r in recs if r.name == ss.LOAD]
    assert m["helper_programs_built"] < m["programs_built"] <= len(loads)
    table = [ln for ln in lines if ln.startswith("set-up spans: ")]
    assert any("program packed_step" in ln and "jit(round_step)" in ln
               for ln in table)
    assert any("helpers by asker: fedml_tpu." in ln for ln in table) == (
        m["helper_programs_built"] > 0)
    assert any("the caller's own compiles" in ln for ln in table)
    assert lines.index(table[-1]) < len(lines) - 1      # earlier lines


def test_the_checks_reference_compiles_are_in_no_metric(traced_tiny_run):
    """The output check compiles its reference after the window: the log has
    those records (the caller's), the metrics end at ``window.t0``."""
    lines, res, recs = traced_tiny_run
    first_calls = [r for r in recs if r.name == ss.BUILD
                   and r.ids.get("phase") == "first_call"]
    window_t0 = max(r.t1 for r in first_calls)   # a floor of it
    late = [r for r in recs if r.name == ss.LOAD and r.t0 > window_t0
            and "by" not in r.ids and r.parent is None]
    assert late, "the check compiled nothing after the window?"
    m = {k: v["value"] for k, v in res["metrics"].items()}
    counted = ss.reduce(recs, 0, t_cut=float("inf"))
    cut = ss.reduce(recs, 0, t_cut=min(r.t0 for r in late))
    assert len(counted["callers"]) > len(cut["callers"])
    for name in METRICS:
        assert cut["metrics"][name] == pytest.approx(m[name])
