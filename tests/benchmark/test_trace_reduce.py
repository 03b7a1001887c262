"""The reduction from a profiler trace to numbers: its arithmetic on made-up
intervals, and the whole of it on the recorded fixture."""

import glob
import os

import pytest

from benchmarks.trace import reduce as tr

from .conftest import ROOT

FIXTURE = os.path.join(ROOT, "benchmarks", "trace", "fixtures")


def test_union_counts_overlap_once():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([(0, 10), (2, 3)]) == pytest.approx(10.0)


def test_idle_gaps_inside_the_window():
    gaps = tr.idle_gaps([(1, 2), (4, 5)], 0.0, 6.0)
    assert gaps == [(0.0, 1), (2, 4), (5, 6.0)]
    assert tr.idle_gaps([(0, 6)], 0.0, 6.0) == []


def test_self_time_takes_nested_ops_out_of_their_parent():
    events = [(0.0, 10.0, "while.1"), (1.0, 4.0, "fusion.1"),
              (5.0, 9.0, "fusion.2"), (12.0, 13.0, "copy.1")]
    got = dict(tr.self_times(events))
    assert got == pytest.approx({"while.1": 3.0, "fusion.1": 3.0,
                                 "fusion.2": 4.0, "copy.1": 1.0})


class _Ev:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _profile(op_dur):
    ops = [_Ev("%fusion.1 = bf16[8,8] fusion(bf16[8,8] %p), kind=kOutput, calls=%f",
               1e9, op_dur),
           _Ev("%all-reduce.1 = f32[4] all-reduce(f32[4] %x)", 2.5e9, 0.25e9)]
    return _Profile([
        _Plane("/device:TPU:0", [
            _Line("XLA Ops", ops),
            _Line("XLA Modules", [_Ev("jit_round_step(123)", 1e9, 2e9)])]),
        _Plane("/host:CPU", [_Line("python", [
            _Ev("bench/dispatch", 0.0, 1e9), _Ev("bench/block_prev", 1e9, 3e9)])])])


def test_reduce_busy_idle_module_and_categories():
    out = tr.reduce_profile(_profile(1e9), 1)
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(1.25)
    assert out["collective_s"] is None        # one chip: nothing to read
    assert out["modules"][0] == ("jit_round_step", pytest.approx(2.0))
    assert out["breakdown"]["device_ops"][0] == ["%fusion.1 (kOutput)",
                                                 pytest.approx(1.0)]
    assert tr.is_collective("%all-reduce.1") and not tr.is_collective("%fusion.1")
    # the longest gap (3.0 .. 4.0 is 1 s; 0 .. 1 is 1 s) is named by a span
    labels = {g[0] for g in out["breakdown"]["idle_gaps"]}
    assert labels <= {"bench/dispatch", "bench/block_prev"}


def test_a_share_over_105_percent_raises():
    prof = _profile(1e9)
    # the same op reported far longer than the window that holds it
    prof.planes[0].lines[0].events.append(_Ev("fusion.9", 0.0, 4e9))
    prof.planes[0].lines[0].events.append(_Ev("fusion.8", 0.0, 4.5e9))
    prof.planes[1].lines[0].events = [_Ev("bench/dispatch", 0.0, 4e9)]
    tr.reduce_profile(prof, 1)          # clipped to the window: 100%, fine
    with pytest.raises(tr.TraceError):
        tr.reduce_profile(prof, 2)      # wrong device count
    with pytest.raises(tr.TraceError):
        tr.reduce_profile(_Profile([]), 1)   # no device operation at all


@pytest.mark.parametrize("reader,trace,want", [
    ("train_mfu_pct", None, "over 105%"),
])
def test_metric_over_105_percent_of_peak_raises(real_spec, reader, trace, want):
    from benchmarks.harness.loop import Window

    config = real_spec.config("resnet56_cifar10")
    w = Window(t0=0.0, t1=1.0)
    ctx = {"spec": real_spec, "config": config, "window": w,
           "real_samples": 10**9, "trace": trace,
           "devices": {"kind": "TPU v5 lite", "count": 1}}
    with pytest.raises(RuntimeError, match=want):
        real_spec.module("metrics", reader).read(ctx)


def test_recorded_fixture_reduces():
    files = glob.glob(os.path.join(FIXTURE, "*.xplane.pb"))
    assert files, "the recorded trace fixture is missing"
    assert os.path.getsize(files[0]) < 1_000_000
    out = tr.reduce_dir(files[0], 1)
    assert 0 < out["busy_s"] <= out["window_s"] * 1.05
    assert out["modules"] and out["modules"][0][1] > 0
    assert out["breakdown"]["device_ops"]
    assert all(len(g) == 2 for g in out["breakdown"]["idle_gaps"])
    idle = 1.0 - out["busy_s"] / out["window_s"]
    assert 0.0 <= idle < 1.0


def test_collective_time_is_the_largest_over_the_chips():
    def plane(n, coll):
        return _Plane(f"/device:TPU:{n}", [
            _Line("XLA Ops", [
                _Ev("%while.1 = (s32[]) while((s32[]) %t)", 0.0, 2e9),
                _Ev("%fusion.2 = bf16[8] fusion(bf16[8] %p), kind=kLoop", 0.1e9, 1e9),
                _Ev("%all-reduce.7 = f32[4] all-reduce(f32[4] %x)", 1.2e9, coll)]),
            _Line("XLA Modules", [_Ev("jit_round_fn(9)", 0.0, 2e9)])])

    prof = _Profile([plane(0, 0.1e9), plane(1, 0.3e9)])
    out = tr.reduce_profile(prof, 2)
    assert out["collective_s"] == pytest.approx(0.3)
    assert out["busy_s"] == pytest.approx(2.0) and out["window_s"] == pytest.approx(2.0)
    # a while's self time is what its body does not cover (the busiest chip's,
    # the first of equals)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["%while.1"] == pytest.approx(2.0 - 1.0 - 0.1)   # chip 0
