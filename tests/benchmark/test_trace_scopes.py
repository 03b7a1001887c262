"""The program's names in the profiler trace, read back: the wire reader on
the first recorded fixture, the reduction's arithmetic on made-up planes,
and every new per-layer reader on the second fixture (the tiny conv cell,
recorded on a TPU v5e with the scopes and spans in the program)."""

import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec
from benchmarks.trace import opmeta, scopes

from .conftest import HERE, ROOT, relaxed_device_check

OLD = os.path.join(ROOT, "benchmarks", "trace", "fixtures",
                   "tiny_xdev_tpu_v5e.xplane.pb")
NEW = os.path.join(HERE, "fixtures", "trace", "tiny_sim_tpu_v5e.xplane.pb")
#: the eleven metrics this layer of readers adds, with what each moves
NEW_METRICS = ["plan_ms", "enqueue_ms", "idle_in_driver_ms", "prologue_ms",
               "conv_ms", "conv_roofline_pct", "norm_ms", "optimizer_ms",
               "step_other_ms", "aggregate_ms", "unscoped_pct"]
PARTS = ["prologue_ms", "conv_ms", "norm_ms", "optimizer_ms", "step_other_ms",
         "aggregate_ms"]


# -- the wire reader ----------------------------------------------------------

def test_wire_reader_on_the_first_fixture():
    dev = [(ev, st) for plane, ev, st in opmeta.entries(OLD)
           if plane == "/device:TPU:0"]
    assert len(dev) == 115
    assert sum("hlo_category" in st for _ev, st in dev) == 93
    assert sum("tf_op" in st for _ev, st in dev) == 68
    cats = {st.get("hlo_category") for _ev, st in dev}
    assert {"while", "custom fusion", "copy-start", "broadcast"} <= cats
    gather = [st for _ev, st in dev if st.get("tf_op") ==
              "jit(_round_body)/vmap()/while/body/closed_call/gather:"]
    assert any(st["source"].endswith("fedml_tpu/parallel/local.py:199")
               and st["hlo_category"] == "custom fusion"
               and st["flops"] == 0 and st["bytes_accessed"] == 15840
               for st in gather)
    table = opmeta.read(OLD)
    assert "/device:TPU:0" in table and "/host:CPU" not in table
    # an async copy-start has an entry on each of its two lines, same name
    assert len(table["/device:TPU:0"]) == 107
    assert all(set(st) <= set(opmeta.KEYS)
               for st in table["/device:TPU:0"].values())
    # the join is by name: the events of the op line are named by the entries
    from benchmarks.trace.reduce import load
    names = {ev.name for pl in load(OLD).planes if pl.name == "/device:TPU:0"
             for ln in pl.lines if ln.name == "XLA Ops" for ev in ln.events}
    assert names and names <= set(table["/device:TPU:0"])


def test_a_trace_of_a_program_without_the_names_reduces_to_none():
    assert scopes.reduce_path(OLD) is None


# -- the reduction's arithmetic ----------------------------------------------

def test_exclusive_times_sum_to_the_union_when_siblings_overlap():
    ev = [(0, 10, "while"), (2, 4, "a"), (3, 6, "b"), (6, 7, "c"), (12, 13, "d")]
    got = scopes.exclusive_times(ev)
    assert got == pytest.approx([5.0, 1.0, 3.0, 1.0, 1.0])
    assert sum(got) == pytest.approx(11.0)       # the union: [0, 10] + [12, 13]
    assert scopes.parents(ev) == [-1, 0, 1, 0, -1]


def test_scope_of_an_op_own_name_nesting_while_argument_and_xla_copy():
    path = "jit(round_step)/vmap(fedml.step)/while/body/closed_call/"
    meta = {
        "tx_copy": {"tf_op": "tx:", "hlo_category": "data formatting"},
        "cast": {"tf_op": "jit(round_step)/fedml.prologue/convert_element_type:"},
        "xla_copy": {"hlo_category": "copy-done"},
        "while": {"hlo_category": "while"},
        "conv": {"tf_op": path + "fedml.step.train/transpose(jvp(CifarResNet))"
                                 "/BasicBlock_2/Conv_0/conv_general_dilated:"},
        "conv_by_category": {"tf_op": path + "fedml.step.opt/transpose:",
                             "hlo_category": "convolution fusion"},
        "bn": {"tf_op": path + "fedml.step.train/jvp(CifarResNet)/"
                               "BatchNorm_0/reduce_sum:"},
        "relu": {"tf_op": path + "fedml.step.train/jvp(CifarResNet)/"
                                 "BasicBlock_8/add:"},
        "async": {"hlo_category": "copy-start"},
        "sum": {"tf_op": "jit(round_step)/fedml.aggregate/reduce_sum:"},
        "stray": {"tf_op": "jit(round_step)/mul:"},
    }
    ops = [(0, 1, "tx_copy"), (1, 2, "cast"), (2, 3, "xla_copy"),
           (3, 13, "while"), (4, 6, "conv"), (6, 7, "conv_by_category"),
           (7, 8, "bn"), (8, 10, "relu"), (10, 11, "async"),
           (13, 14, "sum"), (14, 15, "stray")]
    rows = scopes.device_scopes(ops, meta)
    got = {n: (sc, scopes.part(sc, k)) for (_t, sc, k), (_s, _e, n)
           in zip(rows, ops)}
    assert got == {
        "tx_copy": ("fedml.prologue", "prologue"),
        "cast": ("fedml.prologue", "prologue"),
        "xla_copy": ("fedml.step", "step_other"),     # feeds the while
        "while": ("fedml.step", "step_other"),        # named by its body
        "conv": ("fedml.step.train", "conv"),
        "conv_by_category": ("fedml.step.opt", "optimizer"),
        "bn": ("fedml.step.train", "norm"),
        "relu": ("fedml.step.train", "step_other"),
        "async": ("fedml.step", "step_other"),        # nested in the while
        "sum": ("fedml.aggregate", "aggregate"),
        "stray": (None, "unscoped"),
    }
    assert [t for t, _sc, _k in rows] == pytest.approx(
        [1, 1, 1, 3, 2, 1, 1, 2, 1, 1, 1])


def test_idle_gaps_by_program_span_and_host_self_times():
    main = [(0.0, 10.0, "bench/dispatch"), (1.0, 9.0, "fedml/round"),
            (1.5, 2.0, "fedml/round/plan"), (2.0, 8.0, "fedml/round/enqueue"),
            (3.0, 5.0, "fedml/round/build"), (10.0, 20.0, "bench/block_prev")]
    worker = [(11.0, 14.0, "fedml/prefetch/materialize")]
    threads = {("/host:CPU", "main"): main, ("/host:CPU", "worker"): worker}
    assert scopes.host_self_times(threads) == pytest.approx({
        "fedml/round": 1.5, "fedml/round/plan": 0.5,
        "fedml/round/enqueue": 4.0, "fedml/round/build": 2.0,
        "fedml/prefetch/materialize": 3.0})
    gaps = [(0.0, 0.9), (3.2, 4.8), (5.5, 7.5), (8.5, 9.5), (11.0, 12.0),
            (15.0, 16.0)]
    got = scopes.label_gaps(gaps, threads)
    assert [g[0] for g in got] == [
        scopes.OUTSIDE,                  # before the round span opens
        "fedml/round/build",             # the child, not the enqueue around it
        "fedml/round/enqueue",
        "fedml/round",                   # 0.5 s of its own time, 0.5 outside
        "fedml/prefetch/materialize",    # a worker's span beside the caller
        scopes.OUTSIDE]                  # the caller blocking
    assert [g[2] for g in got] == pytest.approx([0, 1.6, 2.0, 0.5, 0, 0])


# -- every new reader, on the fixture recorded from the tiny conv cell --------

def _ctx(real_spec, tiny_spec, precision=None):
    """What run.py hands a reader after the fixture's run: 3 traced rounds,
    288 executed sample slots (the run's own lines, in the fixture's
    README)."""
    cell = dict(tiny_spec.cell("tiny_sim"))
    config = dict(tiny_spec.config(cell["config"]))
    if precision:
        config["precision"] = dict(config["precision"], module=precision)
    return {"spec": real_spec, "cell": cell, "config": config,
            "window": SimpleNamespace(rounds=[(1,), (2,), (1,)]),
            "padded_samples": 288, "real_samples": 255,
            "devices": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "trace": {"window_s": 1.0}}


@pytest.fixture
def on_fixture(monkeypatch):
    monkeypatch.setattr(scopes, "trace_path",
                        lambda ctx: NEW if ctx.get("trace") else None)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_reads_the_recorded_fixture(
        name, real_spec, tiny_spec, on_fixture, capsys):
    # the tiny configuration computes in float32, for which the chip has no
    # published peak: the roofline share is read at the bf16 peak here, to
    # exercise its arithmetic, and is None at the configuration's own
    ctx = _ctx(real_spec, tiny_spec, "bfloat16")
    value = real_spec.module("metrics", name).read(ctx)
    assert value is not None and value >= 0.0
    if name.endswith("_pct"):
        assert value <= 105.0
    if name == "conv_roofline_pct":
        assert "bound by" in capsys.readouterr().out
        assert real_spec.module("metrics", name).read(
            _ctx(real_spec, tiny_spec)) is None


def test_the_six_parts_sum_to_the_module_time(real_spec, tiny_spec, on_fixture):
    ctx = _ctx(real_spec, tiny_spec)
    red = scopes.reduce_ctx(ctx)
    parts = sum(real_spec.module("metrics", n).read(ctx) for n in PARTS)
    module_ms = red["module_s"] / 3 * 1e3
    assert parts == pytest.approx(module_ms, rel=0.01)
    assert real_spec.module("metrics", "unscoped_pct").read(ctx) < 2.0
    # the driver's spans: planning and enqueueing are inside run_round
    plan, enq = (real_spec.module("metrics", n).read(ctx)
                 for n in ("plan_ms", "enqueue_ms"))
    assert 0 < plan < enq and red["host_self_s"]["fedml/round"] > 0
    assert {label for label, _s in red["long_gaps"]} <= (
        set(red["host_self_s"]) | {scopes.OUTSIDE})
    # the heaviest ops come with their scope, kind and source line
    assert all(sc and sc.startswith("fedml.") and src
               for _n, _secs, sc, _k, src, _tf in red["top_ops"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_returns_none_without_a_trace(
        name, real_spec, tiny_spec):
    ctx = dict(_ctx(real_spec, tiny_spec), trace=None)
    assert real_spec.module("metrics", name).read(ctx) is None


def test_real_benchmark_lists_the_new_metrics_last_with_their_files(real_spec):
    tail = real_spec.doc["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in tail] == NEW_METRICS
    for m in tail:
        assert m["workloads"] == ["resnet56_sim_c8"]
        assert m["moves"] == "real_samples_per_s"
        assert callable(real_spec.module("metrics", m["name"]).read)


def test_a_cpu_traced_run_with_the_new_metrics_listed_reports_none_of_them(
        capsys, tmp_path, real_spec):
    """The harness calls every listed reader in a traced run; where the
    trace has no device plane (the CPU) each returns None and none raises."""
    doc = json.load(open(os.path.join(HERE, "fixtures", "BENCHMARK.tiny.json")))
    for m in real_spec.doc["per_layer"][-len(NEW_METRICS):]:
        doc["per_layer"].append(dict(m, workloads=["tiny_sim"]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    rc = run.main(["--workload", "tiny_sim", "--seed", "9", "--seconds", "0.3",
                   "--trace", "1"], spec=Spec(str(path)),
                  device_check=relaxed_device_check,
                  t_start=time.perf_counter())
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert "dispatch_ms" in res["metrics"]
    assert not set(NEW_METRICS) & set(res["metrics"])
