"""fedcost (fedml_tpu/obs/cost): static per-op roofline attribution.

Pinned contracts (ISSUE 6):
- the HLO parser recovers conv/dot GEMM shapes, feature groups and static
  loop trip counts from text alone (unit-tested on handwritten HLO);
- the lane-fill estimator reproduces docs/perf.md's hand-derived roofline
  for ResNet-56: stage fills 16/32/64 -> 12.5%/25%/50% of the 128-wide MXU
  and a flop-weighted output-lane ceiling of ~29%;
- a golden per-op table for the FLAGSHIP round program (resnet56, packed
  schedule) derived on CPU purely by lowering — no compile, no execution;
- attribution through the obs/compile.timed_build hook records tables and
  stays bit-identical to a run without it;
- the shared peak table matches what bench.py's mfu_basis always reported.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.obs import cost


@pytest.fixture(autouse=True)
def _reset_cost():
    cost.enable_cost_attribution(False)
    cost.reset_cost_tables()
    yield
    cost.enable_cost_attribution(False)
    cost.reset_cost_tables()


# -- pure-text parser units --------------------------------------------------

SCAN_CONV_HLO = """\
HloModule jit_g, entry_computation_layout={(bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0})->bf16[8,32,32,16]{3,2,1,0}}

None.5 {
  Arg_1.7 = bf16[8,32,32,16]{3,2,1,0} parameter(1)
  Arg_0.6 = bf16[3,3,16,16]{3,2,1,0} parameter(0)
  ROOT convolution.8 = bf16[8,32,32,16]{3,2,1,0} convolution(Arg_1.7, Arg_0.6), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f
}

region_0.9 {
  arg_tuple.10 = (s32[], bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0}) parameter(0)
  get-tuple-element.11 = s32[] get-tuple-element(arg_tuple.10), index=0
  constant.14 = s32[] constant(1)
  add.16 = s32[] add(get-tuple-element.11, constant.14)
  get-tuple-element.13 = bf16[3,3,16,16]{3,2,1,0} get-tuple-element(arg_tuple.10), index=2
  get-tuple-element.12 = bf16[8,32,32,16]{3,2,1,0} get-tuple-element(arg_tuple.10), index=1
  call.15 = bf16[8,32,32,16]{3,2,1,0} call(get-tuple-element.13, get-tuple-element.12), to_apply=None.5
  ROOT tuple.17 = (s32[], bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0}) tuple(add.16, call.15, get-tuple-element.13)
}

region_1.18 {
  arg_tuple.19 = (s32[], bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0}) parameter(0)
  get-tuple-element.20 = s32[] get-tuple-element(arg_tuple.19), index=0
  constant.23 = s32[] constant(7)
  ROOT compare.24 = pred[] compare(get-tuple-element.20, constant.23), direction=LT
}

ENTRY main.28 {
  constant.3 = s32[] constant(0)
  Arg_0.1 = bf16[8,32,32,16]{3,2,1,0} parameter(0)
  Arg_1.2 = bf16[3,3,16,16]{3,2,1,0} parameter(1)
  tuple.4 = (s32[], bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0}) tuple(constant.3, Arg_0.1, Arg_1.2)
  while.25 = (s32[], bf16[8,32,32,16]{3,2,1,0}, bf16[3,3,16,16]{3,2,1,0}) while(tuple.4), condition=region_1.18, body=region_0.9
  ROOT get-tuple-element.27 = bf16[8,32,32,16]{3,2,1,0} get-tuple-element(while.25), index=1
}
"""


def test_parser_scan_conv_trip_count_and_shapes():
    ops, unknown = cost.op_table(SCAN_CONV_HLO)
    assert not unknown
    assert len(ops) == 1
    (op,) = ops
    assert op["kind"] == "conv"
    assert op["count"] == 7                      # while trip count, derived
    assert (op["m"], op["k"], op["n"]) == (8 * 32 * 32, 3 * 3 * 16, 16)
    assert op["out_lane_fill"] == pytest.approx(16 / 128)
    assert op["red_lane_fill"] == pytest.approx(1.0)   # K=144 >= 128 lanes
    assert op["flops"] == pytest.approx(2 * 8 * 32 * 32 * 144 * 16)


def test_parser_unknown_trip_count_flagged():
    # break the counter pattern: GE direction is not a scan loop
    txt = SCAN_CONV_HLO.replace("direction=LT", "direction=GE")
    ops, unknown = cost.op_table(txt)
    assert unknown
    assert ops[0]["count"] == 1                  # body counted once


def test_parser_data_bounded_loop_counts_at_its_constant_ceiling():
    """A loop that ends at a data-dependent bound AND at a constant one (the
    packed lanes' step loop, parallel/packed._walk_steps) counts its body at
    the constant; with no constant in the condition it is unknown."""

    def walk(ceiling):
        def f(n, x, w):
            def cond(c):
                ok = c[0] < n
                return ok & (c[0] < ceiling) if ceiling else ok

            return jax.lax.while_loop(
                cond, lambda c: (c[0] + 1, c[1] @ w), (jnp.int32(0), x))[1]

        return (jax.jit(f).lower(jnp.int32(3), jnp.zeros((4, 8)),
                                 jnp.zeros((8, 8)))
                .compiler_ir(dialect="hlo").as_hlo_text())

    ops, unknown = cost.op_table(walk(7))
    assert not unknown and [o["count"] for o in ops] == [7]
    ops, unknown = cost.op_table(walk(0))
    assert unknown and [o["count"] for o in ops] == [1]


def test_parser_grouped_conv_per_group_lanes():
    """A cohort-vmapped conv lowers to feature_group_count=G; the MXU sees
    the PER-GROUP output width, so lane fill must divide by G."""

    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))

    xs = jnp.zeros((4, 2, 8, 8, 16), jnp.bfloat16)
    ws = jnp.zeros((4, 3, 3, 16, 16), jnp.bfloat16)
    txt = (jax.jit(jax.vmap(f)).lower(xs, ws)
           .compiler_ir(dialect="hlo").as_hlo_text())
    ops, _ = cost.op_table(txt)
    assert len(ops) == 1
    assert ops[0]["groups"] == 4
    assert ops[0]["n"] == 16                     # per group, not 64
    assert ops[0]["out_lane_fill"] == pytest.approx(16 / 128)
    assert ops[0]["k"] == 3 * 3 * 16


def test_parser_batched_dot():
    def d(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    txt = (jax.jit(d).lower(jnp.zeros((5, 7, 11)), jnp.zeros((5, 11, 13)))
           .compiler_ir(dialect="hlo").as_hlo_text())
    ops, _ = cost.op_table(txt)
    assert len(ops) == 1
    o = ops[0]
    assert (o["b"], o["m"], o["k"], o["n"]) == (5, 7, 11, 13)
    assert o["flops"] == pytest.approx(2 * 5 * 7 * 11 * 13)


def test_peak_table_is_keyed_by_exact_device_kind():
    """The v5e's own device_kind resolves to its published 197 TFLOP/s; a
    TPU kind nobody has run raises (never a neighbour's peak, never a
    silent null MFU); off-TPU there is no peak and no MFU."""

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert cost.peak_flops(Dev()) == (197e12, "TPU v5 lite")
    Dev.device_kind = "TPU v5p"        # substring of nothing: exact keys only
    with pytest.raises(cost.UnknownDeviceKind, match="TPU v5p"):
        cost.peak_flops(Dev())
    assert cost.peak_flops(jax.devices()[0]) == (None, None)   # CPU


def test_summarize_flop_weighted_ceiling():
    ops = [
        {"kind": "conv", "m": 1, "k": 1, "n": 16, "groups": 1, "b": 1,
         "flops": 100.0, "bytes": 10.0, "name": "a", "dtype": "bf16",
         "count": 1, "out_lane_fill": 16 / 128, "red_lane_fill": 1.0,
         "intensity": 10.0},
        {"kind": "conv", "m": 1, "k": 1, "n": 64, "groups": 1, "b": 1,
         "flops": 100.0, "bytes": 10.0, "name": "b", "dtype": "bf16",
         "count": 3, "out_lane_fill": 64 / 128, "red_lane_fill": 1.0,
         "intensity": 10.0},
    ]
    s = cost.summarize(ops)
    # (100*0.125 + 300*0.5) / 400, reported rounded to 4 decimals
    assert s["out_lane_ceiling"] == pytest.approx(0.40625, abs=1e-4)
    assert s["gemm_flops_per_invocation"] == pytest.approx(400.0)
    assert s["by_output_channels"]["64"]["flops_frac"] == pytest.approx(0.75)


# -- the perf.md roofline, regenerated from HLO ------------------------------

def _flagship_bundle():
    return create_model("resnet56", 10, dtype=jnp.bfloat16,
                        input_shape=(32, 32, 3))


def test_resnet56_fwd_reproduces_perf_md_lane_table():
    """docs/perf.md's hand table — stages C=16/32/64 fill 12.5%/25%/50% of
    the MXU output lanes with ~equal FLOPs, flop-weighted ceiling ~29% —
    must fall out of the HLO with no hand arithmetic."""
    bundle = _flagship_bundle()
    variables = bundle.init(jax.random.PRNGKey(0), 2)
    x = jnp.zeros((64, 32, 32, 3), jnp.bfloat16)

    def fwd(v, xx):
        return bundle.apply_eval(v, xx)

    rep = cost.analyze_lowered(jax.jit(fwd).lower(variables, x))
    s = rep["summary"]
    stage = s["by_output_channels"]
    assert stage["16"]["out_lane_fill"] == pytest.approx(0.125)
    assert stage["32"]["out_lane_fill"] == pytest.approx(0.25)
    assert stage["64"]["out_lane_fill"] == pytest.approx(0.50)
    # channel doubling offsets spatial halving: ~equal FLOPs per stage
    for n in ("16", "32", "64"):
        assert 0.30 < stage[n]["flops_frac"] < 0.37, (n, stage[n])
    assert 0.28 < s["out_lane_ceiling"] < 0.30      # the ~29% ceiling
    assert not s["unknown_trip_counts"]
    # XLA's own cost model agrees with the committed bench artifact scale:
    # r05 pinned model_flops_per_image = 695831616 = 3x the fwd pass
    assert rep["xla_cost"] is not None
    fwd_per_image = rep["xla_cost"]["flops"] / 64
    assert fwd_per_image == pytest.approx(695831616 / 3, rel=0.05)


def test_golden_flagship_round_program_table():
    """Golden per-op table for the FLAGSHIP round program (resnet56,
    packed schedule) — derived on CPU purely by LOWERING the exact jitted
    step the round would execute; no XLA compile, no execution."""
    ds = make_synthetic_classification(
        "cost-golden", (32, 32, 3), 10, 4, records_per_client=8,
        partition_method="homo", partition_alpha=0.5, batch_size=4, seed=0)
    cfg = FedConfig(model="resnet56", dataset="cifar10",
                    client_num_in_total=4, client_num_per_round=2,
                    comm_round=1, batch_size=4, epochs=1, lr=0.1,
                    dtype="bfloat16", frequency_of_the_test=1000, seed=0,
                    pack_lanes=2, device_data="on")
    api = FedAvgAPI(ds, cfg, _flagship_bundle())
    round_plan = api._round_plan(1, record=False)
    sampled, plan = round_plan.sampled, round_plan.lanes
    step = api.build_round_step_packed(plan.shape_key)
    counts = np.asarray(ds.train_counts, np.float32)[sampled]
    plan_arrays = tuple(jnp.asarray(a) for a in (
        plan.slot, plan.epoch, plan.sie, plan.reset, plan.emit, plan.live,
        plan.member_pos, plan.member_valid, plan.steps_real))
    tx, ty, tm, _tc = api._dev_train
    rep = cost.analyze_jitted(step, (
        api.variables, api.server_state, tx, ty, tm,
        jnp.asarray(sampled, jnp.int32),
        jnp.asarray(counts), jax.random.PRNGKey(0), plan_arrays))
    assert rep is not None
    s = rep["summary"]
    # golden census: fwd + dgrad + wgrad convs of the 56-layer stack, per
    # stage, plus the classifier head dots — pinned so a lowering change
    # that silently alters the program's GEMM population fails here
    census = {}
    for o in rep["ops"]:
        census[(o["kind"], o["n"])] = census.get((o["kind"], o["n"]), 0) + 1
    assert census == {("conv", 16): 58, ("conv", 32): 57, ("conv", 64): 55,
                      ("dot", 10): 1, ("dot", 64): 2}, census
    # every conv is cohort-grouped (2 clients vmapped into one program)
    conv_groups = {o["groups"] for o in rep["ops"] if o["kind"] == "conv"}
    assert conv_groups == {2}
    # the scan multiplies every SGD-step op by the same trip count
    counts_set = {o["count"] for o in rep["ops"] if o["kind"] == "conv"}
    assert len(counts_set) == 1 and counts_set.pop() >= 1
    assert not s["unknown_trip_counts"]
    # the training program carries the same ~29% output-lane ceiling as the
    # fwd pass (bwd conv shapes mirror fwd per stage)
    assert 0.27 < s["out_lane_ceiling"] < 0.31
    # reduction lanes are essentially full (K = kh*kw*Cin >= 144 almost
    # everywhere): output lanes, not reduction, are THE binding constraint
    assert s["red_lane_ceiling"] > 0.9


# -- attribution through the timed_build hook --------------------------------

def _tiny_ds():
    return make_synthetic_classification(
        "cost-attr", (8, 8, 3), 4, 8, records_per_client=12,
        partition_method="hetero", partition_alpha=0.5, batch_size=4,
        seed=0)


def _tiny_run(**cfg_kw):
    ds = _tiny_ds()
    cfg = FedConfig(model="cnn", dataset="x", client_num_in_total=8,
                    client_num_per_round=4, comm_round=2, batch_size=4,
                    epochs=1, lr=0.1, seed=0, frequency_of_the_test=1000,
                    pack_lanes=2, device_data="on", **cfg_kw)
    from fedml_tpu import obs

    bundle = create_model("cnn", 4, input_shape=(8, 8, 3))
    api = FedAvgAPI(ds, cfg, bundle)
    # the run_round-only path: configure tracing AND cost exactly as
    # train() would (tracer.configure_from chains into cost.configure_from)
    obs.configure_from(cfg)
    for r in (1, 2):
        api.run_round(r)
    return jax.tree.map(np.asarray, api.variables)


def test_attribution_records_tables_and_is_bit_identical():
    v_off = _tiny_run()
    assert cost.cost_tables() == {}
    v_on = _tiny_run(cost_attribution=True)
    tables = cost.cost_tables()
    assert "packed_step" in tables
    rec = tables["packed_step"]
    assert rec["summary"]["gemm_ops"] > 0
    assert rec["summary"]["out_lane_ceiling"] is not None
    assert rec["shape_key"]                      # attributed WHICH program
    for a, b in zip(jax.tree_util.tree_leaves(v_off),
                    jax.tree_util.tree_leaves(v_on)):
        np.testing.assert_array_equal(a, b)


def test_a_packed_program_says_its_lanes_and_has_no_packing_column():
    """The lane program has one form (ISSUE 43): a built packed program
    carries ``lane_ids`` and no packing hint, and its table has no packing
    column: every FLOP it counts is a client's own."""
    _tiny_run(cost_attribution=True)
    rec = cost.cost_tables()["packed_step"]
    assert "packed_conv" not in rec and "plan" not in rec
    assert "packing" not in rec["summary"]
    assert "useful_flops_per_invocation" not in rec["summary"]
    assert rec["ops"] and not any(
        k in o for o in rec["ops"] for k in ("packing_factor", "useful_flops"))
    api = FedAvgAPI(_tiny_ds(), FedConfig(
        model="cnn", dataset="x", client_num_in_total=8,
        client_num_per_round=8, batch_size=4, pack_lanes=4,
        device_data="on"), create_model("cnn", 4, input_shape=(8, 8, 3)))
    step = api.build_round_step_packed(api._round_plan(1).lanes.shape_key)
    assert step.lane_ids == {"lanes": 4, "lane_width": 2}
    assert not hasattr(step, "cost_hints")
    assert not hasattr(cost, "apply_packing")


def test_attribution_emits_program_cost_event_under_tracing(tmp_path):
    from fedml_tpu import obs

    td = str(tmp_path / "tr")
    try:
        _tiny_run(cost_attribution=True, trace_dir=td)
        obs.flush_all(td)
    finally:
        obs.reset()
    events = []
    import json as _json
    for name in os.listdir(td):
        with open(os.path.join(td, name)) as f:
            events += [_json.loads(line) for line in f if line.strip()]
    costs = [e for e in events
             if e.get("ph") == "i" and e.get("name") == "program_cost"]
    assert costs, "no program_cost instant in the trace"
    args = costs[0]["args"]
    assert args["program"] == "packed_step"
    assert args["summary"]["gemm_ops"] > 0
    assert args["summary"]["out_lane_ceiling"] is not None
    # CPU run: peak unknown -> report prints FLOP/s without inventing MFU
    assert args["peak_bf16_flops"] is None


def test_attribution_failure_never_breaks_the_run():
    """A non-jitted program (no .lower) is skipped, not fatal."""
    assert cost.analyze_jitted(lambda x: x, (1,)) is None
    cost.enable_cost_attribution(True)
    assert cost.attribute_program("nope", ("k",), lambda x: x, (1,)) is None
    assert cost.cost_tables() == {}


def test_configure_from_respects_absent_attribute():
    cost.enable_cost_attribution(True)

    class NoAttr:
        pass

    assert cost.configure_from(NoAttr()) is True   # untouched

    class Off:
        cost_attribution = False

    assert cost.configure_from(Off()) is False
    assert not cost.cost_attribution_enabled()


# -- the trace_report cost section (pure event-list analysis) ----------------

def test_trace_report_cost_section_device_span_mfu():
    """A program_cost instant + matching mesh device spans must fold into
    achieved-FLOP/s and MFU-vs-ceiling in the analyzer — synthetic events,
    no federation run."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(repo, "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)

    summary = {
        "gemm_ops": 1, "gemm_flops_per_invocation": 1e12,
        "out_lane_ceiling": 0.29, "red_lane_ceiling": 0.99,
        "by_output_channels": {"16": {"out_lane_fill": 0.125,
                                      "flops_frac": 1.0}},
        "top_ops": [{"kind": "conv", "count": 8, "m": 1024, "k": 144,
                     "n": 16, "groups": 2, "out_lane_fill": 0.125,
                     "red_lane_fill": 1.0, "flops": 1.25e11, "bytes": 1e6,
                     "name": "c1", "dtype": "bf16", "intensity": 100.0}],
        "unknown_trip_counts": False,
    }
    events = [
        {"ph": "i", "name": "program_cost", "cat": "cost", "rank": 0,
         "ts": 5, "args": {"program": "mesh_packed_round",
                           "path": "packed_mesh", "summary": summary,
                           "xla_cost": None, "peak_bf16_flops": 197e12,
                           "peak_table_entry": "v5e"}},
    ]
    for r in (0, 1):
        base = r * 700_000
        events.append({"ph": "X", "name": "round", "cat": "round",
                       "rank": 0, "ts": base, "dur": 600_000, "sid": r + 1,
                       "args": {"round": r}})
        events.append({"ph": "X", "name": "mesh_step", "cat": "device",
                       "rank": 0, "ts": base + 10, "dur": 500_000,
                       "args": {"round": r, "path": "packed_mesh"}})

    rep = tr.analyze(events)
    prog = rep["cost"]["programs"]["mesh_packed_round"]
    assert prog["summary"]["out_lane_ceiling"] == pytest.approx(0.29)
    ach = rep["cost"]["achieved"]["mesh_packed_round"]
    # 2 rounds x 1 TFLOP over 2 x 500 ms of device spans = 2 TFLOP/s
    assert ach == {"rounds": 2, "measured_ms": 1000.0,
                   "basis": "device spans",
                   "achieved_gflops_per_sec": 2000.0,
                   "mfu_mac": pytest.approx(0.0102),
                   "mfu_vs_ceiling": pytest.approx(0.035)}
    text = tr.format_report(rep)
    assert "cost attribution" in text
    assert "out-lane ceiling 29.0%" in text
    assert "mfu 1.02%" in text
