"""``tools/rows_sweep.py`` off the chip: its parts at a tiny size (no
timing is read), and its count of layer-steps a capacity on a made-up
trace."""

import importlib.util
import os

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sweep():
    spec = importlib.util.spec_from_file_location(
        "rows_sweep", os.path.join(_ROOT, "tools", "rows_sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("capacity,form", [
    (16, "swiglu"), (64, "swiglu"), (192, "swiglu"), (64, "relu2")])
def test_parts_run_and_the_add_back_forms_agree(sweep, monkeypatch, capacity,
                                                form):
    from fedml_tpu.models.moe import row_rungs

    for name, value in dict(TOKENS=32, DIM=16, WIDTH=8, CHOICES=6, ROUTED=16,
                            HELD=2, VOCAB=50, FORM=form).items():
        monkeypatch.setattr(sweep, name, value)
    assert row_rungs(32 * 6) == (24, 48, 96, 192)
    operands = sweep._routing(3)
    assert len(operands) == 6 + (3 if form == "swiglu" else 2)
    filled = int(operands[3].sum())
    assert 0 < filled <= 16
    row = sweep.measure(operands, capacity, iters=1, trace=False)
    assert row["capacity"] == capacity and row["rows_filled"] == filled
    assert {"fan_out", "mask", "experts_fwd", "experts_fwd_bwd",
            "incumbent_fwd", "incumbent_fwd_bwd", "add_back_gather",
            "add_back_scatter", "rung_fwd", "rung_bwd"} <= set(row)
    assert row["scatter_gap_to_gather"] < 1e-5
    # off the chip both rows are ``lax.ragged_dot`` on the cast matrices
    assert row["experts_gap_to_incumbent"] == 0


@pytest.mark.parametrize("shown", [True, False])
def test_rung_shares_count_the_conditionals_of_a_trace(sweep, monkeypatch,
                                                       shown):
    """Three conditionals (two under ``moe_rows_8``, one under
    ``moe_rows_16``), each holding a kernel without a path between two
    operations, one of them a loop of its branch; an operation outside any
    conditional is not one. Counted alike whether the trace shows the
    conditional operations themselves or only what ran inside them."""
    from benchmarks.trace import opmeta, scopes

    ops, meta = [], {}

    def op(start, end, name, tf_op):
        ops.append((start, end, name))
        meta[name] = {"tf_op": tf_op}

    for j, (t, c) in enumerate([(0.0, 8), (1.0, 16), (2.0, 8)]):
        path = f"jit(step)/fedml.lm.route/jit(rung)/moe_rows_{c}/fedml.lm."
        if shown:
            op(t, t + 0.9, f"%conditional.{j}", "jit(step)/fedml.lm.route/cond")
        op(t + 0.1, t + 0.3, f"%fusion.{j}", path + "route/gather")
        op(t + 0.3, t + 0.4, f"%ragged-dot.{j}", "ragged-dot-none:")
        op(t + 0.8, t + 0.85, f"%_gmm_dw.{j}", path + "experts/jit(_gmm_dw)/"
           "pallas_call")
        op(t + 0.4, t + 0.8, f"%while.{j}", path + "experts/while")
        op(t + 0.5, t + 0.6, f"%ragged.{j}", path + "experts/while/body/dot")
    op(3.0, 3.5, "%fusion.9", "jit(step)/fedml.lm.dense/dot_general")
    monkeypatch.setattr(scopes, "find_xplane", lambda d: d)
    monkeypatch.setattr(scopes, "read_trace", lambda p: {
        "devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}},
        "threads": {}})
    monkeypatch.setattr(opmeta, "read", lambda p: {"/device:TPU:0": meta})
    got = sweep.rung_shares("anywhere")
    assert got["conditionals"] == 3
    assert got["counted_by"] == ("conditional" if shown else "runs")
    assert got["shares"] == {8: 2 / 3, 16: 1 / 3}
    np.testing.assert_allclose([got["device_ms"][8], got["device_ms"][16]],
                               [2 * 650.0, 650.0])
    # the grouped matmuls' calls by kernel: the compiler's, and the repo's
    # own by the jitted function that makes the call (``%ragged.{j}`` and the
    # fusions under the kernels' paths are no calls)
    assert got["kernel_calls"] == {"_gmm_dw": 3, "ragged-dot": 3}
    np.testing.assert_allclose([got["kernel_ms"]["_gmm_dw"],
                                got["kernel_ms"]["ragged-dot"]], [150., 300.])
