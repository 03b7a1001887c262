"""``tools/proj_sweep.py``, ``tools/trace_ops.py`` and ``tools/round_fit.py``'s
count of a compiled program's matmuls, off the chip: the forms at a tiny
size (no timing is read), the groups of a recorded trace, a made-up
program text."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sweep():
    return _tool("proj_sweep")


@pytest.mark.parametrize("form,products,pieces", [
    ("incumbent", [8512], [4096, 4352, 64]),
    ("A", [8192, 320], [4096, 4096, 256, 64]),
    ("B", [4096, 4096, 320], [4096, 4096, 256, 64]),
    ("C", [4096, 4352, 64], [4096, 4352, 64]),
    ("D", [8512], [4096, 4352, 64]),
    ("B4", [4096, 4096, 256, 64], [4096, 4096, 256, 64]),
    ("mixer", [4096, 4096, 256, 64], [4096, 4096, 256, 64]),
    ("B4+vjp", [4096, 4096, 256, 64], [4096, 4096, 256, 64]),
    ("only:320", [320], [320])])
def test_every_form_is_the_one_projection(sweep, monkeypatch, form, products,
                                          pieces):
    """Each form's pieces, side by side, are ``u @ W`` and its two gradients
    to bf16's rounding, whatever the products and wherever they are cut; the
    mixer's own function is the form the table chose."""
    monkeypatch.setattr(sweep, "T", 64)
    monkeypatch.setattr(sweep, "DIM", 32)
    monkeypatch.setattr(sweep, "_ms", lambda fn, args, iters=1: 1.0)
    monkeypatch.setattr(sweep, "_device_ops", lambda fn, args: [])
    ks = jax.random.split(jax.random.key(38), 3)
    u = jax.random.normal(ks[0], (1, 64, 32)).astype(jnp.bfloat16)
    w = 0.02 * jax.random.normal(ks[1], (32, sweep.WIDTH), jnp.float32)
    ct = jax.random.normal(ks[2], (1, 64, sweep.WIDTH)).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        y, pull = jax.vjp(lambda u, w: u @ w, u.astype(jnp.float32), w)
        exact = (y,) + pull(ct.astype(jnp.float32))
    row = sweep.measure(form, u, w, ct, exact)
    assert row["products"] == products
    assert [hi - lo for lo, hi in sweep.pieces_of(row["edges"])] == pieces
    assert all(err < 8e-3 for err in row["err"].values()), row["err"]
    assert ("du" in row["err"]) == (not form.startswith("only"))


def test_trace_ops_groups_a_recorded_trace():
    """The groups' seconds sum to the busy time of the recorded trace, a
    round is one run of its heaviest module, and every group names its
    scope, pass, fusion and path."""
    ops = _tool("trace_ops")
    rounds, busy, table = ops.groups(os.path.join(
        _ROOT, "benchmarks", "trace", "fixtures", "tiny_xdev_tpu_v5e.xplane.pb"))
    assert rounds == 6 and busy > 0
    assert abs(sum(v[0] for v in table.values()) - busy) < 1e-12
    scopes = {k[0] for k in table}
    assert "fedml.prologue" in scopes and all(len(k) == 4 for k in table)
    assert {k[1] for k in table} <= {"fwd", "remat-fwd", "bwd"}


def test_round_fit_counts_a_programs_matmuls_by_module_and_pass():
    fit = _tool("round_fit")
    path = "jit(round_step)/fedml.step/while/body/fedml.step.train/"
    lines = [
        ("jvp(LM)/layer_0/ssd/fedml.lm.dense/in_proj/dot_general", 3),
        ("transpose(jvp(LM))/jvp(LM)/checkpoint/rematted_computation/"
         "layer_0/ssd/fedml.lm.dense/in_proj/dot_general", 2),
        ("transpose(jvp(LM))/layer_0/ssd/fedml.lm.dense/in_proj/dot_general", 2),
        ("jvp(LM)/layer_1/fedml.lm.dense/mlp/gate/dot_general", 1),
        ("jvp(LM)/layer_1/embed/dot_general", 1)]
    text = "\n".join(
        f'  %convolution.{i}.{j} = f32[8,8]{{1,0}} convolution(%a, %b), '
        f'dim_labels=bf_io->bf, metadata={{op_name="{path}{op}"}}'
        for i, (op, n) in enumerate(lines) for j in range(n))
    assert fit.products(text) == {
        ("ssd/in_proj", "fwd"): 3, ("ssd/in_proj", "remat-fwd"): 2,
        ("ssd/in_proj", "bwd"): 2, ("mlp/gate", "fwd"): 1}
