"""Generators: the same seed gives the same bytes; another seed gives other
values under the same counts and shapes."""

import numpy as np
import pytest

CASES = [("tiny_sim", [0, 1, 2]), ("tiny_xdev", [3, 17, 499]),
         ("tiny_xsilo", [0, 7])]


def _make(spec, cell_name, seed):
    cell = spec.cell(cell_name)
    config = spec.config(cell["config"])
    ds, rows = spec.module("traffic", config["generator"]).make(
        config, cell, seed)
    return ds, rows


@pytest.mark.parametrize("cell_name,ids", CASES)
def test_same_seed_same_bytes(tiny_spec, cell_name, ids):
    a_ds, a = _make(tiny_spec, cell_name, 5)
    b_ds, b = _make(tiny_spec, cell_name, 5)
    for x, y in zip(a(ids), b(ids)):
        assert x.tobytes() == y.tobytes()
    assert np.array_equal(a_ds.train_counts, b_ds.train_counts)


@pytest.mark.parametrize("cell_name,ids", CASES)
def test_other_seed_other_values_same_counts_and_shapes(tiny_spec, cell_name, ids):
    a_ds, a = _make(tiny_spec, cell_name, 5)
    b_ds, b = _make(tiny_spec, cell_name, 2**31 + 11)
    assert np.array_equal(a_ds.train_counts, b_ds.train_counts)
    assert a_ds.train_x.shape == b_ds.train_x.shape
    ax, ay, am, ac = a(ids)
    bx, by, bm, bc = b(ids)
    assert ax.shape == bx.shape and ay.shape == by.shape
    assert np.array_equal(am, bm) and np.array_equal(ac, bc)
    assert not np.array_equal(ax, bx)


@pytest.mark.parametrize("cell_name,ids", CASES)
def test_rows_agree_with_the_dataset_the_program_gets(tiny_spec, cell_name, ids):
    ds, rows = _make(tiny_spec, cell_name, 9)
    x, y, m, c = rows(ids)
    px, py, pm, pc = ds.client_slice(np.asarray(ids))
    assert np.array_equal(x, px) and np.array_equal(y, py)
    assert np.array_equal(m, pm) and np.array_equal(c, pc)
    # masks mark exactly the real records, which come first
    assert np.array_equal(m.sum(axis=1), c)


def test_lda_class_counts_belong_to_the_cell(tiny_spec):
    a_ds, _ = _make(tiny_spec, "tiny_sim", 1)
    b_ds, _ = _make(tiny_spec, "tiny_sim", 2)
    for k in range(a_ds.num_clients):
        n = int(a_ds.train_counts[k])
        assert np.array_equal(np.bincount(a_ds.train_y[k, :n], minlength=10),
                              np.bincount(b_ds.train_y[k, :n], minlength=10))
