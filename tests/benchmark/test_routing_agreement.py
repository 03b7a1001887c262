"""``benchmarks/routing_agreement.py``: the share of (token, choice) pairs
chosen differently, counted rightly, and the script end to end on the tiny
cell (the program's module and the reference agree at float32)."""

import os

import numpy as np
import pytest

from benchmarks import routing_agreement
from benchmarks.harness.spec import Spec

from .conftest import HERE, relaxed_device_check


@pytest.mark.parametrize("ours,theirs,want", [
    ([[0, 1], [2, 3]], [[1, 0], [3, 2]], 0.0),       # order does not matter
    ([[0, 1], [2, 3]], [[0, 5], [2, 3]], 0.25),
    ([[0, 1], [2, 3]], [[4, 5], [6, 7]], 1.0)])
def test_differing_share_counts_pairs(ours, theirs, want):
    got = routing_agreement.differing_share(np.asarray(ours), np.asarray(theirs))
    assert got == pytest.approx(want)


def test_script_runs_on_the_tiny_cell(capsys):
    spec = Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_lm.json"))
    rc = routing_agreement.main(
        ["--workload", "tiny_kanana2_sim", "--seeds", "1", "--first-seed", "11"],
        spec=spec, device_check=relaxed_device_check)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("seed 11")]
    assert rc == 0 and len(lines) == 2
    for line in lines:
        shares = [float(tok) for tok in line.split("  (")[0].split()
                  if tok.replace(".", "").isdigit() and "." in tok]
        assert len(shares) == 2 and max(shares) < 0.05, line
