"""``benchmarks/readings.py`` at a tiny size: the program over its seeds and
every variant of the reference in the program's place, in one process."""

import json

from benchmarks import readings

from .conftest import relaxed_device_check


def test_readings_give_both_ends_of_every_limit(tiny_spec, tmp_path, capsys):
    out = tmp_path / "readings.json"
    rc = readings.main(["--workload", "tiny_xdev", "--seeds", "2",
                        "--control-seeds", "1", "--first-seed", str(2**31 + 7),
                        "--out", str(out)], spec=tiny_spec,
                       device_check=relaxed_device_check)
    assert rc == 0 and "device memory" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    assert len(doc["program"]) == 2 and doc["rounds"] == [1, 2, 3]
    ref = tiny_spec.module("references", "tiny_lr")
    assert set(doc["variants"]) == set(ref.VARIANTS) - {"reference"}
    limits = tiny_spec.cell("tiny_xdev")["limits"]
    sound = max(row["update_l2"] for row in doc["program"].values())
    stated = max(r["update_l2"] for r in doc["variants"]["stated"].values())
    assert max(sound, stated) < limits["update_l2"]
    for control in ref.CONTROLS:
        low = min(r["update_l2"] for r in doc["variants"][control].values())
        assert low > limits["update_l2"] > 3 * sound, control
    leaves = next(iter(doc["program"].values()))["leaves"]
    assert set(leaves) == {"params/linear/kernel", "params/linear/bias"}
    assert all(len(v) == 3 for v in leaves.values())
