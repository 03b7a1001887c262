"""chip_smoke.py: the command never runs without a chip; its checks and its
phases (functions of their sizes) are exercised here on the CPU."""

import copy
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_without_tpu_exits_nonzero_and_prints_no_result():
    """JAX_PLATFORMS=cpu (conftest sets it): fail in seconds, name the
    missing TPU, print nothing on stdout."""
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout == ""


def test_selects_no_platform_itself():
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    assert "JAX_PLATFORMS" not in src and "jax_platforms" not in src


GOOD = {
    "round": [0, 2, 3], "Test/Loss": [2.5, 2.25, 2.0],
    "placement": {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4,
        "variables_on": ["tpu:0", "tpu:1", "tpu:2", "tpu:3"],
        "device_resident": True,
        "resident_shards": [[d, [8, 192, 32, 32, 3]] for d in range(4)],
        "bytes_in_use": {"0": 1, "1": 1, "2": 1, "3": 1}}}


def _broken(**placement):
    bad = copy.deepcopy(GOOD)
    bad["placement"].update(placement)
    return bad


def test_check_result_accepts_a_healthy_phase():
    chip_smoke.check_result("p", GOOD, platform="tpu", resident=True,
                            spread=True)


@pytest.mark.parametrize("result,match", [
    ({**GOOD, "Test/Loss": []}, "no eval"),
    ({**GOOD, "Test/Loss": [2.5, float("nan")]}, "non-finite"),
    (_broken(variables_on=["cpu:0"]), "model lives on"),
    (_broken(device_resident=False), "device_resident=False"),
    (_broken(resident_shards=[[0, [32, 192, 32, 32, 3]]]),
     "shards on devices"),
])
def test_check_result_rejects(result, match):
    with pytest.raises(chip_smoke.SmokeFailure, match=match):
        chip_smoke.check_result("p", result, platform="tpu", resident=True,
                                spread=True)


@pytest.mark.slow  # ~2 min: four tiny federations through run.main
def test_phases_run_tiny_on_cpu():
    """The same phase functions the chip runs, at toy sizes on the virtual
    CPU mesh (residency forced: 'auto' declines on a CPU backend)."""
    from fedml_tpu.utils.compile_cache import count_cache_events

    events = count_cache_events()
    tiny = dict(model="resnet20", clients=8, batch=8, rounds=2, eval_every=1,
                extra=["--device_data", "on"])
    chip_smoke.sim_phase(events, platform="cpu", per_round=2, **tiny)
    out = chip_smoke.crosssilo_phase(events, platform="cpu", **tiny)
    assert len(out["placement"]["resident_shards"]) == 8   # one per device
    a = chip_smoke.host_phase(events, platform="cpu", clients=500,
                              per_round=6)
    b = chip_smoke.host_phase(events, platform="cpu", clients=500,
                              per_round=6, cohort_chunk=3)
    # the streamed fold computes the batch round's aggregate
    chip_smoke.check_same_losses("p", b["Test/Loss"], a["Test/Loss"])
    with pytest.raises(chip_smoke.SmokeFailure, match="disagree"):
        chip_smoke.check_same_losses("p", [1.0, 2.0], [1.0, 2.1])
    assert events["requests"] > 0 and events["compile_secs"] > 0
    assert events["trace_secs"] > 0 and events["lower_secs"] > 0
