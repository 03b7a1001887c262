"""The harness end to end at a tiny size, through a device check that only
these tests relax: the last line's keys, a broken timed path, a refused
device, and a cell, configuration, generator, reference and per-layer
metric added as new files and entries alone."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check


def _run(capsys, spec, *argv):
    rc = run.main(list(argv), spec=spec, device_check=relaxed_device_check,
                  t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    return rc, out, json.loads(out[-1])


@pytest.mark.parametrize("cell", ["tiny_xdev", "tiny_sim", "tiny_xsilo"])
def test_timed_run_prints_the_contracts_last_line(capsys, tiny_spec, cell):
    rc, lines, res = _run(capsys, tiny_spec, "--workload", cell, "--seed",
                          str(2**31 + 5), "--seconds", "0.5", "--trace", "0")
    assert rc == 0
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True, lines
    assert res["attempted"] >= 2 and res["failed"] == 0
    want = {m["name"] for m in tiny_spec.metric_entries("end_to_end", cell)}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    text = "\n".join(lines)
    for token in ("set-up:", "compile cache:", "window:", "check_s:",
                  "round gap(s) for the percentile", "check lowp_share",
                  "check update_leaf_l2", "device memory"):
        assert token in text


@pytest.mark.parametrize("cell", ["tiny_xdev", "tiny_sim"])
def test_traced_run_reports_per_layer_metrics_and_breakdown(capsys, tiny_spec, cell):
    rc, _lines, res = _run(capsys, tiny_spec, "--workload", cell, "--seed", "8",
                           "--seconds", "0.5", "--trace", "1")
    assert rc == 0 and "breakdown" in res
    listed = {m["name"] for m in tiny_spec.metric_entries("per_layer", cell)}
    assert set(res["metrics"]) <= listed and "dispatch_ms" in res["metrics"]
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    b = res["breakdown"]
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _unchanged_state(api):
    """A step that returns its state unchanged."""
    real = api.run_round

    def run_round(r):
        keep = (api.variables, api.server_state)
        loss = real(r)
        api.variables, api.server_state = keep
        return loss

    api.run_round = run_round


def _drops_a_client(api):
    """Part of the cohort left out of the aggregate: one client's weight 0."""
    import numpy as np

    real = api._sample_failures

    def failures(round_idx, cohort, record=True):
        live = np.ones((cohort,), np.float32)
        live[0] = 0.0
        return live

    api._sample_failures = failures
    assert real is not None


@pytest.mark.parametrize("cell,breaker", [("tiny_xdev", _unchanged_state),
                                          ("tiny_sim", _unchanged_state),
                                          ("tiny_xdev", _drops_a_client)])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch,
                                                   tiny_spec, cell, breaker):
    from benchmarks.harness import cell as cell_module

    build = cell_module.build_api

    def build_broken(*args):
        api = build(*args)
        breaker(api)
        return api

    monkeypatch.setattr(cell_module, "build_api", build_broken)
    rc, lines, res = _run(capsys, tiny_spec, "--workload", cell, "--seed", "9",
                          "--seconds", "0.3", "--trace", "0")
    assert rc == 0 and res["correct"] is False, lines


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "resnet56_sim_c8", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout and "No result" in p.stderr


def test_wrong_device_count_is_refused():
    from benchmarks.harness.device import NoAccelerator, require_tpu

    with pytest.raises(NoAccelerator):
        require_tpu(4)


def test_adding_a_cell_config_generator_reference_and_metric_needs_only_new_files(
        capsys, tmp_path):
    """A later PR's whole change: a directory of its own with one file per
    new thing, and entries in BENCHMARK.json. Nothing that was there is
    edited."""
    extra = tmp_path / "extra"
    for d in ("configs", "workloads", "traffic", "references", "metrics", "flops"):
        (extra / d).mkdir(parents=True)
    fx = os.path.join(HERE, "fixtures")
    config = json.load(open(os.path.join(fx, "configs", "tiny_lr.json")))
    config.update(generator="dummy_gen", reference="dummy_ref", flops="dummy_flops")
    (extra / "configs" / "dummy_lr.json").write_text(json.dumps(config))
    cell = json.load(open(os.path.join(fx, "workloads", "tiny_xdev.json")))
    cell["config"] = "dummy_lr"
    (extra / "workloads" / "dummy_cell.json").write_text(json.dumps(cell))
    shutil.copy(os.path.join(fx, "traffic", "bow_pool.py"),
                extra / "traffic" / "dummy_gen.py")
    shutil.copy(os.path.join(fx, "references", "tiny_lr.py"),
                extra / "references" / "dummy_ref.py")
    shutil.copy(os.path.join(fx, "flops", "tiny_lr.py"),
                extra / "flops" / "dummy_flops.py")
    (extra / "metrics" / "dummy_rounds.py").write_text(
        "def read(ctx):\n    return float(len(ctx['window'].rounds))\n")
    doc = json.load(open(os.path.join(fx, "BENCHMARK.tiny.json")))
    rel = os.path.relpath(extra, ROOT)
    doc["paths"].append(rel)
    doc["configs"].append({"name": "dummy_lr", "source": "test", "reduced": [],
                           "file": os.path.join(rel, "configs", "dummy_lr.json"),
                           "why": "test"})
    doc["workloads"].append({"name": "dummy_cell", "config": "dummy_lr",
                             "traffic": "dummy", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "dummy_rounds", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "round driver",
                             "moves": "real_samples_per_s",
                             "workloads": ["dummy_cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc))
    spec = Spec(str(path))
    rc, lines, res = _run(capsys, spec, "--workload", "dummy_cell", "--seed",
                          "3", "--seconds", "0.3", "--trace", "1")
    assert rc == 0 and res["correct"] is True, lines
    assert res["metrics"]["dummy_rounds"]["value"] == res["attempted"]
    # and the old cells do not report the new metric
    assert "dummy_rounds" not in {
        m["name"] for m in spec.metric_entries("per_layer", "tiny_xdev")}
