"""BENCHMARK.json keeps to its contract's form, and every name in it has
its file."""

import json
import os
import re

import pytest

from .conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in DOC["workloads"]]
CONFIGS = [c["name"] for c in DOC["configs"]]
METRICS = [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= DOC["run_seconds"] <= 51 and isinstance(DOC["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 4)
    assert len(set(CELLS)) == len(CELLS) and len(set(METRICS)) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in DOC["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_found_and_complete(real_spec, name):
    cell = real_spec.cell(name)
    entry = real_spec.workload(name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    for key in ("api", "clients", "fed_config", "partition_seed",
                "sampling_seed", "rounds", "check_rounds", "trace_rounds",
                "limits", "bypasses"):
        assert key in cell, key
    assert cell["config"] in CONFIGS
    # the faults each number is there to catch all carry a limit
    assert {"loss_rel", "update_norm_gap", "change_norm_gap",
            "lowp_share"} <= set(cell["limits"])
    # every cell reports set-up, another end-to-end metric, a per-layer one
    assert len(real_spec.metric_entries("end_to_end", name)) >= 2
    assert len(real_spec.metric_entries("per_layer", name)) >= 1


@pytest.mark.parametrize("name", CONFIGS)
def test_config_is_found_with_generator_reference_and_flops(real_spec, name):
    entry = next(c for c in DOC["configs"] if c["name"] == name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in DOC["paths"]))
    config = real_spec.config(name)
    assert set(entry["reduced"]) == set(config["reduced"])
    assert any(w["config"] == name for w in DOC["workloads"])
    assert callable(real_spec.module("traffic", config["generator"]).make)
    ref = real_spec.module("references", config["reference"])
    assert set(ref.CONTROLS) <= set(ref.VARIANTS) and ref.CONTROLS
    assert real_spec.module("flops", config["flops"]) \
        .train_flops_per_sample(config) > 0


@pytest.mark.parametrize("name", METRICS)
def test_metric_has_a_reader_and_a_lawful_entry(real_spec, name):
    e2e = {m["name"] for m in DOC["end_to_end"]}
    entry = next(m for m in DOC["end_to_end"] + DOC["per_layer"]
                 if m["name"] == name)
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if name in e2e else {"layer", "moves"}
    assert set(entry) <= allowed and NAME.match(name) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    if name in e2e:
        assert entry["source"] in ("host_clock", "device_trace")
    else:
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert entry["moves"] in e2e
        moved = next(m for m in DOC["end_to_end"] if m["name"] == entry["moves"])
        cells = entry.get("workloads", CELLS)
        assert set(cells) <= set(moved.get("workloads", CELLS))
    assert set(entry.get("workloads", [])) <= set(CELLS)
    assert callable(real_spec.module("metrics", name).read)


def test_peaks_known_kind_and_unknown_kind_is_an_error(real_spec):
    from benchmarks.harness.spec import SpecError

    peaks = real_spec.peaks("TPU v5 lite")
    assert peaks["flops_per_s"]["bfloat16"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SpecError):
        real_spec.peaks("TPU v9 imaginary")
