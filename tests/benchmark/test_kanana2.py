"""The ``kanana2_30b_a3b`` configuration's files: found by name, true to
the source's config, the registered model's defaults equal to the file's
``model`` block; the tiny cell of the same model through the harness; the
generator, the FLOP counts, the per-layer readers and the reference's
controls."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check

#: the language model's settings as its public config.json gives them (the
#: catalog row of kakaocorp/kanana-2-30b-a3b-instruct-2601)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
NAME, CELL = "kanana2_30b_a3b", "kanana2_sim_c2"
NEW_READERS = ("attn_ms", "attn_roofline_pct", "expert_mm_ms",
               "expert_mm_roofline_pct", "moe_route_ms", "dense_mm_ms",
               "state_update_ms", "lm_other_ms", "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def lm_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_lm.json"))


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_file_keeps_every_published_key(real_spec, key):
    """Only what ``reduced`` lists differs from the source, and no width."""
    config = real_spec.config(NAME)
    entry = next(c for c in real_spec.doc["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    if key in entry["reduced"]:
        assert config[key] != PUBLISHED[key]
        assert config["published"][key] == PUBLISHED[key]
        assert key in ("num_hidden_layers", "n_routed_experts", "vocab_size")
    else:
        assert key in config and config[key] == PUBLISHED[key]


def test_model_block_is_the_registered_default_and_the_files_own_keys(real_spec):
    from fedml_tpu.models.moe import LATENT_MOE_PRESETS

    config = real_spec.config(NAME)
    m = dict(config["model"])
    assert m.pop("program_name") == NAME
    assert m == LATENT_MOE_PRESETS[NAME]
    same = {"dim": "hidden_size", "heads": "num_attention_heads",
            "nope": "qk_nope_head_dim", "rope": "qk_rope_head_dim",
            "v_dim": "v_head_dim", "kv_rank": "kv_lora_rank",
            "layers": "num_hidden_layers", "first_dense": "first_k_dense_replace",
            "dense_width": "intermediate_size", "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size",
            "n_shared": "n_shared_experts", "held_count": "n_routed_experts",
            "routed_scaling": "routed_scaling_factor", "rope_theta": "rope_theta",
            "eps": "rms_norm_eps"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # the router keeps the published width; the slice is the vocabulary
    assert m["n_routed"] == config["published"]["n_routed_experts"] == 128
    assert config["data"]["vocab"] == config["vocab_size"] == 16032
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]


@pytest.mark.parametrize("spec_name,cell", [("real", CELL),
                                            ("tiny", "tiny_kanana2_sim")])
def test_cell_files_are_found_by_name(real_spec, lm_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else lm_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated"} <= set(ref.VARIANTS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    names = {m["name"] for m in spec.metric_entries("per_layer", cell)}
    assert set(NEW_READERS) <= names
    for n in NEW_READERS:
        assert callable(spec.module("metrics", n).read)


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmarks", "references", NAME + ".py")).read()
    assert "fedml_tpu" not in src.replace("``fedml_tpu``", "")


def test_required_flops_are_the_issues_arithmetic(real_spec):
    config = real_spec.config(NAME)
    f = real_spec.module("flops", config["flops"])
    per_token = f.train_flops_per_sample(config) / config["data"]["seq_len"]
    assert 2.15e9 < per_token < 2.17e9          # 2.16 GFLOP a token
    assert f.routed_rows_per_token(config) == 0.75
    attn, attn_bytes = f.attn_train_cost_per_sample(config)
    experts, exp_bytes = f.expert_train_cost_per_sample(config)
    assert attn > experts > 0 and attn_bytes > 0 and exp_bytes > 0
    # one routed expert is 3 x 2048 x 768 weights: 2 x 3 passes a row
    rows = 4096 * 0.75 * 4
    assert experts == pytest.approx(rows * 3 * 2 * 3 * 2048 * 768)


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_generator_is_seeded_and_keeps_ids_and_counts(lm_spec, seed):
    cell = lm_spec.cell("tiny_kanana2_sim")
    config = lm_spec.config(cell["config"])
    gen = lm_spec.module("traffic", config["generator"])
    a, rows = gen.make(config, cell, seed)
    b, _ = gen.make(config, cell, seed)
    c, _ = gen.make(config, cell, seed + 1)
    assert a.task == "nwp" and a.train_x.dtype == np.int32
    np.testing.assert_array_equal(a.train_x, b.train_x)
    np.testing.assert_array_equal(a.train_counts, c.train_counts)   # the cell's
    assert (a.train_x != c.train_x).any()                            # the seed's
    lo, hi = config["data"]["client_sequences"]
    assert a.train_counts.min() >= lo and a.train_counts.max() <= hi
    assert a.train_x.max() < config["data"]["vocab"] and a.train_x.min() >= 0
    # targets are the ids that follow
    n = int(a.train_counts[0])
    np.testing.assert_array_equal(a.train_x[0, :n, 1:], a.train_y[0, :n, :-1])
    assert a.train_mask[0, :n].all() and not a.train_mask[0, n:].any()
    x, y, m, counts = rows([1, 0])
    np.testing.assert_array_equal(x[1], a.train_x[0])
    assert list(counts) == [a.train_counts[1], a.train_counts[0]]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, lm_spec, trace):
    rc = run.main(["--workload", "tiny_kanana2_sim", "--seed", str(2**31 + 7),
                   "--seconds", "0.3", "--trace", trace], spec=lm_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counter is read on the CPU too; the trace's parts need a TPU
        assert res["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
        assert "dispatch_ms" in res["metrics"]


def _ctx(real_spec, by_scope, busy, rounds=2):
    class W:
        pass

    w = W()
    w.rounds = [(1, 0, 0, 0)] * rounds
    return {"spec": real_spec, "cell": real_spec.cell(CELL),
            "config": real_spec.config(NAME), "window": w, "trace": {"x": 1},
            "padded_samples": 32,
            "devices": {"kind": "TPU v5 lite", "count": 1, "platform": "tpu"},
            "_red": {"by_scope_s": by_scope, "busy_s": busy}}


@pytest.mark.parametrize("reader,want", [
    ("attn_ms", 500.0), ("expert_mm_ms", 50.0), ("moe_route_ms", 25.0),
    ("dense_mm_ms", 600.0), ("state_update_ms", 150.0), ("lm_other_ms", 175.0)])
def test_six_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    from benchmarks.trace import lm_scopes

    by_scope = {"fedml.lm.attn": 1.0, "fedml.lm.experts": 0.1,
                "fedml.lm.route": 0.05, "fedml.lm.dense": 1.2,
                "fedml.step.reset": 0.05, "fedml.step.opt": 0.1,
                "fedml.step.emit": 0.1, "fedml.aggregate": 0.05,
                "fedml.step.train": 0.2, "fedml.lm.loss": 0.05,
                "fedml.prologue": 0.05, "unscoped": 0.05}
    ctx = _ctx(real_spec, by_scope, sum(by_scope.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    # a program without the names (the parent commit), or no trace: nothing
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert real_spec.module("metrics", reader).read(ctx) is None


def _set_model_counters(values: dict):
    from fedml_tpu.obs import model_counters

    g = model_counters()
    for k in list(g.keys()):
        g._data.pop(k)
    for k, v in values.items():
        g[k] = v


@pytest.mark.parametrize("reader,secs,rows,ok", [
    ("attn_roofline_pct", 1.0, None, True),
    ("attn_roofline_pct", 0.2, None, False),
    ("expert_mm_roofline_pct", 0.1, 0.75, True),
    ("expert_mm_roofline_pct", 0.02, 0.75, False),
    # fewer rows by the counter: a time that 0.75 rows a token could not
    # be done in (56 ms at the FLOP peak) is 78% (the weights' bytes bound it)
    ("expert_mm_roofline_pct", 0.05, 0.15, True)])
def test_roofline_share_from_shapes_and_raises_over_105(monkeypatch, real_spec,
                                                        reader, secs, rows, ok):
    from benchmarks.trace import lm_scopes

    part = "fedml.lm.attn" if reader.startswith("attn") else "fedml.lm.experts"
    ctx = _ctx(real_spec, {part: secs, "fedml.lm.dense": 1.0}, secs + 1.0)
    ctx["_red"]["xla"] = {}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", reader)
    if rows is not None:
        # 3 steps of 2 x 4,096 tokens in each of two sparse layers
        _set_model_counters({"rows.layer_1.0": rows * 8192 * 3, "steps.layer_1": 3.0,
                             "rows.layer_2.0": rows * 8192 * 2,
                             "rows.layer_2.5": rows * 8192, "steps.layer_2": 3.0})
    try:
        if ok:
            assert 0 < mod.read(ctx) <= 105.0
        else:
            with pytest.raises(RuntimeError, match="over 105%"):
                mod.read(ctx)
        if rows is not None:
            assert mod.rows_per_token(ctx) == pytest.approx(rows)
            # the share follows the counted rows
            flops = real_spec.module("flops", ctx["config"]["flops"])
            full, _ = flops.expert_train_cost_per_sample(ctx["config"])
            part_, _ = flops.expert_train_cost_per_sample(ctx["config"], rows)
            assert part_ == pytest.approx(full * rows / 0.75)
            # a program without the counter: nothing, and no raise
            _set_model_counters({})
            assert mod.read(ctx) is None
    finally:
        _set_model_counters({})


def test_expert_load_reader_takes_the_worst_layer():
    from benchmarks.metrics import expert_load_max_over_mean as reader

    _set_model_counters({})
    assert reader.read({}) is None
    _set_model_counters({
        **{f"rows.layer_1.{e}": 10.0 for e in range(4)},
        **{f"rows.layer_2.{e}": r for e, r in enumerate([30.0, 10.0, 0.0, 0.0])},
        "steps.layer_1": 4.0})
    assert reader.read({}) == pytest.approx(3.0)
    _set_model_counters({})


@pytest.mark.parametrize("variant", ["stated", "act_fp8", "act_fp8_scaled",
                                     "params_bf16", "local_bf16"])
def test_reference_variants_at_a_tiny_size(lm_spec, variant):
    """``stated`` stays near the float32 reference; each control moves a
    client's update further than ``stated`` does, or shows in the stored
    bits."""
    import jax

    from benchmarks.harness import check

    cell = lm_spec.cell("tiny_kanana2_sim")
    config = lm_spec.config(cell["config"])
    ref = lm_spec.module("references", config["reference"])
    gen = lm_spec.module("traffic", config["generator"])
    _ds, rows = gen.make(config, cell, 3)
    init = jax.device_get(ref.init(jax.random.key(3), config))
    base = check.reference_rounds(ref, config, cell, rows, init, 3, [1])
    low = check.reference_rounds(ref, config, cell, rows, init, 3, [1], variant)
    stated = check.reference_rounds(ref, config, cell, rows, init, 3, [1], "stated")

    def numbers(other):
        out = check.compare(*other, *base, init, {})
        return {n: v for n, v, *_ in out["numbers"]}

    got, near = numbers(low), numbers(stated)
    assert np.isfinite(list(got.values())).all()
    if variant == "stated":
        assert got["update_l2"] < 0.05 and got["lowp_share"] < 0.01
    elif variant.startswith("act_fp8"):
        assert got["update_l2"] > 2 * near["update_l2"]
        if variant == "act_fp8_scaled":
            # rounding noise, not a lost update
            assert got["update_l2"] < 0.5
    else:
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > (0.9 if variant == "params_bf16" else 0.2)
