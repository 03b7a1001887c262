"""The ``nemotron3_super_120b`` configuration's files: found BY NAME (no tail
of a list and no list length is pinned), true to the catalog row of the
source's config, the registered model's defaults equal to the file's
``model`` block and 700.9 M parameters counted from shapes; the tiny cell of
the same model through the harness; the FLOP counts against hand counts; the
nine parts of the round program on a made trace and the readers' silence on
another program's; the ``live_units`` counter's reader; the reference's
controls, the three of its own among them."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check
from .test_zaya1 import _set_model_counters

#: the model's settings as its public config.json gives them (the catalog
#: row of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
        "*EMEMEMEM*EMEMEMEME"),
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
REDUCED = ("num_hidden_layers", "mamba_num_heads", "n_groups",
           "num_attention_heads", "num_key_value_heads", "n_routed_experts",
           "vocab_size")
NAME, CELL, TINY = "nemotron3_super_120b", "nemotron3s_sim_c2", "tiny_nemotron3s_sim"
PROGRAM = "nemotron3_super"
NEW_READERS = ("latent_proj_ms", "latent_proj_roofline_pct", "nemo_other_ms",
               "relu2_live_pct", "sparse_rows_per_token",
               "relu2_expert_roofline_pct")
#: readers the benchmark had, whose lists the cell joins
SHARED_READERS = ("plan_ms", "enqueue_ms", "idle_in_driver_ms", "attn_ms",
                  "attn_roofline_pct", "expert_mm_ms",
                  "moe_route_ms", "dense_mm_ms", "state_update_ms",
                  "expert_load_max_over_mean", "ssd_ms",
                  "ssd_roofline_pct", "ssd_prep_ms", "ssd_decay_mean",
                  "api_init_s", "init_variables_s", "place_data_s",
                  "round_trace_s", "round_lower_s", "round_load_s",
                  "helper_programs_built", "helper_build_s")
#: and the other models' remainders and mixers, which it is not, and the two
#: readers whose rows are a mean over every layer that counts steps (here the
#: state-space layers do, and bring no row)
NOT_ITS_PART = ("held_rows_per_token", "expert_mm_roofline_pct",
                "kda_ms", "kda_roofline_pct", "kda_prep_ms", "hyb_other_ms",
                "attn_window_ms", "attn_window_roofline_pct", "win_other_ms",
                "ssm_other_ms", "cca_mix_ms", "cca_mix_roofline_pct",
                "zaya_other_ms", "skipped_tokens_pct", "lm_other_ms")


@pytest.fixture(scope="module")
def nemo_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_nemotron3s.json"))


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_file_keeps_every_published_key(real_spec, key):
    """Only what ``reduced`` lists differs from the source, and no width."""
    config = real_spec.config(NAME)
    entry = next(c for c in real_spec.doc["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(REDUCED)
    if key in REDUCED:
        assert config[key] != PUBLISHED[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert key in config and config[key] == PUBLISHED[key]


def test_model_block_is_the_registered_default_and_the_files_own_keys(real_spec):
    from fedml_tpu.models.moe import LATENT_MOE_PRESETS

    config = real_spec.config(NAME)
    m = dict(config["model"])
    assert m.pop("program_name") == PROGRAM
    assert m == LATENT_MOE_PRESETS[PROGRAM]
    same = {"dim": "hidden_size", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "v_dim": "head_dim",
            "layers": "num_hidden_layers", "held_count": "n_routed_experts",
            "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size",
            "moe_latent": "moe_latent_size",
            "shared_width": "moe_shared_expert_intermediate_size",
            "n_shared": "n_shared_experts", "eps": "layer_norm_epsilon",
            "routed_scaling": "routed_scaling_factor",
            "ssd_heads": "mamba_num_heads", "ssd_head_dim": "mamba_head_dim",
            "ssd_state": "ssm_state_size", "ssd_conv": "conv_kernel"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # no width is cut; the router keeps the published experts and choices
    assert m["n_routed"] == config["published"]["n_routed_experts"] == 512
    assert m["mlp_form"] == config["mlp_hidden_act"] == "relu2"
    assert m["rope"] == 0 and config["n_groups"] == 1
    assert not config["tie_word_embeddings"]
    # the share of heads is the same eighth in both kinds of mixer, and a
    # whole group of the state-space mixer's
    pub = config["published"]
    assert pub["mamba_num_heads"] // pub["n_groups"] == m["ssd_heads"] == 16
    assert pub["num_attention_heads"] // 8 == m["heads"] == 4
    # the pattern's first eleven entries are what is built: one sub-layer a
    # layer, five M, five E, one *
    kinds = {"M": ("ssd", "none"), "E": ("none", "sparse"), "*": ("full", "none")}
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == pub["num_hidden_layers"] == 88
    assert [kinds[c] for c in pattern[:m["layers"]]] == list(
        zip(m["mixers"], m["mlps"]))
    assert sorted(pattern[:11]) == sorted("MMMMMEEEEE*")
    # the floors: a whole period, eight experts, an eighth of the table
    assert m["held_count"] >= 8
    assert config["data"]["vocab"] == config["vocab_size"] == 16384
    assert config["vocab_size"] * 8 == pub["vocab_size"]
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    assert config["recipe"]["batch_size"] == 1
    for key in ("deployment", "assumed", "departures", "reduced", "not_built"):
        assert config[key]
    assert "64 chips share each layer" in config["deployment"]
    for key in ("position_encoding", "dt_limits", "conv_activation",
                "gated_norm", "router", "init", "optimizer", "held_indices"):
        assert key in config["assumed"], key
    assert "chunk_size" in config["departures"]
    # what differs from ISSUE 44's seeds is said as a departure
    assert "balancing_bias_seed" in config["departures"]
    assert "multi_token_prediction" in config["not_built"]
    assert config["precision"]["router_scores"] == "float32"


def test_parameters_are_the_files_arithmetic(real_spec):
    """700.9 M, part by part, as the built tree has them: counted from
    shapes, nothing is allocated."""
    import jax

    from fedml_tpu.models import create_model

    config = real_spec.config(NAME)
    want = config["parameters"]
    shapes = jax.eval_shape(create_model(PROGRAM, 16384).init,
                            jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == want["total"] == 700_865_520
    ssd = p["layer_0"]["ssd"]
    assert count(ssd["in_proj"]) == want["mamba_in_proj"] == 4096 * 2320
    assert count(ssd["conv_kernel"]) + count(ssd["conv_bias"]) == want["mamba_conv"]
    assert sum(count(ssd[k]) for k in ("A_log", "D", "dt_bias")) == \
        want["mamba_a_d_dt"]
    assert count(ssd["norm"]) == want["mamba_gated_norm"] == 1024
    assert count(ssd["out_proj"]) == want["mamba_out_proj"]
    assert set(p["layer_0"]) == {"attn_norm", "ssd"}
    assert count(p["layer_0"]) == want["mamba_layer"] == 13_708_592
    assert set(p["layer_7"]) == {"attn_norm", "attn"}
    assert count(p["layer_7"]["attn"]) == want["attention_mixer"]
    assert count(p["layer_7"]) == want["attention_layer"] == 5_246_976
    mlp = p["layer_1"]["mlp"]
    assert set(p["layer_1"]) == {"mlp_norm", "mlp"}
    assert count(mlp["router"]) + count(mlp["e_score_correction_bias"]) == \
        want["router"]
    assert count(mlp["latent_in"]) + count(mlp["latent_out"]) == \
        want["latent_projections"]
    assert count(mlp["shared"]) == want["shared_expert"] == 2 * 4096 * 5376
    assert mlp["up"].shape == (8, 1024, 2688)
    assert mlp["down"].shape == (8, 2688, 1024) and "gate" not in mlp
    assert count(mlp["up"]) + count(mlp["down"]) == want["experts_held"]
    assert count(p["layer_1"]) == want["sparse_layer"] == 98_570_752
    assert count(p["embed"]) == want["embedding"] == count(p["lm_head"]) == \
        want["head"]
    assert want["total"] == (5 * want["mamba_layer"] + want["attention_layer"]
                             + 5 * want["sparse_layer"] + want["embedding"]
                             + want["head"] + want["final_norm"])
    # one sub-layer's counters a layer
    c = shapes["counters"]
    assert set(c["layer_0"]) == {"ssd"} and set(c["layer_1"]) == {"mlp"}
    assert set(c["layer_1"]["mlp"]) == {"expert_rows", "live_units", "steps"}
    assert "layer_7" not in c
    # the reference's seeded tree is the program's
    ref = real_spec.module("references", config["reference"])
    ours = jax.eval_shape(lambda k: ref.init(k, config), jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, ours) == \
        jax.tree.map(lambda s: s.shape, dict(shapes))


@pytest.mark.parametrize("spec_name,cell", [("real", CELL), ("tiny", TINY)])
def test_cell_files_are_found_by_name(real_spec, nemo_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else nemo_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated"} == set(ref.VARIANTS) - set(ref.CONTROLS)
    assert {"relu_plain", "scale_plain", "state_cut", "act_fp8_scaled",
            "params_bf16", "local_bf16"} == set(ref.CONTROLS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    assert config["recipe"]["batch_size"] == 1
    names = {m["name"] for m in spec.metric_entries("per_layer", cell)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= names
    # one remainder a cell, and no reader of a mixer it has not
    assert not set(NOT_ITS_PART) & names
    for n in NEW_READERS + SHARED_READERS:
        assert callable(spec.module("metrics", n).read)


def test_real_benchmark_has_the_cell_and_its_metrics_by_name(real_spec):
    """Entries are looked up by name: where they stand in their lists and
    how long a list is belongs to no configuration."""
    doc = real_spec.doc
    config = next(c for c in doc["configs"] if c["name"] == NAME)
    assert config["file"] == f"benchmarks/configs/{NAME}.json"
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/"
        "blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(REDUCED)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "sim_c2_t4096_b1", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for n in NEW_READERS:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "real_samples_per_s"
        assert set(by_name[n]) == {"name", "unit", "better", "source", "layer",
                                   "moves", "workloads"}
    assert by_name["latent_proj_roofline_pct"]["unit"] == "%"
    assert by_name["latent_proj_ms"]["layer"] == "kernels"
    assert by_name["relu2_live_pct"]["source"] == "program_counter"
    for n in SHARED_READERS:
        assert CELL in by_name[n]["workloads"]
    for n in NOT_ITS_PART:
        assert CELL not in by_name[n]["workloads"]
    # limits are the check's own names, each with a reading behind it
    limits = real_spec.cell(CELL)["limits"]
    assert set(limits) == {"loss_rel", "update_norm_gap", "change_norm_gap",
                           "update_l2", "update_leaf_l2", "lowp_share"}
    assert "PLACEHOLDER" not in real_spec.cell(CELL)["limits_note"]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmarks", "references", NAME + ".py")).read()
    assert "fedml_tpu" not in src.replace("``fedml_tpu``", "")


def test_required_flops_are_the_hand_counts(real_spec):
    """One layer of each kind by hand, then the step."""
    config = real_spec.config(NAME)
    f = real_spec.module("flops", config["flops"])
    t, d = 4096, 4096
    assert f.routed_rows_per_token(config) == pytest.approx(22 * 8 / 512)
    # M: the state's own work, 3 multiply-adds an entry of 16 heads' 64 x 128
    # states a token forward, twice that backward, five layers
    ssd, ssd_bytes = f.ssd_train_cost_per_sample(config)
    assert ssd == pytest.approx(3 * 2 * 3 * 64 * 128 * 16 * t * 5)
    assert ssd_bytes == pytest.approx(
        2 * t * (2 * 16 * 64 * 2 + 2 * 128 * 2 + 16 * 4) * 5)
    # *: 4 query heads over 1 of 128, causal, one layer
    attn, attn_bytes = f.attn_train_cost_per_sample(config)
    assert attn == pytest.approx(3 * 2 * (t * (t + 1) / 2) * 4 * 2 * 128)
    assert attn_bytes == pytest.approx(2 * t * 128 * (5 * 4 + 6 * 1))
    # E: an expert is TWO matrices of 1024 x 2688, at 0.34375 rows a token
    experts, expert_bytes = f.expert_train_cost_per_sample(config)
    rows = t * 22 * 8 / 512
    assert experts == pytest.approx(3 * rows * 2 * 2 * 1024 * 2688 * 5)
    assert expert_bytes == pytest.approx(2 * 3 * 5 * (
        rows * 2 * (1024 + 2688) + 8 * 2 * 1024 * 2688))
    # the held experts' weights bound them: read once a pass for 1,408 rows
    assert expert_bytes / 819e9 > experts / 197e12
    # a run's own rows a token and sparse layer are taken as they are
    counted, _ = f.expert_train_cost_per_sample(
        config, rows_per_token=22 * 8 / 512)
    assert counted == pytest.approx(experts)
    half, _ = f.expert_train_cost_per_sample(
        config, rows_per_token=0.5 * 22 * 8 / 512)
    assert half == pytest.approx(experts / 2)
    # the two projections of 4096 x 1024 on every token of five layers
    latent, latent_bytes = f.latent_proj_train_cost_per_sample(config)
    assert latent == pytest.approx(3 * t * 2 * 2 * d * 1024 * 5)
    assert latent_bytes == pytest.approx(
        2 * 3 * 5 * (t * 2 * (d + 1024) + 2 * d * 1024))
    assert latent / 197e12 > latent_bytes / 819e9
    mixer = d * (1024 + 1024 + 256 + 16) + 1024 * d
    full = 2 * d * 512 + 2 * d * 128
    sparse = 2 * d * 5376 + d * 512
    per_token = 5 * mixer + full + 5 * sparse + d * 16384
    assert f.dense_fwd_flops_per_token(config) == pytest.approx(2.0 * per_token)
    total = f.train_flops_per_sample(config)
    assert total == pytest.approx(
        3 * t * 2 * per_token + latent + experts + attn + ssd)
    # ISSUE 44's 846 MFLOP a token forward without the scores and the state
    # (the shared MLPs, the projections, the router and the routed part:
    # two thirds of it)
    fwd = (3 * t * 2 * per_token + latent + experts) / 3 / t
    assert fwd == pytest.approx(845.8e6, rel=1e-3)
    of_sparse = 2 * 5 * (sparse + 2 * d * 1024) + experts / 3 / t
    assert of_sparse / fwd == pytest.approx(0.667, abs=0.01)
    assert total == pytest.approx(10.49e12, rel=2e-3)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, nemo_spec, trace):
    rc = run.main(["--workload", TINY, "--seed", str(2**31 + 11),
                   "--seconds", "0.3", "--trace", trace], spec=nemo_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counters are read on the CPU too; the trace's parts need a TPU
        assert 20 < res["metrics"]["relu2_live_pct"]["value"] < 80
        assert 0 < res["metrics"]["ssd_decay_mean"]["value"] < 1
        # 4 x 4 / 16 rows a token and sparse layer, over the sparse layers'
        # steps alone
        assert 0.5 < res["metrics"]["sparse_rows_per_token"]["value"] < 1.5
        assert "dispatch_ms" in res["metrics"]
        assert not set(NOT_ITS_PART) & set(res["metrics"])


def _ctx(real_spec, by_scope, busy, rounds=2):
    class W:
        pass

    w = W()
    w.rounds = [(1, 0, 0, 0)] * rounds
    return {"spec": real_spec, "cell": real_spec.cell(CELL),
            "config": real_spec.config(NAME), "window": w, "trace": {"x": 1},
            "padded_samples": 16,
            "devices": {"kind": "TPU v5 lite", "count": 1, "platform": "tpu"},
            "_red": {"by_scope_s": by_scope, "busy_s": busy, "xla": {}}}


MADE = {"fedml.lm.latent_proj": 0.2, "fedml.lm.attn": 0.1, "fedml.lm.ssd": 0.3,
        "fedml.lm.ssd_prep": 0.15, "fedml.lm.experts": 0.25,
        "fedml.lm.route": 0.35, "fedml.lm.dense": 0.8, "fedml.step.reset": 0.05,
        "fedml.step.opt": 0.1, "fedml.step.emit": 0.1, "fedml.aggregate": 0.05,
        "fedml.step.train": 0.2, "fedml.lm.loss": 0.05, "fedml.prologue": 0.05,
        "unscoped": 0.05}


@pytest.mark.parametrize("reader,want", [
    ("latent_proj_ms", 100.0), ("attn_ms", 50.0), ("ssd_ms", 150.0),
    ("ssd_prep_ms", 75.0), ("expert_mm_ms", 125.0), ("moe_route_ms", 175.0),
    ("dense_mm_ms", 400.0), ("state_update_ms", 150.0),
    ("nemo_other_ms", 175.0)])
def test_nine_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    """Seven parts by the readers the benchmark had, the projections' and
    the remainder: together the module's whole time."""
    from benchmarks.trace import latent_scopes, lm_scopes, ssd_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    ours, theirs = latent_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    mixers = ssd_scopes.parts_s(ctx)
    assert (sum(ours.values()) + mixers["ssd"] + mixers["ssd_prep"]
            + sum(v for k, v in theirs.items() if k != "other")
            ) == pytest.approx(sum(MADE.values()))
    if reader not in NEW_READERS:
        return
    # another LM's trace (no latent), the parent commit, or no trace
    other = {k: v for k, v in MADE.items() if k != "fedml.lm.latent_proj"}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: {
        "by_scope_s": other, "busy_s": sum(other.values())})
    assert latent_scopes.parts_s(ctx) is None
    assert real_spec.module("metrics", reader).read(ctx) is None
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert real_spec.module("metrics", reader).read(ctx) is None


@pytest.mark.parametrize("reader", NEW_READERS[:3])
def test_readers_say_nothing_on_another_programs_recorded_trace(
        monkeypatch, real_spec, reader):
    """The conv cell's recorded TPU trace has no ``fedml.lm.*`` name: the
    new readers return None and do not raise."""
    from benchmarks.trace import lm_scopes, scopes

    recorded = os.path.join(HERE, "fixtures", "trace",
                            "tiny_sim_tpu_v5e.xplane.pb")
    monkeypatch.setattr(scopes, "trace_path", lambda ctx: recorded)
    ctx = _ctx(real_spec, {}, 0.0)
    assert lm_scopes.reduce_ctx(ctx) is None
    assert real_spec.module("metrics", reader).read(ctx) is None


@pytest.mark.parametrize("secs,ok", [(1.0, True), (0.005, False)])
def test_the_projections_share_is_of_their_own_work_and_raises_over_105(
        monkeypatch, real_spec, capsys, secs, ok):
    from benchmarks.trace import lm_scopes

    made = {**MADE, "fedml.lm.latent_proj": secs}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", "latent_proj_roofline_pct")
    if not ok:
        with pytest.raises(RuntimeError, match="over 105%"):
            mod.read(ctx)
        return
    flops, nbytes = real_spec.module(
        "flops", NAME).latent_proj_train_cost_per_sample(ctx["config"])
    want = 100 * 16 * max(flops / 197e12, nbytes / 819e9) / secs
    assert mod.read(ctx) == pytest.approx(want) and 0 < want < 100
    assert "bound by FLOPs" in capsys.readouterr().out


@pytest.mark.parametrize("secs,ok", [(1.0, True), (0.002, False)])
def test_the_experts_share_takes_the_sparse_layers_rows_and_raises_over_105(
        monkeypatch, real_spec, capsys, secs, ok):
    """Rows over the SPARSE layers' steps go to the cost function as they
    are; a state-space layer's steps are in nobody's mean."""
    from benchmarks.trace import lm_scopes

    made = {**MADE, "fedml.lm.experts": secs}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", "relu2_expert_roofline_pct")
    _set_model_counters({})
    assert mod.read(ctx) is None
    try:
        _set_model_counters({"rows.layer_1.0": 4096 * 1.0, "steps.layer_1": 4.0,
                             "rows.layer_3.5": 4096 * 1.0, "steps.layer_3": 4.0,
                             "decay.layer_0": 3.2, "steps.layer_0": 4.0})
        if not ok:
            with pytest.raises(RuntimeError, match="over 105%"):
                mod.read(ctx)
            return
        flops, nbytes = real_spec.module(
            "flops", NAME).expert_train_cost_per_sample(
                ctx["config"], rows_per_token=0.25)
        want = 100 * 16 * max(flops / 197e12, nbytes / 819e9) / secs
        assert mod.read(ctx) == pytest.approx(want) and 0 < want < 100
        assert "0.25 rows of held experts" in capsys.readouterr().out
    finally:
        _set_model_counters({})


def test_live_units_reader_is_a_share_of_the_sparse_layers_steps(
        real_spec, capsys):
    mod = real_spec.module("metrics", "relu2_live_pct")
    ctx = {"config": real_spec.config(NAME)}
    _set_model_counters({})
    assert mod.read(ctx) is None
    try:
        # a layer without the counter (a state-space mixer, another model's
        # sparse layer) is not in the mean
        _set_model_counters({"live_units.layer_1": 2.0, "steps.layer_1": 4.0,
                             "live_units.layer_3": 1.0, "steps.layer_3": 4.0,
                             "decay.layer_0": 3.2, "steps.layer_0": 4.0,
                             "rows.layer_1.0": 4096 * 3.0,
                             "rows.layer_3.1": 4096 * 1.5})
        assert mod.read(ctx) == pytest.approx(100 * 3.0 / 8)
        assert "8 layer-steps of 2 sparse layers" in capsys.readouterr().out
        # the rows' reader divides by the steps of the layers that keep
        # rows, where the shared one takes every layer that counts steps
        rows = real_spec.module("metrics", "sparse_rows_per_token")
        assert rows.read(ctx) == pytest.approx(4.5 / 8)
        assert "8 layer-steps of 2 sparse layers" in capsys.readouterr().out
        shared = real_spec.module("metrics", "expert_mm_roofline_pct")
        assert shared.rows_per_token(ctx) == pytest.approx(4.5 / 12)
        assert real_spec.module("metrics", "ssd_decay_mean").read(ctx) == \
            pytest.approx(0.8)
    finally:
        _set_model_counters({})


def test_seeded_correction_bias_holds_the_loads_even(real_spec):
    """A seeded router prefers a few experts for every token (scores with a
    common part); under the balanced bias the 22 largest of score + bias
    give every one of the 512 its even share, the held eight among them."""
    import jax
    import jax.numpy as jnp

    ref = real_spec.module("references", NAME)
    k0, k1 = jax.random.split(jax.random.key(0))
    s = jax.nn.sigmoid(1.28 * jax.random.normal(k0, (4096, 512))
                       + jax.random.normal(k1, (512,)))
    bias = jax.jit(lambda s: ref.balanced_bias(s, 22))(s)
    assert abs(float(jnp.mean(bias))) < 1e-6

    def loads(b):
        idx = np.asarray(jax.lax.top_k(s + b, 22)[1]).ravel()
        return np.bincount(idx, minlength=512) * 512 / idx.size

    before, after = loads(jnp.zeros(512)), loads(bias)
    assert before.max() > 5 and before.min() < 0.1
    assert after.max() < 1.05 and after.min() > 0.95
    assert after[:8].sum() * 22 / 512 == pytest.approx(22 * 8 / 512, rel=0.02)


@pytest.fixture(scope="module")
def tiny_rounds(nemo_spec):
    """``numbers(variant)``: one round of the tiny cell by a variant of the
    reference against the float32 reference, each variant computed once."""
    import jax

    from benchmarks.harness import check

    cell = nemo_spec.cell(TINY)
    config = nemo_spec.config(cell["config"])
    ref = nemo_spec.module("references", config["reference"])
    gen = nemo_spec.module("traffic", config["generator"])
    _ds, rows = gen.make(config, cell, 3)
    init = jax.device_get(jax.jit(lambda k: ref.init(k, config))(jax.random.key(3)))
    done = {}
    built, ref._built = ref._built, {}

    def rounds(variant):
        if variant not in done:
            done[variant] = check.reference_rounds(
                ref, config, cell, rows, init, 3, [1], variant)
        return done[variant]

    def numbers(variant, against="reference"):
        out = check.compare(*rounds(variant), *rounds(against), init,
                            cell["limits"])
        return {n: v for n, v, *_ in out["numbers"]}, out["ok"]

    numbers.rounds, numbers.limits = rounds, cell["limits"]
    yield numbers
    ref._built = built


@pytest.mark.parametrize("variant", ["stated", "act_fp8_scaled", "params_bf16",
                                     "local_bf16", "relu_plain", "scale_plain",
                                     "state_cut"])
def test_reference_variants_at_a_tiny_size(tiny_rounds, variant):
    """``stated`` stays near the float32 reference; e4m3's noise moves a
    client's update further than ``stated`` does; parameters kept in bf16
    show in the aggregate's bits and fail ``lowp_share``; the three controls
    of the configuration's own (the square left out, the scaling factor
    ignored, the carry between chunks lost), put in the program's place
    against ``stated`` itself, FAIL the tiny cell's check, which ``stated``
    in its own place passes."""
    import jax

    (got, _), (near, _) = tiny_rounds(variant), tiny_rounds("stated")
    assert np.isfinite(list(got.values())).all()
    limits = tiny_rounds.limits
    if variant == "stated":
        # the reference hands back host trees (its note on memory)
        _losses, states = tiny_rounds.rounds(variant)
        assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(states))
        assert got["update_l2"] < 0.05 and got["lowp_share"] < 0.01
        assert tiny_rounds("stated", against="stated")[1]
    elif variant == "act_fp8_scaled":
        assert got["update_l2"] > 3 * near["update_l2"]
    elif variant in ("params_bf16", "local_bf16"):
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > (0.9 if variant == "params_bf16" else 0.2)
        assert got["lowp_share"] > limits["lowp_share"]
    else:
        apart, ok = tiny_rounds(variant, against="stated")
        assert not ok
        # at hidden 32 the routed part and a state of 32 positions are
        # faint; the square is not (on the chip: PERF.md section 2)
        room = 50 if variant == "relu_plain" else 2
        assert apart["update_l2"] > room * limits["update_l2"]
        assert apart["lowp_share"] < 0.01
