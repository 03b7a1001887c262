"""The ``zaya1_8b`` configuration's files: found BY NAME (no tail of a list
and no list length is pinned), true to the catalog row of the source's
config, the registered model's defaults equal to the file's ``model`` block
and 708.7 M parameters counted from shapes; the tiny cell of the same model
through the harness; the FLOP counts against hand counts; the seven parts of
the round program on a made trace and the readers' silence on another
program's recorded one; the reference's controls, the two of its own among
them."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check

#: the model's settings as its public config.json gives them (the catalog
#: row of Zyphra/ZAYA1-8B)
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "layer_types": ["hybrid"] * 40,
    "lm_head_bias": False, "max_position_embeddings": 131072,
    "model_type": "zaya", "moe_intermediate_size": 2048,
    "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
    "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
REDUCED = ("num_hidden_layers", "num_experts", "vocab_size")
NAME, CELL, TINY = "zaya1_8b", "zaya1_sim_c2", "tiny_zaya1_sim"
NEW_READERS = ("cca_mix_ms", "cca_mix_roofline_pct", "zaya_other_ms",
               "skipped_tokens_pct")
#: readers the benchmark had, whose lists the cell joins
SHARED_READERS = ("plan_ms", "enqueue_ms", "idle_in_driver_ms", "attn_ms",
                  "attn_roofline_pct", "expert_mm_ms", "expert_mm_roofline_pct",
                  "moe_route_ms", "expert_load_max_over_mean",
                  "held_rows_per_token", "dense_mm_ms", "state_update_ms",
                  "api_init_s", "init_variables_s", "place_data_s",
                  "round_trace_s", "round_lower_s", "round_load_s",
                  "helper_programs_built", "helper_build_s")
#: and the scan's, the window's and the state-space readers, which it is not
NOT_ITS_PART = ("kda_ms", "kda_roofline_pct", "kda_prep_ms", "hyb_other_ms",
                "attn_window_ms", "attn_window_roofline_pct", "win_other_ms",
                "ssd_ms", "ssd_roofline_pct", "ssd_prep_ms", "ssm_other_ms",
                "ssd_decay_mean", "lm_other_ms")


@pytest.fixture(scope="module")
def zaya_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_zaya1.json"))


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
    assert m.pop("program_name") == NAME
    assert m == LATENT_MOE_PRESETS[NAME]
    same = {"dim": "hidden_size", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "v_dim": "head_dim",
            "layers": "num_hidden_layers", "held_count": "num_experts",
            "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size", "eps": "rms_norm_eps",
            "router_hidden": "router_hidden_size",
            "tied_head": "tie_word_embeddings"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # no width is cut: attention runs in a latent of heads x head_dim, half
    # the model's width; rotary over half a head; the router keeps the
    # published experts and one output more, and its one choice
    assert m["heads"] * m["v_dim"] * 2 == config["hidden_size"]
    assert m["rope"] == config["partial_rotary_factor"] * config["head_dim"]
    assert m["rope_theta"] == config["rope_parameters"]["hybrid"]["rope_theta"]
    assert m["cca_conv"] == [config["cca_time0"], config["cca_time1"]]
    assert m["n_routed"] == config["published"]["num_experts"] == 16
    assert m["scaled_residual"] and m["top_k"] == 1
    # the load moves the balancing bias by 0.39 of the scores' spread a unit
    # of excess load, through the recipe's own SGD step
    assert m["balance_rate"] * config["recipe"]["lr"] == pytest.approx(0.39)
    assert m["n_shared"] == 0 and m["first_dense"] == 0
    assert m["mixers"] == ["cca"] * m["layers"]
    assert set(config["layer_types"]) == {"hybrid"}
    assert len(config["layer_types"]) == config["published"]["num_hidden_layers"]
    # the floors: at least four layers, eight experts, an eighth of the table
    assert m["layers"] >= 4 and m["held_count"] >= 8
    assert config["data"]["vocab"] == config["vocab_size"] == 32784
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    for key in ("deployment", "assumed", "departures", "reduced"):
        assert config[key]
    for key in ("family_mechanisms", "router_carry", "skip_choice",
                "balancing_bias", "key_temperature", "init"):
        assert key in config["assumed"], key
    # what differs from ISSUE 39's seeds is said as a departure
    for key in ("key_temperature_seed", "balancing_bias_seed",
                "balancing_bias_moves"):
        assert key in config["departures"], key
    assert "tau 2" in config["assumed"]["init"]


def test_parameters_are_the_files_arithmetic(real_spec):
    """708.7 M, part by part, as the built tree has them: counted from
    shapes, nothing is allocated."""
    import jax

    from fedml_tpu.models import create_model

    config = real_spec.config(NAME)
    want = config["parameters"]
    shapes = jax.eval_shape(create_model(NAME, 32784).init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == want["total"] == 708_664_951
    attn = p["layer_1"]["attn"]
    for leaf in ("q_proj", "k_proj", "v_proj", "o_proj"):
        assert count(attn[leaf]) == want[f"cca_{leaf}"]
    assert count(attn["conv0_kernel"]) + count(attn["conv0_bias"]) == \
        want["cca_depthwise_conv"]
    assert count(attn["conv1_kernel"]) + count(attn["conv1_bias"]) == \
        want["cca_headwise_conv"]
    assert count(attn["k_temp"]) == want["cca_key_temperature"]
    assert count(attn) == want["cca"] == 5_575_682
    mlp = p["layer_1"]["mlp"]
    assert count(mlp["router"]) == want["router"] == 660_754
    assert count(p["layer_0"]["mlp"]["router"]) == want["router_layer_0"]
    assert sum(count(mlp[k]) for k in ("gate", "up", "down")) == want["experts_held"]
    assert count(p["layer_1"]["attn_norm"]) + count(p["layer_1"]["mlp_norm"]) == \
        want["layer_norms"]
    assert count(p["layer_1"]["attn_merge"]) + count(p["layer_1"]["mlp_merge"]) == \
        want["residual_scales"]
    assert count(p["layer_0"]) == want["layer_0"] == want["layer"] - 1
    assert count(p["layer_1"]) == want["layer"]
    assert count(p["embed"]) == want["tied_table"] and "lm_head" not in p
    assert want["total"] == (want["layer_0"] + 5 * want["layer"]
                             + want["tied_table"] + want["final_norm"])
    # the reference's seeded tree is the program's
    ref = real_spec.module("references", config["reference"])
    ours = jax.eval_shape(lambda k: ref.init(k, config), jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, ours) == \
        jax.tree.map(lambda s: s.shape, dict(shapes))


@pytest.mark.parametrize("spec_name,cell", [("real", CELL), ("tiny", TINY)])
def test_cell_files_are_found_by_name(real_spec, zaya_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else zaya_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated"} <= set(ref.VARIANTS) - set(ref.CONTROLS)
    assert {"mix_plain", "router_alone", "act_fp8_scaled", "params_bf16",
            "local_bf16"} <= set(ref.CONTROLS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    assert config["recipe"]["batch_size"] == 2
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
    assert config["source"] == ("https://huggingface.co/Zyphra/ZAYA1-8B/"
                                "blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(REDUCED)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "sim_c2_t4096", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for n in NEW_READERS:
        assert CELL in by_name[n]["workloads"]
        assert by_name[n]["moves"] == "real_samples_per_s"
        assert set(by_name[n]) == {"name", "unit", "better", "source", "layer",
                                   "moves", "workloads"}
    assert by_name["cca_mix_roofline_pct"]["unit"] == "%"
    assert by_name["skipped_tokens_pct"]["source"] == "program_counter"
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
    config = real_spec.config(NAME)
    f = real_spec.module("flops", config["flops"])
    t, d = 4096, 2048
    assert f.routed_rows_per_token(config) == pytest.approx(8 / 17)
    # the mixing's own work: 2 taps x 10 heads x 128 x 128 multiply-adds a
    # token forward, twice that backward, six layers
    mix, mix_bytes = f.cca_mix_train_cost_per_sample(config)
    assert mix == pytest.approx(3 * 2 * 2 * 10 * 128 * 128 * t * 6)
    # q~, k~, v in and q, k, v out in bf16, and their cotangents back
    assert mix_bytes == pytest.approx(2 * 6 * t * 2 * 2 * (1280 + 256))
    # the bytes bound it: 0.25 ms of FLOPs, 0.37 of bytes a sequence
    assert mix / 197e12 == pytest.approx(0.2453e-3, rel=1e-3)
    assert mix_bytes / 819e9 == pytest.approx(0.3687e-3, rel=1e-3)
    attn, attn_bytes = f.attn_train_cost_per_sample(config)
    assert attn == pytest.approx(3 * 2 * (t * (t + 1) / 2) * 8 * 2 * 128 * 6)
    assert attn_bytes == pytest.approx(2 * t * 128 * (5 * 8 + 6 * 2) * 6)
    experts, expert_bytes = f.expert_train_cost_per_sample(config)
    assert experts == pytest.approx(3 * (t * 8 / 17) * 3 * 2 * d * d * 6)
    # the FLOPs bound them, for the first time in a cell: 4.4 ms against 3.1
    assert experts / 197e12 > expert_bytes / 819e9
    full, _ = f.expert_train_cost_per_sample(config, rows_per_token=1.0)
    assert full == pytest.approx(experts * 17 / 8)
    mixer = 2 * d * 1024 + 2 * d * 256
    router = d * 256 + 2 * 256 * 256 + 256 * 17
    per_token = 6 * (mixer + router) + d * 32784
    assert f.dense_fwd_flops_per_token(config) == pytest.approx(2.0 * per_token)
    total = f.train_flops_per_sample(config)
    assert total == pytest.approx(3 * t * 2 * per_token + attn + mix + experts)
    # ISSUE 39's 8.1 TFLOP a step of two sequences; the head 41% of a token
    assert 2 * total == pytest.approx(8.12e12, rel=2e-3)
    assert 2 * d * 32784 / (total / 3 / t) == pytest.approx(0.406, abs=0.003)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, zaya_spec, trace):
    rc = run.main(["--workload", TINY, "--seed", str(2**31 + 11),
                   "--seconds", "0.3", "--trace", trace], spec=zaya_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counters are read on the CPU too; the trace's parts need a TPU
        # (64 tokens a step: a tiny router's choices are far from even)
        assert 0 <= res["metrics"]["skipped_tokens_pct"]["value"] <= 100
        assert 0 < res["metrics"]["held_rows_per_token"]["value"] < 1
        assert "dispatch_ms" in res["metrics"]
        assert not set(NOT_ITS_PART) & set(res["metrics"])


def _ctx(real_spec, by_scope, busy, rounds=2):
    class W:
        pass

    w = W()
    w.rounds = [(1, 0, 0, 0)] * rounds
    return {"spec": real_spec, "cell": real_spec.cell(CELL),
            "config": real_spec.config(NAME), "window": w, "trace": {"x": 1},
            "padded_samples": 32,
            "devices": {"kind": "TPU v5 lite", "count": 1, "platform": "tpu"},
            "_red": {"by_scope_s": by_scope, "busy_s": busy, "xla": {}}}


MADE = {"fedml.lm.cca_mix": 0.2, "fedml.lm.attn": 0.3, "fedml.lm.experts": 0.5,
        "fedml.lm.route": 0.25, "fedml.lm.dense": 0.8, "fedml.step.reset": 0.05,
        "fedml.step.opt": 0.1, "fedml.step.emit": 0.1, "fedml.aggregate": 0.05,
        "fedml.step.train": 0.2, "fedml.lm.loss": 0.05, "fedml.prologue": 0.05,
        "unscoped": 0.05}


@pytest.mark.parametrize("reader,want", [
    ("cca_mix_ms", 100.0), ("attn_ms", 150.0), ("expert_mm_ms", 250.0),
    ("moe_route_ms", 125.0), ("dense_mm_ms", 400.0), ("state_update_ms", 150.0),
    ("zaya_other_ms", 175.0)])
def test_seven_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    """Five parts by the LM cells' shared readers, the mixing's and the
    remainder it leaves: together the module's whole time."""
    from benchmarks.trace import cca_scopes, lm_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    ours, theirs = cca_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    assert (sum(ours.values()) + sum(v for k, v in theirs.items() if k != "other")
            ) == pytest.approx(sum(MADE.values()))
    if reader not in NEW_READERS:
        return
    # another LM's trace (no such mixer), the parent commit, or no trace
    other = {k: v for k, v in MADE.items() if k != "fedml.lm.cca_mix"}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: {
        "by_scope_s": other, "busy_s": sum(other.values())})
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
def test_the_mixings_share_is_of_its_own_work_and_raises_over_105(
        monkeypatch, real_spec, capsys, secs, ok):
    from benchmarks.trace import cca_scopes, lm_scopes

    made = {**MADE, "fedml.lm.cca_mix": secs}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", "cca_mix_roofline_pct")
    if not ok:
        with pytest.raises(RuntimeError, match="over 105%"):
            mod.read(ctx)
        return
    flops, nbytes = real_spec.module("flops", NAME).cca_mix_train_cost_per_sample(
        ctx["config"])
    want = 100 * 32 * max(flops / 197e12, nbytes / 819e9) / secs
    assert mod.read(ctx) == pytest.approx(want) and 0 < want < 100
    assert "bound by bytes" in capsys.readouterr().out
    # the reader serves whatever part it is told, and says nothing where the
    # configuration's FLOP file has no such function
    assert cca_scopes.roofline_pct(ctx, 1.0, "attn_train_cost_per_sample",
                                   "attn") == pytest.approx(
        real_spec.module("metrics", "attn_roofline_pct").read(
            {**ctx, "_red": {**ctx["_red"], "by_scope_s": {
                **made, "fedml.lm.attn": 1.0}}}))
    assert cca_scopes.roofline_pct(ctx, 1.0, "no_such_cost", "x") is None
    assert cca_scopes.roofline_pct(ctx, 0.0, "attn_train_cost_per_sample",
                                   "x") is None


def _set_model_counters(values: dict):
    from fedml_tpu.obs import model_counters

    g = model_counters()
    for k in list(g.keys()):
        g._data.pop(k)
    for k, v in values.items():
        g[k] = v


def test_skipped_reader_is_a_share_of_the_trained_tokens(real_spec, capsys):
    mod = real_spec.module("metrics", "skipped_tokens_pct")
    ctx = {"config": real_spec.config(NAME)}
    _set_model_counters({})
    assert mod.read(ctx) is None
    try:
        # a layer without the choice (another model's) is not in the mean
        _set_model_counters({"skipped.layer_0": 8192 * 0.5, "steps.layer_0": 4.0,
                             "skipped.layer_1": 8192 * 1.5, "steps.layer_1": 4.0,
                             "steps.layer_2": 4.0, "rows.layer_2.0": 5.0,
                             "rows.layer_0.3": 7.0})
        assert mod.read(ctx) == pytest.approx(100 * 2.0 / 8)
        assert "8 layer-steps of 8192 tokens in 2 layers" in capsys.readouterr().out
        # the rows' readers do not count the choice that is no expert
        rows = real_spec.module("metrics", "expert_mm_roofline_pct").rows_per_token(ctx)
        assert rows == pytest.approx(12.0 / (12 * 8192))
    finally:
        _set_model_counters({})


@pytest.fixture(scope="module")
def tiny_rounds(zaya_spec):
    """``numbers(variant)``: one round of the tiny cell by a variant of the
    reference against the float32 reference, each variant computed once."""
    import jax

    from benchmarks.harness import check

    cell = zaya_spec.cell(TINY)
    config = zaya_spec.config(cell["config"])
    ref = zaya_spec.module("references", config["reference"])
    gen = zaya_spec.module("traffic", config["generator"])
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

    def local_train(variant):
        return ref.local_train(
            config, init, *(a[0][None, :2].reshape((1, 1, 2) + a[0].shape[1:])
                            for a in rows([0])[:3]), 1, variant)

    numbers.local_train, numbers.limits = local_train, cell["limits"]
    yield numbers
    ref._built = built


@pytest.mark.parametrize("variant", ["stated", "act_fp8_scaled", "params_bf16",
                                     "local_bf16", "mix_plain", "router_alone"])
def test_reference_variants_at_a_tiny_size(tiny_rounds, variant):
    """``stated`` stays near the float32 reference; e4m3's noise moves a
    client's update further than ``stated`` does; parameters kept in bf16
    show in the aggregate's bits and fail ``lowp_share``; the two controls
    of the configuration's own (the mixing left out, the routers cut off
    from one another), put in the program's place against ``stated``
    itself, FAIL the tiny cell's check, which ``stated`` in its own place
    passes."""
    import jax

    (got, _), (near, _) = tiny_rounds(variant), tiny_rounds("stated")
    assert np.isfinite(list(got.values())).all()
    limits = tiny_rounds.limits
    if variant == "stated":
        # the reference hands back host trees (its note on memory)
        new, _ = tiny_rounds.local_train(variant)
        assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(new))
        # hidden 32 and ONE choice a token: bf16's rounding moves a few
        # tokens to another expert, a whole MLP's worth each
        assert got["update_l2"] < 0.5 and got["lowp_share"] < 0.01
        assert tiny_rounds("stated", against="stated")[1]
    elif variant == "act_fp8_scaled":
        assert got["update_l2"] > 1.5 * near["update_l2"]
    elif variant in ("params_bf16", "local_bf16"):
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > (0.9 if variant == "params_bf16" else 0.2)
        assert got["lowp_share"] > limits["lowp_share"]
    else:
        apart, ok = tiny_rounds(variant, against="stated")
        assert not ok
        # the carry is the fainter of the two, here as on the chip (the
        # update's direction moves by 2% there, by 100% without the mixing)
        room = 2 if variant == "router_alone" else 20
        assert apart["update_l2"] > room * limits["update_l2"]
        assert got["lowp_share"] < 0.01
