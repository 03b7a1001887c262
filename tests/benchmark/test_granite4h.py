"""The ``granite4_h_micro`` configuration's files: found BY NAME (no tail of
a list and no list length is pinned), true to the catalog row of the source's
config, the registered model's defaults equal to the file's ``model`` block
and 772.2 M parameters counted from shapes; the tiny cell of the same model
through the harness; the FLOP counts against hand counts; the six parts of
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
#: row of ibm-granite/granite-4.0-h-micro)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"]
                    + (["mamba"] * 9 + ["attention"]) * 3 + ["mamba"] * 4),
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ("num_hidden_layers", "vocab_size")
NAME, CELL, TINY = "granite4_h_micro", "granite4h_sim_c2", "tiny_granite4h_sim"
NEW_READERS = ("ssd_ms", "ssd_roofline_pct", "ssd_prep_ms", "ssm_other_ms",
               "ssd_decay_mean")
#: readers the benchmark had, whose lists the cell joins
SHARED_READERS = ("plan_ms", "enqueue_ms", "idle_in_driver_ms", "attn_ms",
                  "attn_roofline_pct", "dense_mm_ms", "state_update_ms",
                  "api_init_s", "init_variables_s", "place_data_s",
                  "round_trace_s", "round_lower_s", "round_load_s",
                  "helper_programs_built", "helper_build_s")
#: and the sparse layers' readers, which a dense decoder stays out of
NOT_ITS_PART = ("expert_mm_ms", "expert_mm_roofline_pct", "moe_route_ms",
                "expert_load_max_over_mean", "held_rows_per_token")


@pytest.fixture(scope="module")
def gra_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_granite4h.json"))


def test_the_published_layer_pattern_is_forty_long():
    assert len(PUBLISHED["layer_types"]) == 40
    assert PUBLISHED["layer_types"].count("attention") == 4


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
            "kv_heads": "num_key_value_heads", "layers": "num_hidden_layers",
            "dense_width": "intermediate_size", "eps": "rms_norm_eps",
            "ssd_heads": "mamba_n_heads", "ssd_head_dim": "mamba_d_head",
            "ssd_state": "mamba_d_state", "ssd_conv": "mamba_d_conv",
            "ssd_chunk": "mamba_chunk_size",
            "embed_scale": "embedding_multiplier",
            "residual_scale": "residual_multiplier",
            "attn_scale": "attention_multiplier",
            "logit_scale": "logits_scaling", "tied_head": "tie_word_embeddings"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # no width is cut: the mixer's inner width is expand x hidden, a head of
    # attention is hidden / heads, nothing turns, nothing is routed
    assert m["ssd_heads"] * m["ssd_head_dim"] == \
        config["mamba_expand"] * config["hidden_size"]
    assert m["v_dim"] == config["hidden_size"] // config["num_attention_heads"]
    assert m["rope"] == 0 and config["position_embedding_type"] == "nope"
    assert m["n_routed"] == config["num_local_experts"] == 0
    assert m["first_dense"] == m["layers"]
    # one whole period: the first ten entries of the published pattern
    period = {"mamba": "ssd", "attention": "full"}
    assert m["mixers"] == [period[k] for k in config["layer_types"][:10]]
    assert len(config["layer_types"]) == config["published"]["num_hidden_layers"]
    assert m["mixers"].count("ssd") == 9 and m["mixers"].count("full") == 1
    # the slice is the vocabulary
    assert config["data"]["vocab"] == config["vocab_size"] == 12544
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    for key in ("deployment", "assumed", "departures", "reduced"):
        assert config[key]
    assert "init" in config["assumed"] and "mlp_two_matrices" in config["departures"]


def test_parameters_are_the_files_arithmetic(real_spec):
    """772.2 M, part by part, as the built tree has them: counted from
    shapes, nothing is allocated."""
    import jax

    from fedml_tpu.models import create_model

    config = real_spec.config(NAME)
    want = config["parameters"]
    shapes = jax.eval_shape(create_model(NAME, 12544).init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == want["total"] == 772_160_448
    ssd = p["layer_0"]["ssd"]
    assert count(ssd["in_proj"]) == want["mamba_in_proj"]
    assert count(ssd["conv_kernel"]) + count(ssd["conv_bias"]) == want["mamba_conv"]
    assert sum(count(ssd[k]) for k in ("A_log", "D", "dt_bias")) == want["mamba_a_d_dt"]
    assert count(ssd["norm"]) == want["mamba_gated_norm"]
    assert count(ssd["out_proj"]) == want["mamba_out_proj"]
    assert count(p["layer_0"]["mlp"]) == want["mlp"]
    assert count(p["layer_0"]) == want["mamba_layer"]
    assert count(p["layer_5"]["attn"]) == want["attention_mixer"]
    assert count(p["layer_5"]) == want["attention_layer"]
    assert count(p["embed"]) == want["tied_table"] and "lm_head" not in p
    assert want["period"] == 9 * want["mamba_layer"] + want["attention_layer"]
    assert want["total"] == want["period"] + want["tied_table"] + want["final_norm"]
    # the reference's seeded tree is the program's
    ref = real_spec.module("references", config["reference"])
    ours = jax.eval_shape(lambda k: ref.init(k, config), jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, ours) == \
        jax.tree.map(lambda s: s.shape, dict(shapes))


@pytest.mark.parametrize("spec_name,cell", [("real", CELL), ("tiny", TINY)])
def test_cell_files_are_found_by_name(real_spec, gra_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else gra_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated"} <= set(ref.VARIANTS) - set(ref.CONTROLS)
    assert {"state_cut", "scale_plain", "act_fp8_scaled", "params_bf16",
            "local_bf16"} <= set(ref.CONTROLS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    assert config["recipe"]["batch_size"] == 1
    names = {m["name"] for m in spec.metric_entries("per_layer", cell)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= names
    # one remainder a cell, and no reader of a sparse layer
    assert not {"lm_other_ms", "hyb_other_ms", "win_other_ms", "kda_ms"} & names
    assert not set(NOT_ITS_PART) & names
    for n in NEW_READERS + SHARED_READERS:
        assert callable(spec.module("metrics", n).read)


def test_real_benchmark_has_the_cell_and_its_metrics_by_name(real_spec):
    """Entries are looked up by name: where they stand in their lists and
    how long a list is belongs to no configuration."""
    doc = real_spec.doc
    config = next(c for c in doc["configs"] if c["name"] == NAME)
    assert config["file"] == f"benchmarks/configs/{NAME}.json"
    assert config["source"] == ("https://huggingface.co/ibm-granite/"
                                "granite-4.0-h-micro/blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(REDUCED)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "sim_c2_t4096_b1", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for n in NEW_READERS:
        assert CELL in by_name[n]["workloads"]
        assert by_name[n]["moves"] == "real_samples_per_s"
        assert set(by_name[n]) == {"name", "unit", "better", "source", "layer",
                                   "moves", "workloads"}
    assert by_name["ssd_roofline_pct"]["unit"] == "%"
    assert by_name["ssd_decay_mean"]["source"] == "program_counter"
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
    # the recurrence's own work: 3 x 64 x 128 multiply-adds a token and head
    # forward (decay, write, read), twice that backward, nine layers
    ssd, ssd_bytes = f.ssd_train_cost_per_sample(config)
    assert ssd == pytest.approx(2 * 3 * 64 * 128 * 3 * 64 * t * 9)
    # x and y [64, 64] and B, C [128] in bf16, dt [64] in float32, each way
    assert ssd_bytes == pytest.approx(
        2 * 9 * t * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4))
    # it is the bytes that bound it, narrowly: 1.77 ms of FLOPs, 1.54 of bytes
    assert ssd / 197e12 == pytest.approx(1.766e-3, rel=1e-3)
    assert ssd_bytes / 819e9 == pytest.approx(1.544e-3, rel=1e-3)
    attn, attn_bytes = f.attn_train_cost_per_sample(config)
    assert attn == pytest.approx(3 * 2 * (t * (t + 1) / 2) * 32 * 2 * 64)
    assert attn_bytes == pytest.approx(2 * t * 64 * (5 * 32 + 6 * 8))
    mamba = d * 8512 + 4096 * d
    attention = 2 * d * 2048 + 2 * d * 512
    per_token = 9 * mamba + attention + 10 * 3 * d * 8192 + d * 12544
    assert f.dense_fwd_flops_per_token(config) == pytest.approx(2.0 * per_token)
    # every matrix of the tree is in it once: the tree less its vectors
    assert per_token == 772_160_448 - 9 * (21760 + 192 + 4096) - 21 * 2048
    total = f.train_flops_per_sample(config)
    assert total == pytest.approx(3 * t * 2 * per_token + attn + ssd)
    assert 4.6e9 < total / t < 4.8e9


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, gra_spec, trace):
    rc = run.main(["--workload", TINY, "--seed", str(2**31 + 11),
                   "--seconds", "0.3", "--trace", trace], spec=gra_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counter is read on the CPU too; the trace's parts need a TPU
        assert 0.3 < res["metrics"]["ssd_decay_mean"]["value"] < 1.0
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


MADE = {"fedml.lm.ssd": 0.8, "fedml.lm.ssd_prep": 0.3, "fedml.lm.attn": 0.2,
        "fedml.lm.dense": 1.2, "fedml.step.reset": 0.05, "fedml.step.opt": 0.1,
        "fedml.step.emit": 0.1, "fedml.aggregate": 0.05,
        "fedml.step.train": 0.2, "fedml.lm.loss": 0.05, "fedml.prologue": 0.05,
        "unscoped": 0.05}


@pytest.mark.parametrize("reader,want", [
    ("ssd_ms", 400.0), ("ssd_prep_ms", 150.0), ("attn_ms", 100.0),
    ("dense_mm_ms", 600.0), ("state_update_ms", 150.0), ("ssm_other_ms", 175.0)])
def test_six_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    """Three parts by the LM cells' shared readers, the recurrence's two and
    the remainder they leave: together the module's whole time."""
    from benchmarks.trace import lm_scopes, ssd_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    ours, theirs = ssd_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    assert theirs["experts"] == theirs["route"] == 0.0
    assert (sum(ours.values()) + theirs["attn"] + theirs["dense"]
            + theirs["state_update"]) == pytest.approx(sum(MADE.values()))
    if reader not in NEW_READERS:
        return
    # another LM's trace (no state-space layer), the parent commit, or no trace
    other = {k: v for k, v in MADE.items() if k != "fedml.lm.ssd"}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: {
        "by_scope_s": other, "busy_s": sum(other.values())})
    assert real_spec.module("metrics", reader).read(ctx) is None
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert real_spec.module("metrics", reader).read(ctx) is None


def test_a_sparse_decoders_time_stays_inside_the_six_parts(monkeypatch, real_spec):
    """Were a state-space decoder to route (none does today), its experts'
    and router's time would fall to the remainder and not out of the sum."""
    from benchmarks.trace import lm_scopes, ssd_scopes

    made = {**MADE, "fedml.lm.experts": 0.1, "fedml.lm.route": 0.05}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    ours, theirs = ssd_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    assert (sum(ours.values()) + theirs["attn"] + theirs["dense"]
            + theirs["state_update"]) == pytest.approx(sum(made.values()))


@pytest.mark.parametrize("reader", NEW_READERS[:4])
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


@pytest.mark.parametrize("reader,scope,cost,secs,ok", [
    ("ssd_roofline_pct", "fedml.lm.ssd", "ssd_train_cost_per_sample", 1.0, True),
    ("ssd_roofline_pct", "fedml.lm.ssd", "ssd_train_cost_per_sample", 0.02,
     False),
    ("attn_roofline_pct", "fedml.lm.attn", "attn_train_cost_per_sample", 1.0,
     True)])
def test_roofline_shares_from_shapes_and_raise_over_105(
        monkeypatch, real_spec, capsys, reader, scope, cost, secs, ok):
    """The recurrence's share is of the state's own work; the shared reader
    takes this configuration's attention layer by the same function name."""
    from benchmarks.trace import lm_scopes

    made = {"fedml.lm.ssd": 0.5, "fedml.lm.attn": 0.5, "fedml.lm.dense": 1.0,
            scope: secs}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", reader)
    if not ok:
        with pytest.raises(RuntimeError, match="over 105%"):
            mod.read(ctx)
        return
    flops, nbytes = getattr(real_spec.module("flops", NAME), cost)(ctx["config"])
    want = 100 * 16 * max(flops / 197e12, nbytes / 819e9) / secs
    assert mod.read(ctx) == pytest.approx(want) and 0 < want < 100
    assert "bound by FLOPs" in capsys.readouterr().out
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert mod.read(ctx) is None


def _set_model_counters(values: dict):
    from fedml_tpu.obs import model_counters

    g = model_counters()
    for k in list(g.keys()):
        g._data.pop(k)
    for k, v in values.items():
        g[k] = v


def test_decay_reader_means_over_layers_and_steps(real_spec, capsys):
    mod = real_spec.module("metrics", "ssd_decay_mean")
    _set_model_counters({})
    assert mod.read({}) is None
    try:
        # a sparse layer's steps are not a state-space layer's
        _set_model_counters({"decay.layer_0": 0.9 * 3, "steps.layer_0": 3.0,
                             "decay.layer_1": 0.8 * 3, "steps.layer_1": 3.0,
                             "steps.layer_2": 3.0, "rows.layer_2.0": 5.0})
        assert mod.read({}) == pytest.approx(0.85)
        assert "6 layer-steps of 2 state-space layers" in capsys.readouterr().out
    finally:
        _set_model_counters({})


@pytest.fixture(scope="module")
def tiny_rounds(gra_spec):
    """``numbers(variant)``: one round of the tiny cell by a variant of the
    reference against the float32 reference, each variant computed once;
    the scan in blocks of 8 of the tiny sequence's 32 positions."""
    import jax

    from benchmarks.harness import check

    cell = gra_spec.cell(TINY)
    config = gra_spec.config(cell["config"])
    ref = gra_spec.module("references", config["reference"])
    gen = gra_spec.module("traffic", config["generator"])
    _ds, rows = gen.make(config, cell, 3)
    init = jax.device_get(jax.jit(lambda k: ref.init(k, config))(jax.random.key(3)))
    done = {}
    block, built = ref._SCAN_BLOCK, ref._built
    ref._SCAN_BLOCK, ref._built = 8, {}

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
            config, init, *(a[0][None, :2, None] for a in rows([0])[:3]), 1,
            variant)

    numbers.local_train, numbers.limits = local_train, cell["limits"]
    yield numbers
    ref._SCAN_BLOCK, ref._built = block, built


@pytest.mark.parametrize("variant", ["stated", "act_fp8_scaled", "params_bf16",
                                     "local_bf16", "state_cut", "scale_plain"])
def test_reference_variants_at_a_tiny_size(tiny_rounds, variant):
    """``stated`` stays near the float32 reference; e4m3's noise moves a
    client's update further than ``stated`` does; parameters kept in bf16
    show in the aggregate's bits and fail ``lowp_share``; the two controls
    of the configuration's own (the state dropped between blocks, the
    multipliers ignored), put in the program's place against ``stated``
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
        # hidden 32: bf16's rounding is a tenth of so small an update
        assert got["update_l2"] < 0.2 and got["lowp_share"] < 0.01
        assert tiny_rounds("stated", against="stated")[1]
    elif variant == "act_fp8_scaled":
        assert got["update_l2"] > 2 * near["update_l2"]
        assert got["update_l2"] < 0.7           # rounding noise, not a lost update
    elif variant in ("params_bf16", "local_bf16"):
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > (0.9 if variant == "params_bf16" else 0.2)
        assert got["lowp_share"] > limits["lowp_share"]
    else:
        apart, ok = tiny_rounds(variant, against="stated")
        assert not ok
        room = 3 if variant == "state_cut" else 100
        assert apart["update_l2"] > room * limits["update_l2"]
        assert got["lowp_share"] < 0.01
