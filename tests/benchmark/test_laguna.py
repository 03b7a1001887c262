"""The ``laguna_xs2`` configuration's files: found BY NAME (no tail of a list
and no list length is pinned, so the next configuration breaks nothing
here), true to the catalog row of the source's config, the registered
model's defaults equal to the file's ``model`` block and 691.6 M parameters;
the tiny cell of the same model through the harness; the FLOP counts against
hand counts; the seven parts of the round program on a made trace; the
reference's controls, the two of its own among them."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check

_FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
         "original_max_position_embeddings": 4096, "beta_slow": 1,
         "beta_fast": 64, "attention_factor": 1.4158883083359672,
         "partial_rotary_factor": 0.5}
#: the model's settings as its public config.json gives them (the catalog
#: row of poolside/Laguna-XS.2)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": _FULL,
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64]}
PUBLISHED["layer_types"] = PUBLISHED["layer_types"] * 10
PUBLISHED["num_attention_heads_per_layer"] = \
    PUBLISHED["num_attention_heads_per_layer"] * 10
REDUCED = ("num_hidden_layers", "num_experts", "vocab_size")
NAME, CELL, TINY = "laguna_xs2", "laguna_sim_c2", "tiny_laguna_sim"
NEW_READERS = ("attn_window_ms", "attn_window_roofline_pct", "win_other_ms")
#: readers the benchmark had, whose lists the cell joins: one name serves
#: one layer in every LM cell
SHARED_READERS = ("plan_ms", "enqueue_ms", "idle_in_driver_ms", "attn_ms",
                  "attn_roofline_pct", "expert_mm_ms", "expert_mm_roofline_pct",
                  "moe_route_ms", "dense_mm_ms", "state_update_ms",
                  "expert_load_max_over_mean", "held_rows_per_token")


@pytest.fixture(scope="module")
def lag_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_laguna.json"))


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_file_keeps_every_published_key(real_spec, key):
    """Only what ``reduced`` lists differs from the source, and no width;
    the nested groups and the per-layer lists are copied whole."""
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
            "layers": "num_hidden_layers", "dense_width": "intermediate_size",
            "top_k": "num_experts_per_tok", "window": "sliding_window",
            "expert_width": "moe_intermediate_size",
            "held_count": "num_experts", "out_gate": "gating",
            "routed_scaling": "moe_routed_scaling_factor",
            "eps": "rms_norm_eps"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # the rotary laws of both kinds, as published
    full = config["rope_parameters"]["full_attention"]
    assert full == _FULL
    assert (m["rope_theta"], m["yarn_factor"], m["yarn_original"],
            m["yarn_beta_fast"], m["yarn_beta_slow"],
            m["yarn_attention_factor"]) == (
                full["rope_theta"], full["factor"],
                full["original_max_position_embeddings"], full["beta_fast"],
                full["beta_slow"], full["attention_factor"])
    assert m["rope"] == full["partial_rotary_factor"] * m["v_dim"] == 64
    assert m["nope"] + m["rope"] == m["v_dim"]
    sliding = config["rope_parameters"]["sliding_attention"]
    assert m["window_rope_theta"] == sliding["rope_theta"] == 10000
    assert sliding["partial_rotary_factor"] == 1
    # what is built: the first five entries of the three per-layer lists
    kinds = {"full_attention": "full", "sliding_attention": "window"}
    assert m["mixers"] == [kinds[k] for k in config["layer_types"][:5]]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert m["first_dense"] == 1 and m["layers"] == 5
    heads = config["num_attention_heads_per_layer"][:5]
    assert heads == [m["heads"] if k == "full" else m["window_heads"]
                     for k in m["mixers"]] == [48, 64, 64, 64, 48]
    # one shared expert of 512; the router keeps the published width
    assert m["n_shared"] * m["expert_width"] == \
        config["shared_expert_intermediate_size"]
    assert m["n_routed"] == config["published"]["num_experts"] == 256
    assert m["held_count"] * 8 == m["n_routed"] and m["score"] == "softmax"
    assert config["data"]["vocab"] == config["vocab_size"] == 12544
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    for key in ("deployment", "assumed", "departures", "reduced"):
        assert config[key]
    # each inference is named as one, and from what
    for key in ("attention_gate", "router_score", "shared_expert"):
        assert "INFERENCE" in config["assumed"][key]
    assert "permutation" in config["departures"]["rotary_pairs"]


def test_parameters_are_the_files_arithmetic(real_spec):
    """691.6 M, part by part, as the built tree has them."""
    import jax

    from fedml_tpu.models import create_model

    config = real_spec.config(NAME)
    want = config["parameters"]
    shapes = jax.eval_shape(create_model(NAME, 12544).init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == want["total"] == 691_623_936
    assert count(p["layer_0"]["attn"]) == count(p["layer_4"]["attn"]) \
        == want["full_mixer"]
    assert count(p["layer_1"]["attn"]) == want["window_mixer"]
    assert count(p["layer_0"]["mlp"]) == want["dense_mlp"]
    assert count(p["layer_2"]["mlp"]) == want["sparse_mlp"]
    assert count(p["embed"]) + count(p["lm_head"]) == want["embedding_and_head"]
    experts = sum(count(p[f"layer_{i}"]["mlp"][k]) for i in range(1, 5)
                  for k in ("gate", "up", "down"))
    assert experts == want["held_experts"] == 128 * want["expert"]
    assert want["total"] == (2 * want["full_mixer"] + 3 * want["window_mixer"]
                             + want["dense_mlp"] + 4 * want["sparse_mlp"]
                             + want["embedding_and_head"] + 11 * 2048)
    # the reference's seeded tree is the program's
    ref = real_spec.module("references", config["reference"])
    ours = jax.eval_shape(lambda k: ref.init(k, config), jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, ours) == \
        jax.tree.map(lambda s: s.shape, dict(shapes))


@pytest.mark.parametrize("spec_name,cell", [("real", CELL), ("tiny", TINY)])
def test_cell_files_are_found_by_name(real_spec, lag_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else lag_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated"} <= set(ref.VARIANTS) - set(ref.CONTROLS)
    assert {"window_full", "rope_plain", "act_fp8_scaled"} <= set(ref.CONTROLS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    assert config["recipe"]["batch_size"] == 2
    names = {m["name"] for m in spec.metric_entries("per_layer", cell)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= names
    # one remainder a cell
    assert not {"lm_other_ms", "hyb_other_ms", "kda_ms"} & names
    for n in NEW_READERS + SHARED_READERS:
        assert callable(spec.module("metrics", n).read)


def test_real_benchmark_has_the_cell_and_its_metrics_by_name(real_spec):
    """Entries are looked up by name: where they stand in their lists and
    how long a list is belongs to no configuration."""
    doc = real_spec.doc
    config = next(c for c in doc["configs"] if c["name"] == NAME)
    assert config["file"] == f"benchmarks/configs/{NAME}.json"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "sim_c2_t4096", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for n in NEW_READERS:
        assert by_name[n]["workloads"] == [CELL]
        assert by_name[n]["moves"] == "real_samples_per_s"
    assert by_name["attn_window_roofline_pct"]["unit"] == "%"
    for n in SHARED_READERS:
        assert CELL in by_name[n]["workloads"]
    # every metric that moves the rate and lists its cells either lists this
    # one or is not its part
    others = {n for n, m in by_name.items()
              if m["moves"] == "real_samples_per_s" and "workloads" in m
              and CELL not in m["workloads"]}
    assert others == {"prologue_ms", "conv_ms", "conv_roofline_pct", "norm_ms",
                      "optimizer_ms", "step_other_ms", "aggregate_ms",
                      "unscoped_pct", "lm_other_ms", "kda_ms",
                      "kda_roofline_pct", "kda_prep_ms", "hyb_other_ms"} & others


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmarks", "references", NAME + ".py")).read()
    assert "fedml_tpu" not in src.replace("``fedml_tpu``", "")


def test_required_flops_are_the_hand_counts(real_spec):
    config = real_spec.config(NAME)
    f = real_spec.module("flops", config["flops"])
    t, d = 4096, 2048
    assert f.routed_rows_per_token(config) == 1.0
    # the band: position p meets min(p + 1, 512) keys
    assert f.score_pairs(config, "window") == sum(
        min(p + 1, 512) for p in range(t)) == 1_966_336
    assert f.score_pairs(config, "full") == t * (t + 1) / 2
    band, band_bytes = f.attn_window_train_cost_per_sample(config)
    assert band == pytest.approx(3 * 2 * 1_966_336 * 64 * (128 + 128) * 3)
    # q, o of 64 heads and k, v of 8 forward; q, do, dq of 64 and k, v, dk,
    # dv of 8 backward, bf16, three layers
    assert band_bytes == pytest.approx(
        2 * t * 128 * ((2 * 64 + 2 * 8) + (3 * 64 + 4 * 8)) * 3)
    full, full_bytes = f.attn_train_cost_per_sample(config)
    assert full == pytest.approx(3 * 2 * (t * (t + 1) / 2) * 48 * 256 * 2)
    assert full_bytes == pytest.approx(
        2 * t * 128 * ((2 * 48 + 2 * 8) + (3 * 48 + 4 * 8)) * 2)
    full_mixer = d * 6144 + 2 * d * 1024 + 6144 * d + d * 48
    window_mixer = d * 8192 + 2 * d * 1024 + 8192 * d + d * 64
    per_token = (2 * full_mixer + 3 * window_mixer + 3 * d * 8192
                 + 4 * (3 * d * 512 + d * 256) + d * 12544)
    assert f.dense_fwd_flops_per_token(config) == pytest.approx(2.0 * per_token)
    experts, exp_bytes = f.expert_train_cost_per_sample(config)
    assert experts == pytest.approx(3 * t * 1.0 * 3 * 2 * d * 512 * 4)
    # each way: 4,096 rows' x, g, u, h, y and half the 32 held experts'
    # weights (a batch of 2 shares them)
    assert exp_bytes == pytest.approx(
        2 * 3 * 4 * (4096 * (2 * d + 3 * 512) + 16 * 3 * d * 512))
    half, _ = f.expert_train_cost_per_sample(config, rows_per_token=0.5)
    assert half == pytest.approx(experts / 2)
    total = f.train_flops_per_sample(config)
    assert total == pytest.approx(3 * t * 2 * per_token + full + band + experts)
    # 263 M matmul parameters a token outside the experts and 12.6 M inside:
    # 276 M, 1.65 GFLOP forward and backward, and 0.44 G of attention
    assert 2.62e8 < per_token < 2.64e8
    assert 2.75e8 < per_token + experts / (6 * t) < 2.77e8
    assert 0.43e9 < (full + band) / t < 0.45e9 and 2.05e9 < total / t < 2.15e9


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, lag_spec, trace):
    rc = run.main(["--workload", TINY, "--seed", str(2**31 + 9),
                   "--seconds", "0.3", "--trace", trace], spec=lag_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counters are read on the CPU too; the trace's parts need a TPU
        assert 0 < res["metrics"]["held_rows_per_token"]["value"] <= 4.0
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
            "_red": {"by_scope_s": by_scope, "busy_s": busy, "xla": {}}}


MADE = {"fedml.lm.attn_window": 0.4, "fedml.lm.attn": 0.6,
        "fedml.lm.experts": 0.1, "fedml.lm.route": 0.05, "fedml.lm.dense": 1.2,
        "fedml.step.reset": 0.05, "fedml.step.opt": 0.1, "fedml.step.emit": 0.1,
        "fedml.aggregate": 0.05, "fedml.step.train": 0.2, "fedml.lm.loss": 0.05,
        "fedml.prologue": 0.05, "unscoped": 0.05}


@pytest.mark.parametrize("reader,want", [
    ("attn_window_ms", 200.0), ("attn_ms", 300.0), ("expert_mm_ms", 50.0),
    ("moe_route_ms", 25.0), ("dense_mm_ms", 600.0), ("state_update_ms", 150.0),
    ("win_other_ms", 175.0)])
def test_seven_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    """Five parts by the LM cells' shared readers (``attn_ms`` then holds
    the full layers alone), the window layers' and the remainder they
    leave."""
    from benchmarks.trace import lm_scopes, window_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    ours, theirs = window_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    assert theirs["other"] == pytest.approx(sum(ours.values()))
    assert sum(theirs.values()) == pytest.approx(sum(MADE.values()))
    if reader not in NEW_READERS:
        return
    # another LM's trace (no window layer), the parent commit, or no trace
    other = {k: v for k, v in MADE.items() if k != "fedml.lm.attn_window"}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: {
        "by_scope_s": other, "busy_s": sum(other.values())})
    assert real_spec.module("metrics", reader).read(ctx) is None
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert real_spec.module("metrics", reader).read(ctx) is None


@pytest.mark.parametrize("reader,scope,cost,secs,ok", [
    ("attn_window_roofline_pct", "fedml.lm.attn_window",
     "attn_window_train_cost_per_sample", 1.0, True),
    ("attn_window_roofline_pct", "fedml.lm.attn_window",
     "attn_window_train_cost_per_sample", 0.05, False),
    ("attn_roofline_pct", "fedml.lm.attn", "attn_train_cost_per_sample", 1.0,
     True)])
def test_roofline_shares_from_shapes_and_raise_over_105(
        monkeypatch, real_spec, capsys, reader, scope, cost, secs, ok):
    """The band's share is of the band's own pairs; the shared reader takes
    this configuration's full layers by the same function name."""
    from benchmarks.trace import lm_scopes

    made = {"fedml.lm.attn_window": 0.5, "fedml.lm.attn": 0.5,
            "fedml.lm.dense": 1.0, scope: secs}
    ctx = _ctx(real_spec, made, sum(made.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", reader)
    if not ok:
        with pytest.raises(RuntimeError, match="over 105%"):
            mod.read(ctx)
        return
    flops, nbytes = getattr(real_spec.module("flops", NAME), cost)(ctx["config"])
    want = 100 * 32 * max(flops / 197e12, nbytes / 819e9) / secs
    assert mod.read(ctx) == pytest.approx(want) and 0 < want < 100
    assert "bound by FLOPs" in capsys.readouterr().out
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert mod.read(ctx) is None


@pytest.fixture(scope="module")
def tiny_rounds(lag_spec):
    """``numbers(variant)``: one round of the tiny cell by a variant of the
    reference against the float32 reference, each variant computed once."""
    import jax

    from benchmarks.harness import check

    cell = lag_spec.cell(TINY)
    config = lag_spec.config(cell["config"])
    ref = lag_spec.module("references", config["reference"])
    gen = lag_spec.module("traffic", config["generator"])
    _ds, rows = gen.make(config, cell, 3)
    init = jax.device_get(jax.jit(lambda k: ref.init(k, config))(jax.random.key(3)))
    done = {}

    def rounds(variant):
        if variant not in done:
            done[variant] = check.reference_rounds(
                ref, config, cell, rows, init, 3, [1], variant)
        return done[variant]

    def numbers(variant, against="reference"):
        out = check.compare(*rounds(variant), *rounds(against), init, {})
        return {n: v for n, v, *_ in out["numbers"]}

    def local_train(variant):
        return ref.local_train(
            config, init, *(a[0][None, :2, None] for a in rows([0])[:3]), 1,
            variant)

    numbers.local_train, numbers.limits = local_train, cell["limits"]
    return numbers


@pytest.mark.parametrize("variant", ["stated", "act_fp8_scaled", "params_bf16",
                                     "window_full", "rope_plain"])
def test_reference_variants_at_a_tiny_size(tiny_rounds, variant):
    """``stated`` stays near the float32 reference; each control moves a
    client's update further than ``stated`` does, or shows in the stored
    bits; the two of the configuration's own (the window ignored, YaRN left
    out) differ from ``stated`` itself by far more than the tiny cell's
    limits."""
    import jax

    got, near = tiny_rounds(variant), tiny_rounds("stated")
    assert np.isfinite(list(got.values())).all()
    limits = tiny_rounds.limits
    if variant == "stated":
        # the reference hands back host trees (its note on memory)
        new, _ = tiny_rounds.local_train(variant)
        assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(new))
        # hidden 32: bf16's rounding is a tenth of so small an update
        assert got["update_l2"] < 0.2 and got["lowp_share"] < 0.01
    elif variant == "act_fp8_scaled":
        assert got["update_l2"] > 2 * near["update_l2"]
        assert got["update_l2"] < 0.7           # rounding noise, not a lost update
    elif variant == "params_bf16":
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > 0.9
    else:
        # the stated precision but for the mechanism: against ``stated``
        # what it leaves out is all there is
        # (the window ignored moves far more than four rotary pairs of
        # eight turning at another rate)
        apart = tiny_rounds(variant, against="stated")
        room = 20 if variant == "window_full" else 2
        assert apart["update_l2"] > room * limits["update_l2"]
        assert got["lowp_share"] < 0.01
