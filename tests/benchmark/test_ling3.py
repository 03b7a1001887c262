"""The ``ling3_flash_vl`` configuration's files: found by name, true to the
catalog row of the source's config, the registered model's defaults equal to
the file's ``model`` block and 822.0 M parameters; the tiny cell of the same
model through the harness; the FLOP counts against hand counts; the eight
parts of the hybrid round program on a made trace; the reference's controls."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness.spec import Spec

from .conftest import HERE, ROOT, relaxed_device_check

#: the text decoder's settings as the model's public config.json gives them
#: (the catalog row of inclusionAI/Ling-3.0-flash-VL), numbers and flags
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
NAME, CELL, TINY = "ling3_flash_vl", "ling3_sim_c2", "tiny_ling3_sim"
NEW_READERS = ("kda_ms", "kda_roofline_pct", "kda_prep_ms", "hyb_other_ms",
               "held_rows_per_token")
#: readers the benchmark had, whose lists the hybrid cell joins: one name
#: serves one layer in every LM cell
SHARED_READERS = ("plan_ms", "enqueue_ms", "idle_in_driver_ms", "attn_ms",
                  "attn_roofline_pct", "expert_mm_ms", "expert_mm_roofline_pct",
                  "moe_route_ms", "dense_mm_ms", "state_update_ms",
                  "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def hyb_spec():
    return Spec(os.path.join(HERE, "fixtures", "BENCHMARK.tiny_hybrid.json"))


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_config_file_keeps_every_published_key(real_spec, key):
    """Only what ``reduced`` lists differs from the source, and no width."""
    config = real_spec.config(NAME)
    entry = next(c for c in real_spec.doc["configs"] if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    if key in entry["reduced"]:
        assert config[key] != PUBLISHED[key]
        assert config["published"][key] == PUBLISHED[key]
        assert key in ("num_hidden_layers", "num_experts", "vocab_size")
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
            "layers": "num_hidden_layers", "dense_width": "intermediate_size",
            "top_k": "num_experts_per_tok",
            "expert_width": "moe_intermediate_size",
            "held_count": "num_experts", "n_group": "n_group",
            "topk_group": "topk_group", "qk_norm": "use_qk_norm",
            "routed_scaling": "routed_scaling_factor",
            "rope_theta": "rope_theta", "eps": "rms_norm_eps",
            "delta_head_dim": "head_dim", "delta_conv": "short_conv_kernel_size",
            "delta_lower_bound": "kda_lower_bound"}
    for ours, theirs in same.items():
        assert m[ours] == config[theirs], ours
    # the two leading dense layers count once; one shared expert of 768
    assert m["first_dense"] == 1 and config["first_k_dense_replace"] == 2
    assert m["n_shared"] * m["expert_width"] == \
        config["moe_shared_expert_intermediate_size"]
    # the router keeps the published width; the slice is the vocabulary
    assert m["n_routed"] == config["published"]["num_experts"] == 512
    assert m["held_count"] * 64 == m["n_routed"]
    assert config["data"]["vocab"] == config["vocab_size"] == 19648
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["data"]["seq_len"] == m["seq_len"] == 4096
    # one whole period: the latent layer is the published index 5, where
    # (index + 1) % layer_group_size == 0, and layer i >= 1 here is i + 1
    period = config["layer_group_size"]
    assert m["layers"] == m["first_dense"] + period
    for i, mixer in enumerate(m["mixers"]):
        published_index = i + (config["first_k_dense_replace"] - 1) * (i > 0)
        assert (mixer == "latent") == ((published_index + 1) % period == 0)
    # no clamp in a kept layer
    assert not any(config["expert_swiglu_limit_list"][:8])
    assert not any(config["share_expert_swiglu_limit_list"][:8])
    for key in ("deployment", "assumed", "departures", "reduced"):
        assert config[key]


def test_parameters_are_the_files_arithmetic(real_spec):
    """822.0 M, part by part, as the built tree has them."""
    import jax

    from fedml_tpu.models import create_model

    config = real_spec.config(NAME)
    want = config["parameters"]
    shapes = jax.eval_shape(create_model(NAME, 19648).init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == want["total"] == 822_036_928
    assert count(p["layer_0"]["delta"]) == want["delta_mixer"]
    assert count(p["layer_4"]["attn"]) == want["latent_mixer"]
    assert count(p["layer_0"]["mlp"]) == want["dense_mlp"]
    assert count(p["embed"]) + count(p["lm_head"]) == want["embedding_and_head"]
    experts = sum(count(p[f"layer_{i}"]["mlp"][k]) for i in range(1, 7)
                  for k in ("gate", "up", "down"))
    assert experts == want["held_experts"] == 48 * want["expert"]
    # the reference's seeded tree is the program's
    ref = real_spec.module("references", config["reference"])
    ours = jax.eval_shape(lambda k: ref.init(k, config), jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, ours) == \
        jax.tree.map(lambda s: s.shape, dict(shapes))


@pytest.mark.parametrize("spec_name,cell", [("real", CELL), ("tiny", TINY)])
def test_cell_files_are_found_by_name(real_spec, hyb_spec, spec_name, cell):
    spec = real_spec if spec_name == "real" else hyb_spec
    c = spec.cell(cell)
    config = spec.config(c["config"])
    for kind, key in (("traffic", "generator"), ("references", "reference"),
                      ("flops", "flops")):
        assert os.path.isfile(spec.find(kind, config[key], exts=(".py",)))
    ref = spec.module("references", config["reference"])
    assert set(ref.CONTROLS) < set(ref.VARIANTS)
    assert {"reference", "stated", "state_bf16"} <= set(ref.VARIANTS)
    assert c["fed_config"]["pack_lanes"] == 1 and c["check_rounds"] == 1
    assert config["recipe"]["batch_size"] == 1
    names = {m["name"] for m in spec.metric_entries("per_layer", cell)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= names
    # one remainder a cell: the other LM cell's would hold the scan here
    assert "lm_other_ms" not in names
    for n in NEW_READERS + SHARED_READERS:
        assert callable(spec.module("metrics", n).read)


def test_real_benchmark_appends_the_cell_and_its_metrics_last(real_spec):
    doc = real_spec.doc
    assert doc["configs"][-1]["name"] == NAME
    assert doc["workloads"][-1] == {**doc["workloads"][-1], "name": CELL,
                                    "config": NAME, "chips": 1,
                                    "traffic": "sim_c2_t4096_b1"}
    tail = doc["per_layer"][-len(NEW_READERS):]
    assert [m["name"] for m in tail] == list(NEW_READERS)
    assert all(m["workloads"] == [CELL] for m in tail)
    assert all(m["moves"] == "real_samples_per_s" for m in tail)
    # the readers it shares keep their place and their other cells: the new
    # cell is the last name of their lists
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for n in SHARED_READERS:
        assert by_name[n]["workloads"][-1] == CELL
        assert len(by_name[n]["workloads"]) == 2


@pytest.mark.parametrize("side", ["reference", "program"])
def test_decay_gate_starts_on_a_slow_decay(real_spec, side):
    """``dt_bias`` is seeded so that a channel's decay at a zero
    pre-activation lies in 0.905 .. 0.999 (the public KDA init's range): a
    state lives long enough for the check to see the carry between chunks.
    The reference's seeded tree and the module's own init follow one law."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import slow_decay_bias

    config = real_spec.config(NAME)
    ref = real_spec.module("references", config["reference"])
    bound = config["model"]["delta_lower_bound"]
    key = jax.random.key(11)
    if side == "reference":
        bias = ref._slow_decay_bias(key, (4096,), bound)
        np.testing.assert_array_equal(bias, slow_decay_bias(key, (4096,), bound))
    else:
        from fedml_tpu.models import create_model

        tree = create_model("ling3_tiny", 64).init(key)["params"]
        bias = tree["layer_0"]["delta"]["dt_bias"]
        assert not np.any(np.asarray(tree["layer_0"]["delta"]["A_log"]))
    alpha = np.exp(bound * np.asarray(jax.nn.sigmoid(bias)))
    assert 0.904 < alpha.min() and alpha.max() < 0.9991
    assert 0.97 < np.median(alpha) < 0.995
    # the state after 64 positions at the median decay: more than half of it
    assert np.median(alpha) ** 64 > 0.5
    assert jnp.asarray(bias).dtype == jnp.float32


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmarks", "references", NAME + ".py")).read()
    assert "fedml_tpu" not in src.replace("``fedml_tpu``", "")


def test_required_flops_are_the_hand_counts(real_spec):
    config = real_spec.config(NAME)
    f = real_spec.module("flops", config["flops"])
    t, d, h = 4096, 2560, 32
    assert f.routed_rows_per_token(config) == 0.125
    # the recurrence's own work: 7 x 128 x 128 multiply-adds a token and
    # head forward, twice that backward, six layers
    kda, kda_bytes = f.kda_train_cost_per_sample(config)
    assert kda == pytest.approx(2 * 7 * 128 * 128 * 3 * h * t * 6)
    assert kda_bytes == pytest.approx(
        2 * 6 * t * h * (4 * 128 * 2 + 128 * 4 + 4))
    attn, attn_bytes = f.attn_train_cost_per_sample(config)
    assert attn == pytest.approx(3 * 2 * (t * (t + 1) / 2) * h * (192 + 128))
    assert attn_bytes > 0
    delta = 5 * d * 4096 + 2 * d * h
    latent = d * h * 192 + d * 576 + 512 * h * 256 + 4096 * d + d * h
    per_token = (6 * delta + latent + 3 * d * 6144
                 + 6 * (3 * d * 768 + d * 512) + d * 19648)
    assert f.dense_fwd_flops_per_token(config) == pytest.approx(2.0 * per_token)
    experts, exp_bytes = f.expert_train_cost_per_sample(config)
    assert experts == pytest.approx(3 * t * 0.125 * 3 * 2 * d * 768 * 6)
    # each way: 512 rows' x, g, u, h, y and the 8 held experts' weights
    assert exp_bytes == pytest.approx(
        2 * 3 * 6 * (512 * (2 * d + 3 * 768) + 8 * 3 * d * 768))
    twice, _ = f.expert_train_cost_per_sample(config, rows_per_token=0.25)
    assert twice == pytest.approx(2 * experts)
    total = f.train_flops_per_sample(config)
    assert total == pytest.approx(3 * t * 2 * per_token + attn + kda + experts)
    # 488 M matmul parameters a token: 2.93 GFLOP forward and backward, and
    # 0.25 G of attention, the delta rule and the experts' rows
    assert 4.85e8 < per_token < 4.95e8 and 3.15e9 < total / t < 3.3e9


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_cell_runs_through_the_harness(capsys, hyb_spec, trace):
    rc = run.main(["--workload", TINY, "--seed", str(2**31 + 7),
                   "--seconds", "0.3", "--trace", trace], spec=hyb_spec,
                  device_check=relaxed_device_check, t_start=time.perf_counter())
    lines = capsys.readouterr().out.strip().splitlines()
    res = json.loads(lines[-1])
    assert rc == 0 and res["correct"] is True, lines
    assert res["failed"] == 0 and res["attempted"] >= 2
    if trace == "0":
        assert {"setup_s", "real_samples_per_s"} <= set(res["metrics"])
    else:
        # the counter is read on the CPU too; the trace's parts need a TPU
        assert 0 < res["metrics"]["held_rows_per_token"]["value"] <= 4.0
        assert "dispatch_ms" in res["metrics"]


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


MADE = {"fedml.lm.kda": 1.0, "fedml.lm.kda_prep": 0.3, "fedml.lm.attn": 0.2,
        "fedml.lm.experts": 0.1, "fedml.lm.route": 0.05, "fedml.lm.dense": 1.2,
        "fedml.step.reset": 0.05, "fedml.step.opt": 0.1, "fedml.step.emit": 0.1,
        "fedml.aggregate": 0.05, "fedml.step.train": 0.2, "fedml.lm.loss": 0.05,
        "fedml.prologue": 0.05, "unscoped": 0.05}


@pytest.mark.parametrize("reader,want", [
    ("kda_ms", 500.0), ("kda_prep_ms", 150.0), ("attn_ms", 100.0),
    ("expert_mm_ms", 50.0), ("moe_route_ms", 25.0), ("dense_mm_ms", 600.0),
    ("state_update_ms", 150.0), ("hyb_other_ms", 175.0)])
def test_eight_parts_partition_the_busy_time(monkeypatch, real_spec, reader, want):
    """Five parts by the LM cells' shared readers, the scan's two and the
    remainder they leave."""
    from benchmarks.trace import hybrid_scopes, lm_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    assert real_spec.module("metrics", reader).read(ctx) == pytest.approx(want)
    ours, theirs = hybrid_scopes.parts_s(ctx), lm_scopes.parts_s(ctx)
    assert theirs["other"] == pytest.approx(sum(ours.values()))
    assert sum(theirs.values()) == pytest.approx(sum(MADE.values()))
    if reader not in NEW_READERS:
        return
    # another LM's trace (no delta rule), the parent commit, or no trace
    other = {k: v for k, v in MADE.items() if k != "fedml.lm.kda"}
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: {
        "by_scope_s": other, "busy_s": sum(other.values())})
    assert real_spec.module("metrics", reader).read(ctx) is None
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert real_spec.module("metrics", reader).read(ctx) is None


@pytest.mark.parametrize("secs,ok", [(1.0, True), (0.002, False)])
def test_kda_roofline_share_from_shapes_and_raises_over_105(monkeypatch, real_spec,
                                                            secs, ok):
    from benchmarks.trace import lm_scopes

    ctx = _ctx(real_spec, {"fedml.lm.kda": secs, "fedml.lm.dense": 1.0}, secs + 1)
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    mod = real_spec.module("metrics", "kda_roofline_pct")
    if ok:
        # 16 slots: 0.78 ms at the byte peak of 1 s taken
        f = real_spec.module("flops", NAME)
        flops, nbytes = f.kda_train_cost_per_sample(ctx["config"])
        want = 100 * 16 * max(flops / 197e12, nbytes / 819e9) / secs
        assert mod.read(ctx) == pytest.approx(want) and 0 < want < 5
    else:
        with pytest.raises(RuntimeError, match="over 105%"):
            mod.read(ctx)
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: None)
    assert mod.read(ctx) is None


def _set_model_counters(values: dict):
    from fedml_tpu.obs import model_counters

    g = model_counters()
    for k in list(g.keys()):
        g._data.pop(k)
    for k, v in values.items():
        g[k] = v


@pytest.mark.parametrize("reader", ["attn_roofline_pct", "expert_mm_roofline_pct"])
def test_shared_roofline_readers_take_this_configurations_costs(
        monkeypatch, real_spec, reader, capsys):
    """The LM cells' two shares read ``flops/ling3_flash_vl.py`` by the same
    function names as the other LM's: one latent layer's scores and values,
    and the held experts' rows by the program's counter."""
    from benchmarks.trace import lm_scopes

    ctx = _ctx(real_spec, MADE, sum(MADE.values()))
    monkeypatch.setattr(lm_scopes, "reduce_ctx", lambda c: c["_red"])
    f = real_spec.module("flops", NAME)
    try:
        _set_model_counters({"rows.layer_1.0": 0.25 * 4096 * 3,
                             "steps.layer_1": 3.0})
        got = real_spec.module("metrics", reader).read(ctx)
    finally:
        _set_model_counters({})
    if reader == "attn_roofline_pct":
        flops, nbytes = f.attn_train_cost_per_sample(ctx["config"])
        secs = MADE["fedml.lm.attn"]
    else:
        flops, nbytes = f.expert_train_cost_per_sample(ctx["config"], 0.25)
        secs = MADE["fedml.lm.experts"]
    want = 100 * 16 * max(flops / 197e12, nbytes / 819e9) / secs
    assert got == pytest.approx(want) and 0 < want < 100
    assert "bound by" in capsys.readouterr().out


def test_held_rows_reader_means_over_layers_and_steps(real_spec, capsys):
    mod = real_spec.module("metrics", "held_rows_per_token")
    ctx = {"config": real_spec.config(NAME)}
    _set_model_counters({})
    assert mod.read(ctx) is None
    try:
        # 3 steps of 4,096 tokens in each of two sparse layers
        _set_model_counters({
            "rows.layer_1.0": 0.125 * 4096 * 3, "steps.layer_1": 3.0,
            "rows.layer_2.0": 0.1 * 4096 * 3, "rows.layer_2.5": 0.025 * 4096 * 3,
            "steps.layer_2": 3.0, "group_tokens.layer_1": 2048.0 * 3,
            "group_tokens.layer_2": 2048.0 * 3})
        assert mod.read(ctx) == pytest.approx(0.125)
        assert "0.5000 of the tokens" in capsys.readouterr().out
    finally:
        _set_model_counters({})


@pytest.mark.parametrize("variant", ["stated", "act_fp8", "act_fp8_scaled",
                                     "params_bf16", "local_bf16", "state_bf16",
                                     "state_cut"])
def test_reference_variants_at_a_tiny_size(monkeypatch, hyb_spec, variant):
    """``stated`` stays near the float32 reference; each control moves a
    client's update further than ``stated`` does, or shows in the stored
    bits."""
    import jax

    from benchmarks.harness import check

    cell = hyb_spec.cell(TINY)
    config = hyb_spec.config(cell["config"])
    ref = hyb_spec.module("references", config["reference"])
    gen = hyb_spec.module("traffic", config["generator"])
    # the tiny sequence is 32 positions: four blocks of the scan
    monkeypatch.setattr(ref, "_SCAN_BLOCK", 8)
    monkeypatch.setattr(ref, "_built", {})
    _ds, rows = gen.make(config, cell, 3)
    init = jax.device_get(ref.init(jax.random.key(3), config))
    base = check.reference_rounds(ref, config, cell, rows, init, 3, [1])
    low = check.reference_rounds(ref, config, cell, rows, init, 3, [1], variant)
    stated = check.reference_rounds(ref, config, cell, rows, init, 3, [1], "stated")
    # the reference hands back host trees (its note on memory)
    new, _ = ref.local_train(
        config, init, *(a[0][None, :1, None] for a in rows([0])[:3]), 1)
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(new))

    def numbers(other):
        out = check.compare(*other, *base, init, {})
        return {n: v for n, v, *_ in out["numbers"]}

    got, near = numbers(low), numbers(stated)
    assert np.isfinite(list(got.values())).all()
    if variant == "stated":
        # hidden 32: bf16's rounding is a tenth of so small an update
        assert got["update_l2"] < 0.2 and got["lowp_share"] < 0.01
    elif variant.startswith("act_fp8"):
        assert got["update_l2"] > 2 * near["update_l2"]
        if variant == "act_fp8_scaled":
            assert got["update_l2"] < 0.5       # rounding noise, not a lost update
    elif variant == "state_bf16":
        # only the scan's carried state is rounded: another update than the
        # stated precision's, by about its own rounding noise
        apart = check.compare(*low, *stated, init, {})["numbers"]
        assert dict((n, v) for n, v, *_ in apart)["update_l2"] > 0.01
        assert got["lowp_share"] < 0.01
    elif variant == "state_cut":
        # the state dropped between blocks: the seeded decay is slow, so
        # what each block forgets shows as far more than the stated
        # precision's rounding
        assert got["update_l2"] > 3 * near["update_l2"]
        assert got["lowp_share"] < 0.01
    else:
        # parameters kept in bf16 show exactly in the aggregate's bits
        assert got["lowp_share"] > (0.9 if variant == "params_bf16" else 0.2)
