"""The latent-attention sparse-expert LM at a small size on the CPU: the
program against the plain reference (``benchmarks/references/
kanana2_30b_a3b.py``), the share against the uncut layer, one federated
round, and token ids through the resident stack. The ops it is built on:
``tests/test_lm_ops.py``."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import kanana2_30b_a3b as ref
from fedml_tpu.core.tasks import nwp
from fedml_tpu.models import create_model
from fedml_tpu.models import moe
from fedml_tpu.models.moe import LATENT_MOE_PRESETS, SharedRoutedMoe

VOCAB = 64


def tiny_config(**over):
    sizes = {**LATENT_MOE_PRESETS["kanana2_tiny"], **over}
    return {"name": "tiny", "model": sizes, "data": {"vocab": VOCAB},
            "recipe": {"lr": 0.1, "momentum": 0.0}}


def batch(seed=1, n=4, t=16):
    x = jax.random.randint(jax.random.key(seed), (n, t + 1), 0, VOCAB)
    return x[:, :-1], x[:, 1:], jnp.asarray([1.0] * (n - 1) + [0.0])


# --- the model against the reference ---------------------------------------

@pytest.mark.parametrize("held_first,held_count,remat", [
    (0, 4, True), (0, 2, True), (2, 3, False), (4, 4, True), (0, 8, False)])
def test_logits_loss_and_gradients_match_the_reference(held_first, held_count,
                                                       remat):
    over = dict(held_first=held_first, held_count=held_count)
    config = tiny_config(**over)
    v = ref.init(jax.random.key(7), config)
    b = create_model("kanana2_tiny", VOCAB, input_shape=(16,),
                     dtype=jnp.float32, remat=remat, **over)
    assert (jax.tree.map(jnp.shape, b.init(jax.random.key(0)))
            == jax.tree.map(jnp.shape, v))
    x, y, m = batch()
    forward = ref._forward(config, "reference")

    def program(p):
        logits, new = b.apply_train({**v, "params": p}, x, None)
        return nwp.loss(logits, y, m), (logits, new["counters"])

    def reference(p):
        logits, stats, _ = forward(p, v["counters"], x)
        per = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                                   -1)[..., 0]
        w = jnp.broadcast_to(m[:, None], per.shape)
        return jnp.sum(per * w) / jnp.sum(w), (logits, stats)

    with jax.default_matmul_precision("highest"):
        (lp, (op, sp)), gp = jax.value_and_grad(program, has_aux=True)(v["params"])
        (lr, (orf, sr)), gr = jax.value_and_grad(reference, has_aux=True)(v["params"])
    np.testing.assert_allclose(op, orf, atol=2e-6)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, c, atol=2e-6 * float(jnp.abs(c).max() + 1e-6)
                                   + 1e-9, err_msg=str(path))
    # the correction bias selects and gets no gradient; the counters count
    assert not np.any(np.asarray(gp["layer_1"]["mlp"]["e_score_correction_bias"]))
    for name in sp:
        np.testing.assert_array_equal(sp[name]["mlp"]["expert_rows"],
                                      sr[name]["mlp"]["expert_rows"])
        assert float(sp[name]["mlp"]["steps"]) == 1.0


def test_registered_defaults_are_the_published_widths():
    k = LATENT_MOE_PRESETS["kanana2_30b_a3b"]
    assert (k["dim"], k["heads"], k["nope"], k["rope"], k["v_dim"],
            k["kv_rank"]) == (2048, 32, 128, 64, 128, 512)
    assert (k["n_routed"], k["top_k"], k["n_shared"], k["expert_width"],
            k["dense_width"], k["routed_scaling"]) == (128, 6, 2, 768, 6144, 2.448)
    b = create_model("kanana2_30b_a3b", 16032)
    shapes = jax.eval_shape(lambda: b.module.init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32)))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))
    assert 575e6 < n < 577e6            # the issue's 576 M parameters
    assert shapes["params"]["layer_1"]["mlp"]["router"].shape == (2048, 128)


# --- the share --------------------------------------------------------------

def test_all_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 experts: the routed parts of all the shares, plus the
    shared experts counted once, are the uncut reference layer's output."""
    d, n_routed, width = 32, 16, 24
    config = tiny_config(n_routed=n_routed, held_first=0, held_count=n_routed,
                         top_k=3, layers=2)
    v = ref.init(jax.random.key(3), config)
    p = v["params"]["layer_1"]["mlp"]
    x = jax.random.normal(jax.random.key(4), (2, 16, d), jnp.float32)

    def layer(first, count, n_shared):
        mod = SharedRoutedMoe(n_routed, 3, width, n_shared, 2.448, first, count)
        params = {k: (a[first:first + count] if k in ("gate", "up", "down")
                      else a) for k, a in p.items() if n_shared or k != "shared"}
        stats = {"expert_rows": jnp.zeros((count,)), "steps": jnp.zeros(())}
        return mod.apply({"params": params, "counters": stats}, x)[0]

    with jax.default_matmul_precision("highest"):
        whole = layer(0, n_routed, 2)
        shared_once = whole - layer(0, n_routed, 0)
        parts = sum(layer(first, 2, 0) for first in range(0, n_routed, 2))
    np.testing.assert_allclose(parts + shared_once, whole, atol=3e-6)
    with jax.default_matmul_precision("highest"):
        uncut, rows, _ = ref._forward(config, "reference").moe(x, p)
    np.testing.assert_allclose(whole, uncut, atol=3e-6)
    assert float(rows.sum()) == 2 * 16 * 3


@pytest.mark.parametrize("target", [0, 3])
def test_no_token_is_dropped_when_all_choose_one_expert(target):
    """A bias that sends every token to the same two experts: every (token,
    choice) pair of a held expert is computed, however many there are."""
    d, n = 32, 2 * 16
    config = tiny_config(top_k=2)
    p = dict(ref.init(jax.random.key(5), config)["params"]["layer_1"]["mlp"])
    p["e_score_correction_bias"] = jnp.zeros((8,)).at[
        jnp.asarray([target, 7])].set(10.0)
    x = jax.random.normal(jax.random.key(6), (2, 16, d), jnp.float32)
    mod = SharedRoutedMoe(8, 2, 24, 2, 2.448, 0, 4)
    stats = {"expert_rows": jnp.zeros((4,)), "steps": jnp.zeros(())}
    with jax.default_matmul_precision("highest"):
        (out, _), new = mod.apply({"params": p, "counters": stats}, x, True,
                             mutable=["counters"])
        # expert 7 is absent: only the held target's part is in the sum
        want, _, _ = ref._forward(config, "reference").moe(x, p)
    rows = np.asarray(new["counters"]["expert_rows"])
    assert rows[target] == n and rows.sum() == n
    np.testing.assert_allclose(out, want, atol=3e-6)


# --- the row capacities -------------------------------------------------------

def test_row_rungs_come_from_the_pair_count_alone():
    assert moe.row_rungs(8192 * 6) == (6144, 12288, 24576, 49152)
    assert moe.row_rungs(64) == (8, 16, 32, 64)
    assert moe.row_rungs(4) == (1, 2, 4)
    for pairs in (64, 100, 1000, 8192 * 6, 9000 * 7):
        rungs = moe.row_rungs(pairs)
        assert rungs[-1] == pairs and 1 <= len(rungs) <= 4
        assert all(a < b for a, b in zip(rungs, rungs[1:]))
    assert all(c % 1024 == 0 for c in moe.row_rungs(9000 * 8)[:-1])


@pytest.mark.parametrize("pairs, rows, want", [
    # the window / full cell: 8,192 tokens x 8 choices, 32 of 256 held, so
    # 8,192 rows expected, which is the first rung to the row: one row more
    # takes the second (``PERF.md`` PR 32: the cell's rate follows it)
    (8192 * 8, 8192, 0),
    (8192 * 8, 8193, 1),
    (8192 * 8, 16385, 2),
    (8192 * 8, 8192 * 8, 3),
    # kanana's 6 choices with 16 of 128 held: 6,144 expected, its edge too
    (8192 * 6, 6144, 0),
    (8192 * 6, 6145, 1),
    # the hybrid's 8 of 512 held: 512 rows in a first rung of 4,096
    (4096 * 8, 512, 0),
])
def test_the_rung_taken_is_the_first_that_holds_the_rows(pairs, rows, want):
    rungs = moe.row_rungs(pairs)
    sizes = jnp.asarray([rows - rows // 2, rows // 2], jnp.int32)
    assert int(moe._rung_index(rungs, sizes)) == want
    assert rungs[want] >= rows and (want == 0 or rungs[want - 1] < rows)


def _steered_layer(totals, held_count=4, dtype=jnp.float32):
    """A layer of 32 tokens x 2 choices over 8 experts (rungs 8 / 16 / 32 /
    64 of its 64 pairs) whose input is solved for so that exactly
    ``totals[lane]`` pairs choose a held expert (0 .. 3 of 8; all 8 when
    ``held_count`` is None): -> (module, variables, x [lanes, 2, 16, 32])."""
    n, k, d, e = 32, 2, 32, 8
    held = e if held_count is None else held_count
    config = tiny_config(top_k=k)
    p = dict(ref.init(jax.random.key(5), config)["params"]["layer_1"]["mlp"])
    logits = np.full((len(totals), n, e), -4.0)
    for lane, total in enumerate(totals):
        for tok in range(n):
            # this token's pairs of held experts: 2, 1 or 0
            mine = min(2, max(0, total - 2 * tok)) if held < e else 2
            chosen = ([tok % held, (tok + 1) % held][:mine]
                      + [held + tok % 2, held + 2 + tok % 2][:2 - mine])
            logits[lane, tok, chosen] = 4.0
    # x @ router = logits exactly: the router's pseudo-inverse places the
    # logits, and noise in the router's null space fills the other widths
    router = np.asarray(jax.random.normal(jax.random.key(8), (d, e)), np.float64)
    pinv = np.linalg.pinv(router)
    noise = np.asarray(jax.random.normal(jax.random.key(6),
                                         (len(totals), n, d)), np.float64)
    xs = jnp.asarray(logits @ pinv + noise @ (np.eye(d) - router @ pinv),
                     jnp.float32)
    p["router"] = jnp.asarray(router, jnp.float32)
    p["e_score_correction_bias"] = jnp.zeros((e,))
    if held != 4:
        wide = ref.init(jax.random.key(5), tiny_config(
            top_k=k, held_count=held))["params"]["layer_1"]["mlp"]
        p.update({name: wide[name] for name in ("gate", "up", "down")})
    mod = SharedRoutedMoe(e, k, 24, 2, 2.448, 0, held_count, dtype)
    stats = {"expert_rows": jnp.zeros((held,)), "steps": jnp.zeros(())}
    return mod, {"params": p, "counters": stats}, xs.reshape(-1, 2, 16, d)


def _layer_value_and_grads(mod, variables, x, c):
    def loss(params, x):
        (out, _), new = mod.apply({**variables, "params": params}, x, True,
                             mutable=["counters"])
        return (jnp.sum(out.astype(jnp.float32) * c),
                (out, new["counters"]["expert_rows"]))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            variables["params"], x)


def _assert_same(got, want, tol):
    """Every leaf within ``tol`` of its largest magnitude; the loss, a sum
    of terms that cancel, within ``tol`` of the terms' magnitudes."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = np.abs(b).max() if b.ndim else np.abs(want[0][1][0]).sum()
        np.testing.assert_allclose(a, b, atol=tol * (scale + 1e-9), rtol=0,
                                   err_msg=str(path))


# totals against the rungs 8 / 16 / 32 / 64: inside each rung, on its edge
# (total == C) and one past it (total == C + 1), none, and every pair
RUNG_CASES = [(0, 0), (5, 0), (8, 0), (9, 1), (16, 1), (17, 2), (32, 2),
              (33, 3), (47, 3), (64, 3)]


@pytest.mark.parametrize("total,rung,held_count,dtype", [
    *[(t, r, 4, "float32") for t, r in RUNG_CASES],
    (64, 3, None, "float32"),
    *[(t, r, 4, "bfloat16") for t, r in RUNG_CASES[1::3]]])
def test_every_rung_is_the_full_capacity_layer(monkeypatch, total, rung,
                                               held_count, dtype):
    """Output, loss, and the gradients of every parameter and of the input:
    the layer at the capacity its count picks against the same layer with
    the one capacity of every pair."""
    mod, variables, x = _steered_layer([total], held_count, jnp.dtype(dtype))
    x = x[0]
    c = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    rungs = moe.row_rungs(64)
    assert rungs == (8, 16, 32, 64)
    got = _layer_value_and_grads(mod, variables, x, c)
    rows = np.asarray(got[0][1][1])
    assert rows.sum() == total
    assert int(moe._rung_index(rungs, jnp.asarray(rows, jnp.int32))) == rung
    monkeypatch.setattr(moe, "ROW_RUNG_SHARES", (1,))
    assert moe.row_rungs(64) == (64,)
    want = _layer_value_and_grads(mod, variables, x, c)
    np.testing.assert_array_equal(rows, want[0][1][1])
    # float32: the last bits (one gradient's last bit, 9.5e-7, in the
    # issue's own check); bf16: one rounding of the dtype
    _assert_same(got, want, 2e-6 if dtype == "float32" else 8e-3)
    if total:
        assert np.abs(np.asarray(got[1][0]["gate"], np.float32)).max() > 0


def test_a_rung_too_small_would_lose_rows(monkeypatch):
    """The comparison above can tell: the layer forced to a capacity under
    its count differs from the full one."""
    mod, variables, x = _steered_layer([33])
    seen = []

    def spy(rungs, form, *operands):
        seen.append(operands)
        return moe._rung(rungs[-1], form)(*operands)

    monkeypatch.setattr(moe, "routed_rows", spy)
    mod.apply(variables, x[0])
    full, small, fits = (moe._rung(c)(*seen[0])[0] for c in (64, 32, 48))
    np.testing.assert_allclose(fits, full, atol=1e-6)
    assert float(jnp.abs(small - full).max()) > 1e-3


def test_lanes_under_vmap_take_their_own_rung():
    """Two lanes whose counts land on different rungs, under ``jax.vmap``
    (JAX runs every rung and selects): each lane is its own layer."""
    mod, variables, x = _steered_layer([5, 33])
    c = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)
    got = jax.vmap(lambda x, c: _layer_value_and_grads(mod, variables, x, c))(
        x, c)
    assert [float(r.sum()) for r in got[0][1][1]] == [5, 33]
    for lane in range(2):
        want = _layer_value_and_grads(mod, variables, x[lane], c[lane])
        _assert_same(jax.tree.map(lambda a: a[lane], got), want, 2e-6)


# --- the federated round ------------------------------------------------------

def _round_spec(fixture: str = "BENCHMARK.tiny_lm.json"):
    import os
    from benchmarks.harness.spec import Spec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return Spec(os.path.join(root, "tests", "benchmark", "fixtures", fixture))


@pytest.mark.parametrize("seed,round_idx", [(3, 1), (2**31 + 9, 2)])
def test_one_fedavg_round_matches_the_reference_rounds(seed, round_idx):
    from benchmarks.harness import check, protocol
    from benchmarks.harness.cell import build_api, seed_program

    spec = _round_spec()
    cell = spec.cell("tiny_kanana2_sim")
    config = spec.config(cell["config"])
    gen = spec.module("traffic", config["generator"])
    rf = spec.module("references", config["reference"])
    ds, rows = gen.make(config, cell, seed)
    api = build_api(config, cell, ds)
    init = seed_program(api, rf, config, seed)
    loss = float(api.run_round(round_idx))
    state = jax.device_get(api.variables)
    api.close()
    ref_losses, ref_states = check.reference_rounds(
        rf, config, cell, rows, init, seed, [round_idx])
    out = check.compare([loss], [state], ref_losses, ref_states, init,
                        {"loss_rel": 1e-5, "update_norm_gap": 1e-4,
                         "change_norm_gap": 1e-4, "update_l2": 1e-4,
                         "lowp_share": 0.01})
    assert out["ok"], out["numbers"]
    # the round SUMS its clients' counters, where the reference's rounds
    # average every leaf: hold it to the reference's clients one by one
    ids = protocol.sample_cohort(round_idx, int(cell["clients"]),
                                 int(cell["fed_config"]["client_num_per_round"]),
                                 int(cell["sampling_seed"]))
    keys = protocol.client_keys(protocol.run_key(seed), round_idx, len(ids))
    x, y, m, counts = rows(ids)
    batch_size = int(config["recipe"]["batch_size"])
    want = jax.tree.map(np.zeros_like, init["counters"])
    for j in range(len(ids)):
        order = protocol.epoch_orders(keys[j], 1, m[j])

        def batched(a):
            return a[order].reshape((1, -1, batch_size) + a.shape[1:])

        new, _ = rf.local_train(config, init, batched(x[j]), batched(y[j]),
                                batched(m[j]), -(-int(counts[j]) // batch_size))
        want = jax.tree.map(lambda w, n: w + np.asarray(n), want,
                            jax.device_get(new["counters"]))
    assert len(ids) == 2
    for name, stats in want.items():
        assert stats["mlp"]["steps"] > 1
        np.testing.assert_array_equal(
            state["counters"][name]["mlp"]["expert_rows"],
            stats["mlp"]["expert_rows"])
        assert state["counters"][name]["mlp"]["steps"] == stats["mlp"]["steps"]
    # and close() published them
    from fedml_tpu.obs import model_counters

    assert model_counters()["steps.layer_1"] == want["layer_1"]["mlp"]["steps"]


@pytest.mark.parametrize("dtype,kind", [
    ("bfloat16", "ids"), ("float32", "ids"), ("bfloat16", "pixels")])
def test_token_ids_reach_the_model_unrounded(dtype, kind):
    """The resident stack casts floating inputs to bf16 when training in
    bf16 and leaves integer ids alone: an id above 256 does not survive
    bf16."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import FedDataset

    rng = np.random.default_rng(0)
    if kind == "ids":
        x = rng.integers(257, 16032, (3, 4, 16)).astype(np.int32)
        x[0, 0, 0] = 16031
        y, model, task, classes = x.copy(), "kanana2_tiny", "nwp", 16032
    else:
        x = rng.standard_normal((3, 4, 8)).astype(np.float32)
        y, model, task, classes = (rng.integers(0, 4, (3, 4)).astype(np.int32),
                                   "lr", "classification", 4)
    ds = FedDataset(train_x=x, train_y=y, train_mask=np.ones((3, 4), np.float32),
                    train_counts=np.full((3,), 4), test_x=x[0], test_y=y[0],
                    test_mask=np.ones((4,), np.float32), class_num=classes,
                    task=task)
    cfg = FedConfig(model=model, batch_size=2, epochs=1, lr=0.1, dtype=dtype,
                    client_num_in_total=3, client_num_per_round=2,
                    pack_lanes=1, device_data="on", comm_round=1)
    api = FedAvgAPI(ds, cfg, create_model(
        model, classes, input_shape=x.shape[2:],
        **({"dtype": jnp.float32} if kind == "ids" else {})))
    placed = api._dev_train[0]
    if kind == "ids":
        assert placed.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(placed), x)
        assert np.isfinite(float(api.run_round(1)))
    else:
        assert placed.dtype == (jnp.bfloat16 if dtype == "bfloat16"
                                else jnp.float32)
    api.close()


@pytest.mark.parametrize("fixture,cell_name", [
    ("BENCHMARK.tiny_lm.json", "tiny_kanana2_sim"),
    ("BENCHMARK.tiny_hybrid.json", "tiny_ling3_sim"),
    ("BENCHMARK.tiny_laguna.json", "tiny_laguna_sim"),
    ("BENCHMARK.tiny_granite4h.json", "tiny_granite4h_sim"),
    ("BENCHMARK.tiny_zaya1.json", "tiny_zaya1_sim"),
    ("BENCHMARK.tiny_nemotron3s.json", "tiny_nemotron3s_sim")])
def test_lowered_lm_round_program_names_every_scope(fixture, cell_name):
    """An LM's round program carries the step's scopes and the
    ``fedml.lm.*`` names of what it is built of, as metadata only: all of
    the table but the window layers' name for the hybrid decoder, that
    without the delta rule's two for the latent-attention one, for the
    window / full decoder all but the delta rule's; the state-space
    recurrence's two are the state-space decoder's alone, which is dense
    and names no router and no expert; the mixing's name is the
    compressed-attention decoder's alone."""
    import re

    from benchmarks.harness.cell import build_api
    from fedml_tpu.obs import tracer
    from fedml_tpu.parallel.packed import plan_arrays_tuple

    spec = _round_spec(fixture)
    cell = spec.cell(cell_name)
    config = spec.config(cell["config"])
    ds, _rows = spec.module("traffic", config["generator"]).make(config, cell, 1)
    api = build_api(config, cell, ds)
    round_plan = api._round_plan(1)
    sampled, plan = round_plan.sampled, round_plan.lanes
    step = api.build_round_step_packed(plan.shape_key)
    args = (api.variables, api.server_state, *api._dev_train[:3],
            jnp.asarray(sampled, jnp.int32),
            jnp.ones((len(sampled),), jnp.float32), jax.random.key(0),
            tuple(jnp.asarray(a) for a in plan_arrays_tuple(plan)))
    lowered = step.lower(*args)
    named = lowered.as_text(debug_info=True)
    found = set(re.findall(r"fedml\.[a-z_.]+", named))
    table = {v for k, v in vars(tracer).items() if k.startswith("SCOPE_")}
    mixers = config["model"].get("mixers", ())
    if "delta" not in mixers:
        table -= {tracer.SCOPE_LM_KDA, tracer.SCOPE_LM_KDA_PREP}
    if "window" not in mixers:
        table -= {tracer.SCOPE_LM_ATTN_WINDOW}
    if "ssd" not in mixers:
        table -= {tracer.SCOPE_LM_SSD, tracer.SCOPE_LM_SSD_PREP}
    if "cca" not in mixers:
        table -= {tracer.SCOPE_LM_CCA_MIX}
    sizes = config["model"]
    if not sizes.get("moe_latent"):
        table -= {tracer.SCOPE_LM_LATENT}
    if sizes["first_dense"] == sizes["layers"]:
        table -= {tracer.SCOPE_LM_ROUTE, tracer.SCOPE_LM_EXPERTS}
    assert found == table
    text = lowered.as_text()
    assert "fedml." not in text
    # one lane, so no lane axis: the lane loop branches twice a step, at a
    # client's reset and at its emit (parallel/packed.make_lane_train)
    cases = text.count('"stablehlo.case"')
    if sizes["first_dense"] == sizes["layers"]:
        assert cases == 2
        api.close()
        return
    # the sparse layers' row capacities: one conditional a layer and pass
    # (forward, and the backward that rebuilds its rung; the remat replay's
    # is dead code), a branch a rung, one shared function a rung; inside a
    # branch the LAST fedml.* name is still the route's or the experts'
    pairs = (int(config["recipe"]["batch_size"]) * sizes["seq_len"]
             * sizes["top_k"])
    rungs = moe.row_rungs(pairs)
    assert len(rungs) == 4
    conds = [c for c in re.findall(
        r'"stablehlo\.case"\(.*?\n +\}\) :', text, re.S) if "@rung" in c]
    assert len(conds) == cases - 2
    # (a scaled residual's gradient reads the branch it scales, and the
    # gradient of the projection out of a latent reads the routed sum it
    # projects, so there the replay's conditional is live too)
    passes = (3 if sizes.get("scaled_residual") or sizes.get("moe_latent")
              else 2)
    sparse = (list(sizes["mlps"]).count("sparse") if sizes.get("mlps")
              else sizes["layers"] - sizes["first_dense"])
    assert len(conds) == passes * sparse
    for cond in conds:
        assert cond.count("func.call @rung") == len(rungs) == cond.count(
            "stablehlo.return")
    assert len(set(re.findall(r"func\.call @(rung[a-z_0-9]*)", text))) == passes * len(rungs)
    inside = set(re.findall(r'loc\("([^"]*moe_rows_[^"]*)"', named))
    assert ({int(c) for path in inside
             for c in re.findall(r"moe_rows_(\d+)", path)} == set(rungs))
    for path in inside:
        last = re.findall(r"fedml\.[a-z_.]+", path)
        assert last and last[-1] in (tracer.SCOPE_LM_ROUTE,
                                     tracer.SCOPE_LM_EXPERTS), path
    api.close()


# --- the hybrid decoder: delta-rule and latent mixers, group-limited router --

from benchmarks.references import ling3_flash_vl as hyb  # noqa: E402


def hybrid_config(**over):
    sizes = {**LATENT_MOE_PRESETS["ling3_tiny"], **over}
    return {"name": "tiny_hybrid", "model": sizes, "data": {"vocab": VOCAB},
            "recipe": {"lr": 0.1, "momentum": 0.0}}


@pytest.mark.parametrize("over,remat", [
    ({}, True), ({"held_first": 4, "held_count": 8}, False),
    ({"mixers": ["latent", "delta", "delta", "latent"]}, True)])
def test_hybrid_logits_loss_and_gradients_match_the_reference(over, remat):
    """The mixers' pattern is data: the same module builds any of them."""
    config = hybrid_config(**over)
    v = hyb.init(jax.random.key(7), config)
    b = create_model("ling3_tiny", VOCAB, input_shape=(32,),
                     dtype=jnp.float32, remat=remat, **over)
    assert (jax.tree.map(jnp.shape, b.init(jax.random.key(0)))
            == jax.tree.map(jnp.shape, v))
    x, y, m = batch(t=32)
    forward = hyb._forward(config, "reference")

    def program(p):
        logits, new = b.apply_train({**v, "params": p}, x, None)
        return nwp.loss(logits, y, m), (logits, new["counters"])

    def reference(p):
        logits, stats, _ = forward(p, v["counters"], x)
        per = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                                   -1)[..., 0]
        w = jnp.broadcast_to(m[:, None], per.shape)
        return jnp.sum(per * w) / jnp.sum(w), (logits, stats)

    with jax.default_matmul_precision("highest"):
        (lp, (op, sp)), gp = jax.value_and_grad(program, has_aux=True)(v["params"])
        (lr, (orf, sr)), gr = jax.value_and_grad(reference, has_aux=True)(v["params"])
    np.testing.assert_allclose(op, orf, atol=5e-6)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        # the chunked scan sums in another order than the recurrence: the
        # decay's own gradients are small differences of large terms
        np.testing.assert_allclose(a, c, atol=5e-4 * float(jnp.abs(c).max() + 1e-6)
                                   + 1e-9, err_msg=str(path))
    for name in sp:
        for leaf in ("expert_rows", "steps", "group_tokens"):
            np.testing.assert_array_equal(sp[name]["mlp"][leaf],
                                          sr[name]["mlp"][leaf], err_msg=leaf)


def test_mixers_must_name_every_layer():
    with pytest.raises(ValueError, match="mixers"):
        create_model("ling3_tiny", VOCAB, mixers=["delta", "latent"]).init(
            jax.random.key(0))


@pytest.mark.parametrize("n_group,topk_group,top_k", [(4, 2, 4), (8, 4, 8),
                                                      (2, 1, 3)])
def test_group_limited_selection_against_a_naive_one(n_group, topk_group, top_k):
    """A group's score is the sum of its two largest ``score + bias``; only
    the best groups' experts stand; ``top_k`` over those; weights from the
    UNBIASED scores."""
    n, e = 64, 32
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (n, e)))
    bias = 0.3 * jax.random.normal(jax.random.key(2), (e,))
    groups = moe.chosen_groups(scores + bias, n_group, topk_group)
    idx, w = moe.route(scores, bias, top_k, 2.5, groups)
    s, b = np.asarray(scores, np.float64), np.asarray(bias, np.float64)
    size = e // n_group
    for t in range(n):
        biased = s[t] + b
        gscore = [np.sort(biased[g * size:(g + 1) * size])[-2:].sum()
                  for g in range(n_group)]
        best = set(np.argsort(gscore)[-topk_group:])
        assert set(np.flatnonzero(groups[t])) == best
        stands = [i for i in range(e) if i // size in best]
        want = sorted(stands, key=lambda i: -biased[i])[:top_k]
        assert set(np.asarray(idx[t])) == set(want)
        chosen = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(w[t], chosen / chosen.sum() * 2.5, rtol=1e-5)
    # and the reference's own, written another way, agrees
    np.testing.assert_array_equal(
        groups, hyb.chosen_groups(scores + bias, n_group, topk_group))


def test_route_without_groups_is_the_ungrouped_router():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (16, 8)))
    bias = jnp.zeros((8,))
    every = jnp.ones((16, 2), jnp.bool_)
    for a, b in zip(moe.route(scores, bias, 2, 1.0),
                    moe.route(scores, bias, 2, 1.0, every)):
        np.testing.assert_array_equal(a, b)


def test_group_routed_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 of 16 experts in 4 groups (a share holds one whole
    group, as the cell's 8 held experts lie in group 0): the routed parts of
    all the shares, plus the shared expert counted once, are the uncut
    reference layer's output; each share counts the tokens its group stood
    for, 2 of 4 groups a token."""
    d, n_routed, width, k = 32, 16, 24, 4
    config = hybrid_config(held_first=0, held_count=n_routed)
    v = hyb.init(jax.random.key(3), config)
    p = v["params"]["layer_1"]["mlp"]
    x = jax.random.normal(jax.random.key(4), (2, 32, d), jnp.float32)

    def layer(first, count, n_shared):
        mod = SharedRoutedMoe(n_routed, k, width, n_shared, 2.5, first, count,
                              jnp.float32, 4, 2)
        params = {kk: (a[first:first + count] if kk in ("gate", "up", "down")
                       else a) for kk, a in p.items() if n_shared or kk != "shared"}
        stats = {"expert_rows": jnp.zeros((count,)), "steps": jnp.zeros(()),
                 "group_tokens": jnp.zeros(())}
        (out, _), new = mod.apply({"params": params, "counters": stats}, x, True,
                             mutable=["counters"])
        return out, new["counters"]

    with jax.default_matmul_precision("highest"):
        whole, stats = layer(0, n_routed, 1)
        shared_once = whole - layer(0, n_routed, 0)[0]
        shares = [layer(first, 4, 0) for first in range(0, n_routed, 4)]
        uncut, (rows, reached), _ = hyb._forward(config, "reference").moe(x, p)
    np.testing.assert_allclose(sum(s[0] for s in shares) + shared_once, whole,
                               atol=3e-6)
    np.testing.assert_allclose(whole, uncut, atol=3e-6)
    assert float(rows.sum()) == 64 * k == float(stats["expert_rows"].sum())
    assert float(reached) == 64 == float(stats["group_tokens"])
    assert sum(float(s[1]["group_tokens"]) for s in shares) == 64 * 2
    # a share's rows come only from tokens its group stood for
    for out, c in shares:
        assert float(c["expert_rows"].sum()) <= k * float(c["group_tokens"])


def test_hybrid_registered_defaults_are_the_published_widths():
    k = LATENT_MOE_PRESETS["ling3_flash_vl"]
    assert (k["dim"], k["heads"], k["delta_head_dim"], k["delta_conv"],
            k["delta_lower_bound"]) == (2560, 32, 128, 4, -5.0)
    assert (k["nope"], k["rope"], k["v_dim"], k["kv_rank"]) == (128, 64, 128, 512)
    assert (k["n_routed"], k["top_k"], k["n_group"], k["topk_group"],
            k["n_shared"], k["expert_width"], k["dense_width"],
            k["routed_scaling"]) == (512, 8, 8, 4, 1, 768, 6144, 2.5)
    assert k["mixers"].count("delta") == 6 and k["mixers"][4] == "latent"
    b = create_model("ling3_flash_vl", 19648)
    shapes = jax.eval_shape(b.init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))
    assert n == 822_036_928              # the issue's 822.0 M parameters
    layer = shapes["params"]["layer_1"]
    assert layer["mlp"]["router"].shape == (2560, 512)
    assert layer["mlp"]["gate"].shape == (8, 2560, 768)
    assert sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(layer["delta"])) == 52_646_048
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        shapes["params"]["layer_4"]["attn"])) == 31_966_208


def test_hybrid_round_counts_its_groups_and_trains():
    """One packed round of the tiny hybrid model: the counters are sums over
    the clients' steps, the loss is finite and the weights move."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import FedDataset

    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (4, 3, 33)).astype(np.int32)
    ds = FedDataset(train_x=ids[..., :-1], train_y=ids[..., 1:],
                    train_mask=np.ones((4, 3), np.float32),
                    train_counts=np.full((4,), 3, np.int64),
                    test_x=ids[0, :, :-1], test_y=ids[0, :, 1:],
                    test_mask=np.ones(3, np.float32), class_num=VOCAB,
                    task="nwp", name="tiny")
    cfg = FedConfig(model="ling3_tiny", dataset="tiny", batch_size=1, epochs=1,
                    client_optimizer="sgd", lr=0.1, momentum=0.0,
                    client_num_in_total=4, client_num_per_round=2,
                    pack_lanes=1, device_data="on", comm_round=1)
    api = FedAvgAPI(ds, cfg, create_model("ling3_tiny", VOCAB,
                                          input_shape=(32,)))
    before = jax.device_get(api.variables)
    loss = float(api.run_round(1))
    after = jax.device_get(api.variables)
    assert np.isfinite(loss)
    c = after["counters"]["layer_1"]["mlp"]
    assert float(c["steps"]) == 6.0                  # 2 clients x 3 sequences
    assert 0 < float(c["group_tokens"]) <= 6 * 32
    assert float(c["expert_rows"].sum()) <= 4 * float(c["group_tokens"])
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         after["params"], before["params"])
    assert moved["layer_0"]["delta"]["f_proj"]["kernel"] > 0
    assert moved["layer_2"]["attn"]["q_norm"]["scale"] > 0
    counters = api.bundle.counters(api.variables)
    assert counters["group_tokens.layer_1"] == float(c["group_tokens"])
    api.close()


# --- the window / full decoder: grouped-query mixers, a softmax router ------

from benchmarks.references import laguna_xs2 as lag  # noqa: E402


def laguna_config(**over):
    sizes = {**LATENT_MOE_PRESETS["laguna_tiny"], **over}
    return {"name": "tiny_laguna", "model": sizes, "data": {"vocab": VOCAB},
            "recipe": {"lr": 0.1, "momentum": 0.0}}


@pytest.mark.parametrize("over,remat", [
    ({}, True),
    ({"held_first": 4, "held_count": 8,
      "mixers": ["window", "full", "window"]}, False)])
def test_laguna_logits_loss_and_gradients_match_the_reference(over, remat):
    """6 and 8 query heads over 2 key-value heads, a window of 8 in 32
    positions, YaRN in the full layers, the head-wise gate, the softmax
    router; the layers' pattern is data."""
    config = laguna_config(**over)
    v = jax.jit(lambda k: lag.init(k, config))(jax.random.key(7))
    b = create_model("laguna_tiny", VOCAB, input_shape=(32,),
                     dtype=jnp.float32, remat=remat, **over)
    assert (jax.tree.map(jnp.shape, b.init(jax.random.key(0)))
            == jax.tree.map(jnp.shape, v))
    x, y, m = batch(t=32)
    forward = lag._forward(config, "reference")

    def program(p):
        logits, new = b.apply_train({**v, "params": p}, x, None)
        return nwp.loss(logits, y, m), (logits, new["counters"])

    def reference(p):
        logits, stats, _ = forward(p, v["counters"], x)
        per = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                                   -1)[..., 0]
        w = jnp.broadcast_to(m[:, None], per.shape)
        return jnp.sum(per * w) / jnp.sum(w), (logits, stats)

    with jax.default_matmul_precision("highest"):       # jitted: a program
        # run op by op on the CPU takes ten times as long
        (lp, (op, sp)), gp = jax.jit(jax.value_and_grad(
            program, has_aux=True))(v["params"])
        (lr, (orf, sr)), gr = jax.jit(jax.value_and_grad(
            reference, has_aux=True))(v["params"])
    np.testing.assert_allclose(op, orf, atol=5e-6)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, c, atol=5e-6 * float(jnp.abs(c).max() + 1e-6)
                                   + 1e-9, err_msg=str(path))
    # a softmax router has no correction bias; the gate has no norm
    assert "e_score_correction_bias" not in gp["layer_1"]["mlp"]
    assert set(gp["layer_1"]["attn"]["out_gate"]) == {"proj"}
    for name in sp:
        np.testing.assert_array_equal(sp[name]["mlp"]["expert_rows"],
                                      sr[name]["mlp"]["expert_rows"])
        assert float(sp[name]["mlp"]["steps"]) == 1.0
    # what the two controls of its own leave out shows in the logits
    for variant in ("window_full", "rope_plain"):
        other = jax.jit(lag._forward(config, variant))(
            v["params"], v["counters"], x)[0]
        assert float(jnp.abs(other - orf).max()) > 1e-3, variant


def test_yarn_frequencies_against_numbers_written_out_by_hand():
    """The published keys (theta 500,000, factor 64, original 4,096,
    beta_fast 64, beta_slow 1, rotary width 64): ``corr(64)`` = 5.05 and
    ``corr(1)`` = 15.19, so pairs 0 - 5 keep ``theta^(-i/32)``, pairs 16 - 31
    take a 64th of it, and pair ``i`` between blends by ``(i - 5) / 11``.
    Three values are what ``transformers`` 4.57 returns for these keys. The
    program's function and the reference's own agree."""
    from fedml_tpu.models.transformer import yarn_frequencies

    f = yarn_frequencies(64, 5e5, 64.0, 4096, 64.0, 1.0)
    plain = 5e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(f[16:], plain[16:] / 64, rtol=1e-12)
    ramp = (np.arange(6, 16) - 5) / 11.0
    np.testing.assert_allclose(
        f[6:16], plain[6:16] * (1 - ramp) + plain[6:16] / 64 * ramp, rtol=1e-12)
    np.testing.assert_allclose(
        [f[6], f[16], f[31]], [0.0777550, 2.2097085e-5, 4.7091532e-8], rtol=1e-6)
    assert 0.1 * np.log(64) + 1 == pytest.approx(1.4158883083359672)
    np.testing.assert_allclose(
        f, lag._yarn_frequencies(64, 5e5, 64.0, 4096, 64.0, 1.0), rtol=1e-12)
    # no blend: the plain law
    np.testing.assert_allclose(yarn_frequencies(64, 5e5, 1.0, 4096, 64.0, 1.0),
                               plain, rtol=1e-12)


def test_rotary_with_given_frequencies_and_scale():
    """``rotary`` turns pair ``i`` by ``pos * inv_freq[i]`` and scales both
    components; without either it is what it was."""
    from fedml_tpu.models.transformer import rotary

    x = jax.random.normal(jax.random.key(0), (2, 5, 8))
    inv = np.array([1.0, 0.5, 0.25, 0.125])
    got = np.asarray(rotary(x, 10.0, inv, 1.5))
    pos = np.arange(5)[:, None] * inv
    a, b = np.asarray(x)[..., 0::2], np.asarray(x)[..., 1::2]
    want = np.stack([a * np.cos(pos) - b * np.sin(pos),
                     a * np.sin(pos) + b * np.cos(pos)], -1).reshape(x.shape)
    np.testing.assert_allclose(got, 1.5 * want, atol=1e-6)
    np.testing.assert_array_equal(
        rotary(x, 10.0), rotary(x, 10.0, 10.0 ** (-np.arange(0, 8, 2) / 8)))


@pytest.mark.parametrize("top_k,scaling", [(4, 2.5), (8, 1.0), (1, 2.5)])
def test_softmax_routing_against_a_naive_one(top_k, scaling):
    """Softmax over all the logits, the ``top_k`` largest, weights
    normalised over the chosen and scaled; no bias anywhere."""
    n, e = 64, 32
    logits = jax.random.normal(jax.random.key(1), (n, e))
    scores = jax.nn.softmax(logits, axis=-1)
    idx, w = moe.route(scores, None, top_k, scaling)
    z = np.asarray(logits, np.float64)
    for t in range(n):
        p = np.exp(z[t] - z[t].max())
        p /= p.sum()
        want = np.argsort(-p)[:top_k]
        assert set(np.asarray(idx[t])) == set(want)
        chosen = p[np.asarray(idx[t])]
        np.testing.assert_allclose(w[t], chosen / chosen.sum() * scaling,
                                   rtol=1e-5)
    # the reference's own router, written another way, agrees
    config = laguna_config(n_routed=e, top_k=top_k, routed_scaling=scaling)
    x = jax.random.normal(jax.random.key(2), (n, 32))
    router = jax.random.normal(jax.random.key(3), (32, e))
    ridx, rw = lag._forward(config, "reference").choose(x, {"router": router})
    with jax.default_matmul_precision("highest"):
        pidx, pw = moe.route(jax.nn.softmax(x @ router, -1), None, top_k,
                             scaling)
    np.testing.assert_array_equal(np.sort(ridx, -1), np.sort(pidx, -1))
    np.testing.assert_allclose(np.sort(rw, -1), np.sort(pw, -1), rtol=1e-5)


def test_softmax_routed_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 of 16 experts: the routed parts of all the shares, plus
    the shared expert counted once, are the uncut reference layer's output;
    the router has no bias parameter and no group."""
    d, n_routed, width, k = 32, 16, 24, 4
    config = laguna_config(held_first=0, held_count=n_routed)
    v = jax.jit(lambda k: lag.init(k, config))(jax.random.key(3))
    p = v["params"]["layer_1"]["mlp"]
    assert "e_score_correction_bias" not in p
    x = jax.random.normal(jax.random.key(4), (2, 32, d), jnp.float32)

    def layer(first, count, n_shared):
        mod = SharedRoutedMoe(n_routed, k, width, n_shared, 2.5, first, count,
                              jnp.float32, score="softmax")
        params = {kk: (a[first:first + count] if kk in ("gate", "up", "down")
                       else a) for kk, a in p.items() if n_shared or kk != "shared"}
        stats = {"expert_rows": jnp.zeros((count,)), "steps": jnp.zeros(())}
        (out, _), new = mod.apply({"params": params, "counters": stats}, x, True,
                             mutable=["counters"])
        return out, new["counters"]

    with jax.default_matmul_precision("highest"):
        whole, stats = layer(0, n_routed, 1)
        shared_once = whole - layer(0, n_routed, 0)[0]
        shares = [layer(first, 4, 0) for first in range(0, n_routed, 4)]
        uncut, rows, _ = lag._forward(config, "reference").moe(x, p)
    np.testing.assert_allclose(sum(s[0] for s in shares) + shared_once, whole,
                               atol=3e-6)
    np.testing.assert_allclose(whole, uncut, atol=3e-6)
    assert float(rows.sum()) == 64 * k == float(stats["expert_rows"].sum())
    assert sum(float(s[1]["expert_rows"].sum()) for s in shares) == 64 * k
    with pytest.raises(ValueError, match="groups"):
        SharedRoutedMoe(n_routed, k, width, 0, 2.5, 0, 4, jnp.float32, 4, 2,
                        "softmax").init(jax.random.key(0), x)


def test_laguna_registered_defaults_are_the_published_widths():
    k = LATENT_MOE_PRESETS["laguna_xs2"]
    assert (k["dim"], k["heads"], k["window_heads"], k["kv_heads"], k["v_dim"],
            k["window"]) == (2048, 48, 64, 8, 128, 512)
    assert k["nope"] + k["rope"] == k["v_dim"] and k["rope"] == 64
    assert (k["n_routed"], k["top_k"], k["n_shared"], k["expert_width"],
            k["dense_width"], k["routed_scaling"], k["score"]) == (
                256, 8, 1, 512, 8192, 2.5, "softmax")
    assert k["mixers"] == ["full", "window", "window", "window", "full"]
    # an eighth of the experts held: the first row capacity's edge
    assert k["held_count"] / k["n_routed"] == moe.ROW_RUNG_SHARES[0]
    b = create_model("laguna_xs2", 12544)
    shapes = jax.eval_shape(b.init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == 691_623_936        # the issue's 691.6 M parameters
    assert count(p["layer_0"]["attn"]) == 29_458_432
    assert count(p["layer_1"]["attn"]) == 37_879_808
    assert count(p["layer_1"]["mlp"]) == 104_333_312
    assert p["layer_1"]["mlp"]["router"].shape == (2048, 256)
    assert p["layer_1"]["mlp"]["gate"].shape == (32, 2048, 512)
    assert p["layer_1"]["attn"]["out_gate"]["proj"]["kernel"].shape == (2048, 64)
    assert p["layer_4"]["attn"]["k_proj"]["kernel"].shape == (2048, 1024)


def test_laguna_round_counts_its_rows_and_trains():
    """One packed round of the tiny window / full model: the counters are
    sums over the clients' steps, the loss is finite and the weights move."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import FedDataset

    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (4, 4, 33)).astype(np.int32)
    ds = FedDataset(train_x=ids[..., :-1], train_y=ids[..., 1:],
                    train_mask=np.ones((4, 4), np.float32),
                    train_counts=np.full((4,), 4, np.int64),
                    test_x=ids[0, :, :-1], test_y=ids[0, :, 1:],
                    test_mask=np.ones(4, np.float32), class_num=VOCAB,
                    task="nwp", name="tiny")
    cfg = FedConfig(model="laguna_tiny", dataset="tiny", batch_size=2, epochs=1,
                    client_optimizer="sgd", lr=0.1, momentum=0.0,
                    client_num_in_total=4, client_num_per_round=2,
                    pack_lanes=1, device_data="on", comm_round=1)
    api = FedAvgAPI(ds, cfg, create_model("laguna_tiny", VOCAB,
                                          input_shape=(32,)))
    before = jax.device_get(api.variables)
    loss = float(api.run_round(1))
    after = jax.device_get(api.variables)
    assert np.isfinite(loss)
    c = after["counters"]["layer_1"]["mlp"]
    assert float(c["steps"]) == 4.0                  # 2 clients x 2 batches
    assert 0 < float(c["expert_rows"].sum()) <= 4 * 64 * 4
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         after["params"], before["params"])
    assert moved["layer_0"]["attn"]["out_gate"]["proj"]["kernel"] > 0
    assert moved["layer_1"]["attn"]["k_proj"]["kernel"] > 0
    assert moved["layer_2"]["mlp"]["router"] > 0
    counters = api.bundle.counters(api.variables)
    assert counters["steps.layer_1"] == 4.0 and "rows.layer_2.3" in counters
    api.close()


# --- the state-space / attention hybrid: Mamba-2 mixers, the four ----------
# --- multipliers, a tied head ------------------------------------------------

from benchmarks.references import granite4_h_micro as gra  # noqa: E402


def granite_config(**over):
    sizes = {**LATENT_MOE_PRESETS["granite4h_tiny"], **over}
    return {"name": "tiny_granite4h", "model": sizes, "data": {"vocab": VOCAB},
            "recipe": {"lr": 0.1, "momentum": 0.0}}


def _granite_program_and_reference(config, remat=True, **over):
    v = jax.jit(lambda k: gra.init(k, config))(jax.random.key(7))
    b = create_model("granite4h_tiny", VOCAB, input_shape=(32,),
                     dtype=jnp.float32, remat=remat, **over)
    assert (jax.tree.map(jnp.shape, b.init(jax.random.key(0)))
            == jax.tree.map(jnp.shape, v))
    return v, b, gra._forward(config, "reference")


@pytest.mark.parametrize("over,remat", [
    ({}, True),
    ({"mixers": ["full", "ssd", "ssd", "full"], "ssd_chunk": 12}, False)])
def test_granite_logits_loss_and_gradients_match_the_reference(monkeypatch,
                                                               over, remat):
    """8 state-space heads of 8 over a state of 16 in chunks of 8 (and of 12,
    which does not divide the 32 positions), 4 query heads over 2 key-value
    heads of 8 with no rotary and a given score scale, the four multipliers
    and the tied head, against the reference's token-by-token recurrence;
    the layers' pattern is data."""
    config = granite_config(**over)
    v, b, forward = _granite_program_and_reference(config, remat, **over)
    x, y, m = batch(t=32)

    def program(p):
        logits, new = b.apply_train({**v, "params": p}, x, None)
        return nwp.loss(logits, y, m), (logits, new["counters"])

    def reference(p):
        logits, stats = forward(p, v["counters"], x)
        per = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                                   -1)[..., 0]
        w = jnp.broadcast_to(m[:, None], per.shape)
        return jnp.sum(per * w) / jnp.sum(w), (logits, stats)

    with jax.default_matmul_precision("highest"):
        (lp, (op, sp)), gp = jax.jit(jax.value_and_grad(
            program, has_aux=True))(v["params"])
        (lr, (orf, sr)), gr = jax.jit(jax.value_and_grad(
            reference, has_aux=True))(v["params"])
    np.testing.assert_allclose(op, orf, atol=5e-6)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, c, atol=1e-5 * float(jnp.abs(c).max() + 1e-6)
                                   + 1e-9, err_msg=str(path))
    # a dense decoder with a tied head: no router, no expert, no lm_head;
    # every leaf of the recurrence takes a gradient
    assert "lm_head" not in gp and "router" not in str(jax.tree.map(jnp.shape, gp))
    ssd_leaves = gp["layer_1"]["ssd"]
    assert set(ssd_leaves) == {"in_proj", "conv_kernel", "conv_bias", "A_log",
                               "dt_bias", "D", "norm", "out_proj"}
    for name in ("A_log", "dt_bias", "D", "conv_bias"):
        assert float(jnp.abs(ssd_leaves[name]).max()) > 0, name
    assert sorted(sp) == sorted(sr) == [
        f"layer_{i}" for i, k in enumerate(config["model"]["mixers"])
        if k == "ssd"]
    for name in sp:
        np.testing.assert_allclose(sp[name]["ssd"]["decay"],
                                   sr[name]["ssd"]["decay"], rtol=1e-6)
        assert 0.3 < float(sp[name]["ssd"]["decay"]) < 1.0
        assert float(sp[name]["ssd"]["steps"]) == 1.0
    # what the two controls of its own leave out shows in the logits: the
    # carry between blocks of the scan, and the multipliers
    # (against the stated precision, whose rounding they share)
    monkeypatch.setattr(gra, "_SCAN_BLOCK", 8)
    stated = jax.jit(gra._forward(config, "stated"))(
        v["params"], v["counters"], x)[0]
    assert float(jnp.abs(stated - orf).max()) < 0.02 * float(jnp.abs(orf).max())
    for variant, least in (("state_cut", 1e-4), ("scale_plain", 1e-2)):
        other = jax.jit(gra._forward(config, variant))(
            v["params"], v["counters"], x)[0]
        assert float(jnp.abs(other - stated).max()) > least, variant


@pytest.mark.parametrize("name,plain", [
    ("embed_scale", 1.0), ("residual_scale", 1.0), ("attn_scale", None),
    ("logit_scale", 1.0)])
def test_each_multiplier_is_seen(name, plain):
    """A model with one of the four multipliers left at its plain value
    gives other logits than the configuration's, and the reference built
    with the same one agrees with it again."""
    config = granite_config()
    v, b, forward = _granite_program_and_reference(config)
    # at hidden 32 the seeded scores are near zero and every softmax near
    # uniform, whatever its scale: widen the attention layer's q and k
    attn = v["params"]["layer_2"]["attn"]
    attn["q_proj"]["kernel"] = attn["q_proj"]["kernel"] * 40.0
    attn["k_proj"]["kernel"] = attn["k_proj"]["kernel"] * 40.0
    x, _y, _m = batch(t=32)
    want = b.apply_eval(v, x)
    np.testing.assert_allclose(want, forward(v["params"], v["counters"], x)[0],
                               atol=5e-6)
    other = create_model("granite4h_tiny", VOCAB, input_shape=(32,),
                         dtype=jnp.float32, **{name: plain})
    got = other.apply_eval(v, x)
    assert float(jnp.abs(got - want).max()) > 1e-3 * float(jnp.abs(want).max())
    plain_value = 8 ** -0.5 if plain is None else plain     # heads of 8
    again = gra._forward(granite_config(**{name: plain_value}), "reference")
    np.testing.assert_allclose(got, again(v["params"], v["counters"], x)[0],
                               atol=5e-6)


def test_mamba2_mixer_against_the_reference_and_its_gate_comes_before_the_norm():
    """The mixer alone on the reference's seeded leaves: the joint
    projection's split, the convolution with its bias over x, B, C together,
    softplus steps, the skip, the gated norm over all channels. A mixer that
    normalised BEFORE the gate would read another output."""
    from fedml_tpu.models.transformer import Mamba2Mixer

    config = granite_config()
    m = config["model"]
    p = jax.jit(lambda k: gra.init(k, config))(jax.random.key(5))[
        "params"]["layer_0"]["ssd"]
    mixer = Mamba2Mixer(m["ssd_heads"], m["ssd_head_dim"], m["ssd_state"],
                        m["ssd_conv"], m["ssd_chunk"], m["eps"])
    u = jax.random.normal(jax.random.key(6), (2, 32, m["dim"]))
    with jax.default_matmul_precision("highest"):
        zero = jnp.zeros((), jnp.float32)
        got = mixer.apply({"params": p, "counters": {"decay": zero,
                                                     "steps": zero}}, u)
        want, decay = gra._forward(config, "reference").ssd(u, p)
    np.testing.assert_allclose(got, want, atol=5e-6)
    assert 0.3 < float(decay) < 1.0
    # the same recurrence, the norm first and the gate after
    inner = m["ssd_heads"] * m["ssd_head_dim"]
    n = m["ssd_state"]
    zx = u @ p["in_proj"]["kernel"]
    z, xbc, dt = (zx[..., :inner], zx[..., inner:2 * inner + 2 * n],
                  zx[..., 2 * inner + 2 * n:])
    pad = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + 32] * p["conv_kernel"][i]
                          for i in range(4)) + p["conv_bias"])
    y = gra.recurrence(xbc[..., :inner].reshape(2, 32, m["ssd_heads"], -1),
                       jax.nn.softplus(dt + p["dt_bias"]), p["A_log"],
                       xbc[..., inner:inner + n], xbc[..., inner + n:], p["D"])
    y = y.reshape(2, 32, inner)

    def rms(a):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + m["eps"])

    with jax.default_matmul_precision("highest"):
        gate_first = (rms(y * jax.nn.silu(z)) * p["norm"]["scale"]
                      ) @ p["out_proj"]["kernel"]
        norm_first = (rms(y) * p["norm"]["scale"] * jax.nn.silu(z)
                      ) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(got, gate_first, atol=2e-5)
    assert float(jnp.abs(got - norm_first).max()) > 0.01 * float(jnp.abs(got).max())


# the joint projection's widths: 2 H P + 2 N + H = 168 (the tiny preset: no
# multiple of a lane tile's 128, as the published 8,512 is none) and 384
MIXER_WIDTHS = {168: {}, 384: {"ssd_heads": 16, "ssd_head_dim": 8,
                               "ssd_state": 56}}


def _mixer_and_reference(width, dtype):
    from fedml_tpu.models.transformer import Mamba2Mixer

    config = granite_config(**MIXER_WIDTHS[width])
    m = config["model"]
    assert (2 * m["ssd_heads"] * m["ssd_head_dim"] + 2 * m["ssd_state"]
            + m["ssd_heads"]) == width
    p = jax.jit(lambda k: gra.init(k, config))(jax.random.key(5))[
        "params"]["layer_0"]["ssd"]
    mixer = Mamba2Mixer(m["ssd_heads"], m["ssd_head_dim"], m["ssd_state"],
                        m["ssd_conv"], m["ssd_chunk"], m["eps"], dtype)
    zero = jnp.zeros((), jnp.float32)

    def program(p, u):
        return mixer.apply({"params": p, "counters": {"decay": zero,
                                                      "steps": zero}}, u)

    variant = "reference" if dtype == jnp.float32 else "stated"
    return m, p, program, lambda p, u: gra._forward(config, variant).ssd(u, p)[0]


@pytest.mark.parametrize("width", sorted(MIXER_WIDTHS))
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 8e-3)])
def test_mamba2_mixer_and_every_gradient_against_the_single_lin_reference(
        width, dtype, tol):
    """The joint projection as one product a consumer over column slices of
    the ONE kernel against the reference's single ``lin`` cut after the
    fact: the output, and the gradient of every leaf (``in_proj/kernel``
    whole, one ``[d, 2 H P + 2 N + H]`` array) and of the input."""
    dtype = jnp.dtype(dtype)
    m, p, program, reference = _mixer_and_reference(width, dtype)
    u = jax.random.normal(jax.random.key(6), (2, 32, m["dim"])).astype(dtype)
    c = jax.random.normal(jax.random.key(8), (2, 32, m["dim"]))

    def both(fn):
        def loss(p, u):
            out = fn(p, u)
            return jnp.sum(out.astype(jnp.float32) * c), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, got), (gp, gu) = both(program)(p, u)
        (_, want), (rp, ru) = both(reference)(p, u)
    assert got.dtype == dtype and gu.dtype == dtype
    assert gp["in_proj"]["kernel"].shape == (m["dim"], width)
    assert set(gp) == {"in_proj", "conv_kernel", "conv_bias", "A_log",
                       "dt_bias", "D", "norm", "out_proj"}
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path((got, gp, gu)),
            jax.tree.leaves((want, rp, ru))):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0, path
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max(), rtol=0,
                                   err_msg=str(path))


def test_mamba2_mixer_tree_is_the_parents_leaf_for_leaf():
    """Paths, shapes and dtypes as the parent commit's mixer made them (one
    ``in_proj/kernel`` of all the joint projection's columns, no padded,
    re-ordered or second leaf), and the same seeded numbers in it:
    ``Linear``'s initialiser under ``Linear``'s path."""
    from fedml_tpu.models.transformer import Linear, Mamba2Mixer

    u = jnp.zeros((1, 16, 32))
    v = Mamba2Mixer(8, 8, 16, 4, 8).init(jax.random.key(3), u)
    assert {jax.tree_util.keystr(path): (leaf.shape, str(leaf.dtype))
            for path, leaf in jax.tree_util.tree_leaves_with_path(v)} == {
        "['counters']['decay']": ((), "float32"),
        "['counters']['steps']": ((), "float32"),
        "['params']['A_log']": ((8,), "float32"),
        "['params']['D']": ((8,), "float32"),
        "['params']['conv_bias']": ((96,), "float32"),
        "['params']['conv_kernel']": ((4, 96), "float32"),
        "['params']['dt_bias']": ((8,), "float32"),
        "['params']['in_proj']['kernel']": ((32, 168), "float32"),
        "['params']['norm']['scale']": ((64,), "float32"),
        "['params']['out_proj']['kernel']": ((64, 32), "float32")}
    kernel = v["params"]["in_proj"]["kernel"]
    # the parent's numbers under the same key (read from its checkout)
    np.testing.assert_allclose(
        [kernel[0, 0], kernel[31, 167], jnp.abs(kernel).sum()],
        [-0.016635634005069733, 0.0031889884267002344, 84.93415832519531],
        rtol=1e-6)

    class Joint(nn.Module):
        @nn.compact
        def __call__(self, x):
            return Linear(168, name="in_proj")(x)

    np.testing.assert_array_equal(
        kernel, Joint().init(jax.random.key(3), u)["params"]["in_proj"]["kernel"])


@pytest.mark.parametrize("name,column", [
    ("z", 5), ("x", 64 + 5), ("B", 128 + 5), ("C", 128 + 16 + 5),
    ("dt", 128 + 32 + 5)])
def test_each_slice_of_in_proj_reaches_its_consumer(name, column):
    """One column of ``in_proj/kernel`` moved, in each consumer's range: the
    output moves, and it is again the reference's on the moved kernel (a
    slice wired to another consumer would read another output)."""
    m, p, program, reference = _mixer_and_reference(168, jnp.float32)
    u = jax.random.normal(jax.random.key(6), (2, 32, m["dim"]))
    moved = {**p, "in_proj": {"kernel": p["in_proj"]["kernel"].at[
        :, column].add(0.5)}}
    with jax.default_matmul_precision("highest"):
        before, after = program(p, u), program(moved, u)
        want = reference(moved, u)
    assert float(jnp.abs(after - before).max()) > 1e-3 * float(
        jnp.abs(before).max()), name
    np.testing.assert_allclose(after, want, atol=1e-5 * float(
        jnp.abs(want).max()))


def test_the_tied_tables_gradient_has_both_parts():
    """Untie the head (a model whose ``lm_head`` is the table transposed
    gives the same logits): the tied table's gradient is the sum of what the
    gather and the head take there."""
    config = granite_config()
    v, tied, _ = _granite_program_and_reference(config)
    untied = create_model("granite4h_tiny", VOCAB, input_shape=(32,),
                          dtype=jnp.float32, tied_head=False)
    x, y, m = batch(t=32)
    p = v["params"]
    p2 = {**p, "lm_head": {"kernel": p["embed"].T}}

    def loss(bundle, variables):
        def f(params):
            logits, _ = bundle.apply_train({**variables, "params": params}, x, None)
            return nwp.loss(logits, y, m)
        return f

    with jax.default_matmul_precision("highest"):
        g = jax.grad(loss(tied, v))(p)
        g2 = jax.grad(loss(untied, {**v, "params": p2}))(p2)
    gather, head = g2["embed"], g2["lm_head"]["kernel"].T
    assert float(jnp.abs(gather).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(g["embed"], gather + head,
                               atol=1e-6 * float(jnp.abs(head).max()))
    # an id no input holds takes the head's part alone
    unused = sorted(set(range(VOCAB)) - set(np.asarray(x).ravel().tolist()))
    assert unused and not np.any(np.asarray(gather[unused[0]]))
    assert np.any(np.asarray(g["embed"][unused[0]]))


@pytest.mark.parametrize("scale", [None, 0.3])
def test_grouped_attention_without_rotary_at_a_given_scale(scale):
    """``rotary_dim`` 0 and a given score scale against the plain formula:
    no channel turns, ``softmax(q . k * scale)`` over the causal prefix."""
    from fedml_tpu.models.transformer import GroupedAttention

    h, g, d, t = 4, 2, 8, 12
    mod = GroupedAttention(h, g, d, 0, scale=scale)
    # wide inputs: at the seeded 0.02 the scores are near zero and a softmax
    # near uniform at any scale
    x = 40.0 * jax.random.normal(jax.random.key(1), (2, t, 16))
    with jax.default_matmul_precision("highest"):
        v = mod.init(jax.random.key(2), x)
        got = mod.apply(v, x)
        p = v["params"]
        q = (x @ p["q_proj"]["kernel"]).reshape(2, t, h, d)
        k = jnp.repeat((x @ p["k_proj"]["kernel"]).reshape(2, t, g, d), h // g, 2)
        val = jnp.repeat((x @ p["v_proj"]["kernel"]).reshape(2, t, g, d), h // g, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5 if scale is None
                                                   else scale)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), val)
        want = o.reshape(2, t, h * d) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.abs(want).max()))
    if scale is not None:
        plain = GroupedAttention(h, g, d, 0).apply(v, x)
        assert float(jnp.abs(plain - got).max()) > 1e-3 * float(jnp.abs(got).max())


def test_granite_registered_defaults_are_the_published_widths():
    k = LATENT_MOE_PRESETS["granite4_h_micro"]
    assert (k["dim"], k["heads"], k["kv_heads"], k["v_dim"], k["rope"],
            k["dense_width"]) == (2048, 32, 8, 64, 0, 8192)
    assert (k["ssd_heads"], k["ssd_head_dim"], k["ssd_state"], k["ssd_conv"],
            k["ssd_chunk"]) == (64, 64, 128, 4, 256)
    assert (k["embed_scale"], k["residual_scale"], k["attn_scale"],
            k["logit_scale"], k["tied_head"]) == (12.0, 0.22, 1 / 64, 8.0, True)
    assert k["mixers"] == ["ssd"] * 5 + ["full"] + ["ssd"] * 4
    assert k["first_dense"] == k["layers"] == 10 and k["n_routed"] == 0
    b = create_model("granite4_h_micro", 12544)
    shapes = jax.eval_shape(b.init, jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))

    p = shapes["params"]
    assert count(p) == 772_160_448        # the issue's 772.2 M parameters
    assert count(p["layer_0"]) == 76_182_976
    assert count(p["layer_5"]) == 60_821_504
    assert p["layer_0"]["ssd"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert p["layer_0"]["ssd"]["conv_kernel"].shape == (4, 4352)
    assert p["layer_0"]["ssd"]["norm"]["scale"].shape == (4096,)
    assert p["layer_5"]["attn"]["k_proj"]["kernel"].shape == (2048, 512)
    assert p["embed"].shape == (12544, 2048) and "lm_head" not in p
    # only the state-space layers keep counters
    assert sorted(shapes["counters"]) == [f"layer_{i}" for i in range(10)
                                          if i != 5]


def test_granite_round_sums_its_decays_and_trains():
    """One packed round of the tiny state-space model, a dense tree with no
    ``rows.*``: the counters are sums over the clients' steps, the loss is
    finite and every kind of leaf moves."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import FedDataset

    rng = np.random.default_rng(0)
    ids = rng.integers(0, VOCAB, (4, 3, 33)).astype(np.int32)
    ds = FedDataset(train_x=ids[..., :-1], train_y=ids[..., 1:],
                    train_mask=np.ones((4, 3), np.float32),
                    train_counts=np.full((4,), 3, np.int64),
                    test_x=ids[0, :, :-1], test_y=ids[0, :, 1:],
                    test_mask=np.ones(3, np.float32), class_num=VOCAB,
                    task="nwp", name="tiny")
    cfg = FedConfig(model="granite4h_tiny", dataset="tiny", batch_size=1,
                    epochs=1, client_optimizer="sgd", lr=0.1, momentum=0.0,
                    client_num_in_total=4, client_num_per_round=2,
                    pack_lanes=1, device_data="on", comm_round=1)
    api = FedAvgAPI(ds, cfg, create_model("granite4h_tiny", VOCAB,
                                          input_shape=(32,)))
    before = jax.device_get(api.variables)
    loss = float(api.run_round(1))
    after = jax.device_get(api.variables)
    assert np.isfinite(loss)
    c = after["counters"]["layer_0"]["ssd"]
    assert float(c["steps"]) == 6.0                  # 2 clients x 3 sequences
    assert 0.3 < float(c["decay"]) / 6.0 < 1.0
    moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                         after["params"], before["params"])
    for leaf in ("dt_bias", "D", "conv_bias", "conv_kernel", "in_proj"):
        leaf_moved = moved["layer_0"]["ssd"][leaf]
        assert (leaf_moved["kernel"] if leaf == "in_proj" else leaf_moved) > 0, leaf
    assert moved["layer_2"]["attn"]["k_proj"]["kernel"] > 0
    assert moved["embed"] > 0
    counters = api.bundle.counters(api.variables)
    assert counters["steps.layer_0"] == 6.0
    assert counters["decay.layer_3"] == float(
        after["counters"]["layer_3"]["ssd"]["decay"])
    assert not [k for k in counters if k.startswith("rows.")]
    api.close()


def test_a_model_without_counters_publishes_none():
    """``bundle.counters`` on a tree with no ``counters`` collection (all
    attention, all dense): an empty dict, not a failure."""
    b = create_model("granite4h_tiny", VOCAB, input_shape=(32,),
                     mixers=("full",) * 4)
    v = b.init(jax.random.key(0))
    assert "counters" not in v and b.counters(v) == {}
