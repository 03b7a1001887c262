"""The compressed-convolutional-attention MoE decoder (``zaya1_tiny``) at a
small size on the CPU: the mixer against its equations written as loops, the
MLP router with its carry and its choice that is no expert, the two shares
against the uncut layer, the whole model against the plain reference
(``benchmarks/references/zaya1_8b.py``), and the four presets the model
family already had, which read what they read before. Seeded random weights
throughout; float32 at the highest matmul precision on both sides, so a
tolerance is float32's own rounding over a few hundred terms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import zaya1_8b as ref
from fedml_tpu.core.tasks import nwp
from fedml_tpu.models import create_model
from fedml_tpu.models.moe import (LATENT_MOE_PRESETS, MlpRouter,
                                  SharedRoutedMoe, layer_counters)
from fedml_tpu.models.transformer import CompressedConvAttention

VOCAB = 64


def zaya_config(**over):
    sizes = {**LATENT_MOE_PRESETS["zaya1_tiny"], **over}
    return {"name": "tiny_zaya1", "model": sizes, "data": {"vocab": VOCAB},
            "recipe": {"lr": 0.1, "momentum": 0.0}}


def batch(seed=1, n=4, t=32):
    x = jax.random.randint(jax.random.key(seed), (n, t + 1), 0, VOCAB)
    return x[:, :-1], x[:, 1:], jnp.asarray([1.0] * (n - 1) + [0.0])


def _assert_leaves_close(got, want, tol):
    """Every leaf within ``tol`` of the wanted leaf's largest magnitude."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=tol * float(jnp.abs(b).max() + 1e-6) + 1e-9, rtol=0,
            err_msg=jax.tree_util.keystr(path))


# --- the mixer against its equations as loops -------------------------------

H, G, E, ROPE, THETA = 4, 2, 8, 4, 5e6


def _mixer_params(seed=11):
    """A CCA sub-layer's seeded leaves, the key temperatures AWAY from their
    seed of 1 (a temperature of 1 could be dropped unseen)."""
    config = zaya_config()
    p = dict(ref.init(jax.random.key(seed), config)["params"]["layer_1"]["attn"])
    p["k_temp"] = jnp.asarray([1.3, 0.7], jnp.float32)
    return config, p


def cca_loops(x, p, drop=None):
    """ISSUE 39's eight steps, a position and a head at a time, in float64.
    ``drop`` leaves ONE mechanism out: ``tap0`` / ``tap1`` the depthwise /
    head-wise convolution's tap on the position before, ``mean`` the means,
    ``tau`` the key temperature, ``shift`` the second value head's shift."""
    x = np.asarray(x, np.float64)
    w = {k: np.asarray(v["kernel"] if isinstance(v, dict) else v, np.float64)
         for k, v in p.items()}
    b, t, _ = x.shape
    r = H // G
    out = np.zeros((b, t, x.shape[-1]))
    for s in range(b):
        qt = (x[s] @ w["q_proj"]).reshape(t, H, E)
        kt = (x[s] @ w["k_proj"]).reshape(t, G, E)
        vt = (x[s] @ w["v_proj"]).reshape(t, G, E)
        m_q = np.zeros((t, H, E))
        m_k = np.zeros((t, G, E))
        if drop != "mean":
            for i in range(H):
                m_q[:, i] = (qt[:, i] + kt[:, i // r]) / 2
            for g in range(G):
                m_k[:, g] = np.mean(m_q[:, g * r:(g + 1) * r], axis=1)
        z = np.concatenate([qt, kt], axis=1).reshape(t, (H + G) * E)
        u = np.zeros_like(z)
        for pos in range(t):
            u[pos] = w["conv0_kernel"][1] * z[pos] + w["conv0_bias"]
            if pos and drop != "tap0":
                u[pos] += w["conv0_kernel"][0] * z[pos - 1]
        u = u.reshape(t, H + G, E)
        y = np.zeros_like(u)
        for pos in range(t):
            for j in range(H + G):
                y[pos, j] = u[pos, j] @ w["conv1_kernel"][1, j] + w["conv1_bias"][j]
                if pos and drop != "tap1":
                    y[pos, j] += u[pos - 1, j] @ w["conv1_kernel"][0, j]
        q, k = y[:, :H] + m_q, y[:, H:] + m_k
        q = np.sqrt(E) * q / np.linalg.norm(q, axis=-1, keepdims=True)
        k = np.sqrt(E) * k / np.linalg.norm(k, axis=-1, keepdims=True)
        if drop != "tau":
            k = k * w["k_temp"][:, None]
        inv = THETA ** (-np.arange(0, ROPE, 2) / ROPE)
        for a in (q, k):
            for pos in range(t):
                for i, f in enumerate(inv):
                    c, sn = np.cos(pos * f), np.sin(pos * f)
                    even, odd = a[pos, :, 2 * i].copy(), a[pos, :, 2 * i + 1].copy()
                    a[pos, :, 2 * i] = even * c - odd * sn
                    a[pos, :, 2 * i + 1] = even * sn + odd * c
        if drop != "shift":
            vt[1:, 1], vt[0, 1] = vt[:-1, 1].copy(), 0.0
        o = np.zeros((t, H, E))
        for i in range(H):
            for pos in range(t):
                sc = q[pos, i] @ k[:pos + 1, i // r].T / np.sqrt(E)
                pr = np.exp(sc - sc.max())
                o[pos, i] = (pr / pr.sum()) @ vt[:pos + 1, i // r]
        out[s] = o.reshape(t, H * E) @ w["o_proj"]
    return out


def _program_mixer(p, x):
    mod = CompressedConvAttention(H, G, E, ROPE, THETA)
    with jax.default_matmul_precision("highest"):
        return mod.apply({"params": p}, x)


def test_mixer_is_the_eight_steps_written_as_loops():
    _config, p = _mixer_params()
    x = jax.random.normal(jax.random.key(12), (2, 12, 32), jnp.float32)
    got, want = _program_mixer(p, x), cca_loops(x, p)
    # float32 against float64 over sums of at most 32 terms
    np.testing.assert_allclose(got, want, atol=2e-6 * np.abs(want).max())
    mod = CompressedConvAttention(H, G, E, ROPE, THETA)
    assert (jax.tree.map(jnp.shape, mod.init(jax.random.key(0), x)["params"])
            == jax.tree.map(jnp.shape, p))


@pytest.mark.parametrize("drop", ["tap0", "tap1", "mean", "tau", "shift"])
def test_each_mechanism_of_the_mixing_is_seen(drop):
    """Leave ONE of them out of the loops and the module's output is no
    longer theirs: a program that dropped it would fail the test above."""
    _config, p = _mixer_params()
    x = jax.random.normal(jax.random.key(12), (2, 12, 32), jnp.float32)
    got, whole, without = _program_mixer(p, x), cca_loops(x, p), cca_loops(x, p, drop)
    scale = np.abs(whole).max()
    assert np.abs(got - whole).max() < 2e-6 * scale
    assert np.abs(got - without).max() > 1e-2 * scale, drop


def test_mixer_is_causal():
    """Position ``t``'s output does not move when a later position does,
    and the next one's does (the shift and both convolutions read back)."""
    _config, p = _mixer_params()
    x = jax.random.normal(jax.random.key(13), (1, 12, 32), jnp.float32)
    moved = x.at[:, 7].add(1.0)
    a, b = _program_mixer(p, x), _program_mixer(p, moved)
    np.testing.assert_array_equal(a[:, :7], b[:, :7])
    assert float(jnp.abs(a[:, 7] - b[:, 7]).max()) > 1e-4
    assert float(jnp.abs(a[:, 8] - b[:, 8]).max()) > 1e-4


def test_mixer_values_and_every_gradient_against_the_reference():
    config, p = _mixer_params()
    x = jax.random.normal(jax.random.key(14), (2, 32, 32), jnp.float32)
    c = jax.random.normal(jax.random.key(15), (2, 32, 32), jnp.float32)
    parts = ref._parts(config, "reference")
    mod = CompressedConvAttention(H, G, E, ROPE, THETA)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(
            lambda p, x: jnp.sum(mod.apply({"params": p}, x) * c),
            argnums=(0, 1))(p, x)
        want = jax.value_and_grad(
            lambda p, x: jnp.sum(parts.mixer(x, p) * c), argnums=(0, 1))(p, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    _assert_leaves_close(got[1], want[1], 1e-5)
    for name in ("conv0_kernel", "conv0_bias", "conv1_kernel", "conv1_bias",
                 "k_temp"):
        assert float(jnp.abs(got[1][0][name]).max()) > 0, name


# --- the router --------------------------------------------------------------

def _router_case(seed=21):
    config = zaya_config()
    v = ref.init(jax.random.key(seed), config)
    x = jax.random.normal(jax.random.key(seed + 1), (48, 32), jnp.float32)
    carry = jax.random.normal(jax.random.key(seed + 2), (48, 16), jnp.float32)
    return config, v["params"]["layer_1"]["mlp"]["router"], x, carry


def _router_loops(x, r, carry, eps=1e-5):
    """The router a token and a layer at a time, in float64."""
    from math import erf
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), r)
    gelu = np.vectorize(lambda a: 0.5 * a * (1 + erf(a / np.sqrt(2))))
    probs, state = [], []
    for n in range(x.shape[0]):
        s = np.asarray(x[n], np.float64) @ w["down_kernel"] + w["down_bias"]
        if carry is not None:
            s = s + w["gamma"] * np.asarray(carry[n], np.float64)
        y = s / np.sqrt(np.mean(s * s) + eps) * w["norm"]["scale"]
        y = gelu(y @ w["fc1_kernel"] + w["fc1_bias"])
        y = gelu(y @ w["fc2_kernel"] + w["fc2_bias"])
        logits = y @ w["out_kernel"]
        e = np.exp(logits - logits.max())
        probs.append(e / e.sum())
        state.append(s)
    return np.stack(probs), np.stack(state)


def test_router_is_its_layers_written_as_a_loop_and_carries():
    config, r, x, carry = _router_case()
    mod = MlpRouter(17, 16, 1e-5)
    with jax.default_matmul_precision("highest"):
        p, bias, s = mod.apply({"params": r}, x, carry)
    want_p, want_s = _router_loops(x, r, carry)
    np.testing.assert_allclose(p, want_p, atol=1e-7)
    np.testing.assert_allclose(s, want_s, atol=1e-6)
    np.testing.assert_array_equal(bias, r["bias"])
    assert p.shape == (48, 17) and s.shape == (48, 16)
    # the carry is seen: gamma = 0 is another router
    alone = mod.apply({"params": {**r, "gamma": jnp.zeros(())}}, x, carry)
    assert float(jnp.abs(alone[0] - p).max()) > 1e-4
    np.testing.assert_allclose(alone[0], _router_loops(x, {**r, "gamma": 0.0},
                                                       carry)[0], atol=1e-7)
    # the first layer of a stage has none, and no gamma
    first = {k: v for k, v in r.items() if k != "gamma"}
    p0, _, s0 = mod.apply({"params": first}, x)
    np.testing.assert_allclose(p0, _router_loops(x, first, None)[0], atol=1e-7)
    assert "gamma" not in mod.init(jax.random.key(0), x)["params"]
    assert "gamma" in mod.init(jax.random.key(0), x, carry)["params"]


def _sparse(held_first, held_count, params, x, carry=None, train=True):
    mod = SharedRoutedMoe(16, 1, 32, 0, 1.0, held_first, held_count,
                          jnp.float32, router_hidden=16, eps=1e-5)
    stats = {"expert_rows": jnp.zeros((held_count,)), "steps": jnp.zeros(()),
             "skipped": jnp.zeros(())}
    with jax.default_matmul_precision("highest"):
        (out, new_carry), new = mod.apply(
            {"params": params, "counters": stats}, x, train, carry,
            mutable=["counters", "intermediates"])
    return out, new_carry, new["counters"], new["intermediates"]["choices"][0]


def test_the_choice_that_is_no_expert_adds_nothing_and_is_counted():
    """A bias that sends every token to the seventeenth output: the sparse
    sub-layer is zero, ``skipped`` counts every token and no ``rows.*``
    does; a bias that sends all to expert 3 computes every token there."""
    config = zaya_config()
    p = jax.tree.map(lambda a: a, ref.init(jax.random.key(5), config)[
        "params"]["layer_0"]["mlp"])
    x = jax.random.normal(jax.random.key(6), (2, 16, 32), jnp.float32)
    none = {**p, "router": {**p["router"], "bias": jnp.zeros((17,)).at[16].set(9.0)}}
    out, carry, stats, idx = _sparse(0, 8, none, x)
    assert float(jnp.abs(out).max()) == 0.0
    assert float(stats["skipped"]) == 32.0 and float(stats["expert_rows"].sum()) == 0
    assert int(idx.min()) == 16 and carry.shape == (32, 16)
    counters = layer_counters({"counters": {"layer_0": {"mlp": stats}}})
    assert counters["skipped.layer_0"] == 32.0 and counters["steps.layer_0"] == 1.0
    assert sum(v for k, v in counters.items() if k.startswith("rows.")) == 0
    three = {**p, "router": {**p["router"], "bias": jnp.zeros((17,)).at[3].set(9.0)}}
    out, _, stats, _ = _sparse(0, 8, three, x)
    assert float(stats["skipped"]) == 0.0 and float(stats["expert_rows"][3]) == 32.0
    assert float(jnp.abs(out).max()) > 0
    # the other share holds neither choice
    for params in (none, three):
        out, _, stats, _ = _sparse(8, 8, params, x)
        assert float(jnp.abs(out).max()) == 0.0
        assert float(stats["expert_rows"].sum()) == 0


def _sparse_loss(rate, p, x, stats):
    mod = SharedRoutedMoe(16, 1, 32, 0, 1.0, 0, 8, jnp.float32,
                          router_hidden=16, eps=1e-5,
                          balance_rate=rate)

    def loss(p):
        (out, _), new = mod.apply({"params": p, "counters": stats}, x, True,
                                  mutable=["counters", "intermediates"])
        return jnp.sum(out ** 2), new["intermediates"]["choices"][0]

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(p)


def test_the_balancing_bias_is_moved_by_the_load_and_by_nothing_else():
    """``balance_rate``: the bias's gradient is ``rate * std(p) * (17 * load
    - 1)``, the excess held to +-1, of the step's own choices, whatever the
    loss; every other leaf's
    gradient, the output and the choices are what they are without it; SGD
    on that gradient evens a lopsided router's loads out on a fixed batch;
    and a bias that is left alone (rate 0) has no gradient at all."""
    config = zaya_config()
    p = ref.init(jax.random.key(5), config)["params"]["layer_0"]["mlp"]
    p = {**p, "router": {**p["router"],
                         "bias": jnp.zeros((17,)).at[2].set(0.02)}}
    x = jax.random.normal(jax.random.key(6), (2, 64, 32), jnp.float32)
    stats = {"expert_rows": jnp.zeros((8,)), "steps": jnp.zeros(()),
             "skipped": jnp.zeros(())}
    (l0, idx0), g0 = _sparse_loss(0.0, p, x, stats)
    (l1, idx1), g1 = _sparse_loss(4.0, p, x, stats)
    assert float(l0) == float(l1)
    np.testing.assert_array_equal(idx0, idx1)
    assert float(jnp.abs(g0["router"]["bias"]).max()) == 0.0
    load = np.bincount(np.asarray(idx1).reshape(-1), minlength=17) / 128
    assert load[2] > 0.9                                 # lopsided by the bias
    mod = MlpRouter(17, 16, 1e-5)
    with jax.default_matmul_precision("highest"):
        scores, _, _ = mod.apply({"params": p["router"]},
                                 x.reshape(-1, 32).astype(jnp.float32))
    want = 4.0 * float(jnp.std(scores)) * np.clip(17 * load - 1, -1, 1)
    np.testing.assert_allclose(g1["router"]["bias"], want, rtol=1e-5, atol=1e-8)
    g1["router"]["bias"] = g0["router"]["bias"]
    _assert_leaves_close(g1, g0, 0.0)
    # SGD on it, as a client runs it: the fullest choice's share falls
    tops = []
    for _ in range(24):
        (_, idx), g = _sparse_loss(4.0, p, x, stats)
        tops.append(np.bincount(np.asarray(idx).reshape(-1), minlength=17).max())
        p = {**p, "router": {**p["router"], "bias": p["router"]["bias"]
                             - 0.1 * g["router"]["bias"]}}
    assert tops[0] > 115 and max(tops[-4:]) <= 26, tops   # 7.5 is even


def test_two_shares_add_up_to_the_uncut_layer_with_nothing_counted_twice():
    """The share tied to the model: experts 0 - 7 on one chip and 8 - 15 on
    the other, the router computed alike on both, add up to what the layer
    that holds all 16 gives, which is the uncut reference's; every token is
    one share's row or skipped, once."""
    config = zaya_config(held_first=0, held_count=16)
    v = ref.init(jax.random.key(3), config)
    p = v["params"]["layer_1"]["mlp"]
    x = jax.random.normal(jax.random.key(4), (2, 32, 32), jnp.float32)
    carry = jax.random.normal(jax.random.key(8), (64, 16), jnp.float32)

    def share(first, count):
        params = {k: (a[first:first + count] if k in ("gate", "up", "down")
                      else a) for k, a in p.items()}
        return _sparse(first, count, params, x, carry)

    whole, lower, upper = share(0, 16), share(0, 8), share(8, 8)
    np.testing.assert_allclose(lower[0] + upper[0], whole[0], atol=3e-6)
    np.testing.assert_array_equal(lower[3], whole[3])
    np.testing.assert_array_equal(lower[1], upper[1])       # the same carry
    rows = float(lower[2]["expert_rows"].sum() + upper[2]["expert_rows"].sum())
    assert rows == float(whole[2]["expert_rows"].sum())
    assert float(lower[2]["skipped"]) == float(upper[2]["skipped"])
    assert rows + float(whole[2]["skipped"]) == 64.0
    assert float(lower[2]["expert_rows"].sum()) > 0 < float(upper[2]["expert_rows"].sum())
    # the uncut reference: the branch before its residual merge
    parts = ref._parts(config, "reference")
    layer = {"mlp": p, "mlp_merge": {
        "res_scale": jnp.zeros((32,)), "res_bias": jnp.zeros((32,)),
        "branch_scale": jnp.ones((32,)), "branch_bias": jnp.zeros((32,))}}
    with jax.default_matmul_precision("highest"):
        uncut, s, (ref_rows, skipped, idx, _) = parts.sparse(
            x, x, layer, carry)
    np.testing.assert_allclose(whole[0], uncut, atol=3e-6)
    np.testing.assert_allclose(whole[1], s, atol=1e-6)
    np.testing.assert_array_equal(whole[2]["expert_rows"], ref_rows)
    assert float(skipped) == float(whole[2]["skipped"])


# --- the whole model against the reference -----------------------------------

def _program_and_reference(config, remat=True, **over):
    v = jax.jit(lambda k: ref.init(k, config))(jax.random.key(7))
    b = create_model("zaya1_tiny", VOCAB, input_shape=(32,),
                     dtype=jnp.float32, remat=remat, **over)
    assert (jax.tree.map(jnp.shape, b.init(jax.random.key(0)))
            == jax.tree.map(jnp.shape, v))
    return v, b, ref._forward(config, "reference")


@pytest.mark.parametrize("over,remat", [
    ({}, True), ({"held_first": 8}, False),
    ({"held_first": 0, "held_count": 16, "layers": 2, "mixers": ["cca"] * 2},
     True)])
def test_logits_loss_and_gradients_match_the_reference(over, remat):
    """4 query heads over 2 key-value heads of 8, both convolutions over 2
    positions, a router of 16 hidden channels with its carry through the
    layers (and through ``nn.remat``), one choice of 16 experts or none, the
    scaled residuals and the tied head: logits, loss, every leaf's gradient
    and the counters, on either share and uncut."""
    config = zaya_config(**over)
    v, b, forward = _program_and_reference(config, remat, **over)
    x, y, m = batch()

    def program(p):
        logits, new = b.apply_train({**v, "params": p}, x, None)
        return nwp.loss(logits, y, m), (logits, new["counters"])

    def reference(p):
        logits, stats, _, pulls = forward(p, v["counters"], x)
        per = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                                   -1)[..., 0]
        w = jnp.broadcast_to(m[:, None], per.shape)
        return jnp.sum(per * w) / jnp.sum(w), (logits, stats, pulls)

    with jax.default_matmul_precision("highest"):
        (lp, (op, sp)), gp = jax.jit(jax.value_and_grad(
            program, has_aux=True))(v["params"])
        (lr, (orf, sr, pulls)), gr = jax.jit(jax.value_and_grad(
            reference, has_aux=True))(v["params"])
    np.testing.assert_allclose(op, orf, atol=5e-6)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    # the weight is p[c]: at ONE choice a token the router still learns, by
    # every leaf but the balancing bias, which selects: the loss has no
    # gradient for it (the reference's is zero), and the step is handed what
    # the LOAD asks in its place, which the reference writes out beside
    for name, pull in pulls.items():
        assert float(jnp.abs(gr[name]["mlp"]["router"]["bias"]).max()) == 0
        assert float(jnp.abs(pull).max()) > 0
        gr[name]["mlp"]["router"]["bias"] = pull
    _assert_leaves_close(gp, gr, 1e-5)
    for name, leaf in gp["layer_1"]["mlp"]["router"].items():
        assert max(float(jnp.abs(a).max()) for a in jax.tree.leaves(leaf)) > 0
    assert "gamma" not in gp["layer_0"]["mlp"]["router"]
    assert "lm_head" not in gp
    for name in sp:
        for key in ("expert_rows", "steps", "skipped"):
            np.testing.assert_array_equal(sp[name]["mlp"][key],
                                          sr[name]["mlp"][key])
        assert float(sp[name]["mlp"]["steps"]) == 1.0
    tokens = x.size
    assert all(float(s["mlp"]["expert_rows"].sum() + s["mlp"]["skipped"])
               <= tokens for s in sp.values())
    if config["model"]["held_count"] == 16:
        assert all(float(s["mlp"]["expert_rows"].sum() + s["mlp"]["skipped"])
                   == tokens for s in sp.values())


def test_the_carry_between_routers_and_the_mixing_show_in_the_logits():
    """What the two controls of the configuration's own leave out: ``gamma
    = 0`` and CCA run as plain grouped-query attention are other models."""
    config = zaya_config()
    v, b, _ = _program_and_reference(config)
    x, _y, _m = batch()
    apply = jax.jit(lambda v: b.apply_train(v, x, None)[0])
    logits = apply(v)
    p = jax.tree.map(lambda a: a, v["params"])
    for i in (1, 2):
        p[f"layer_{i}"]["mlp"]["router"]["gamma"] = jnp.zeros(())
    alone = apply({**v, "params": p})
    assert float(jnp.abs(alone - logits).max()) > 1e-4
    stated = jax.jit(ref._forward(config, "stated"))(v["params"], v["counters"], x)[0]
    assert float(jnp.abs(stated - logits).max()) < 0.05 * float(jnp.abs(logits).max())
    for variant in ("router_alone", "mix_plain"):
        other = jax.jit(ref._forward(config, variant))(
            v["params"], v["counters"], x)[0]
        assert float(jnp.abs(other - stated).max()) > 1e-3, variant
    np.testing.assert_allclose(
        jax.jit(ref._forward(config, "reference"))(p, v["counters"], x)[0],
        alone, atol=5e-6)


def test_the_tied_tables_gradient_has_both_parts():
    """Untie the head (a model whose ``lm_head`` is the table transposed
    gives the same logits): the tied table's gradient is the sum of what the
    gather and the head take there."""
    config = zaya_config()
    v, tied, _ = _program_and_reference(config)
    untied = create_model("zaya1_tiny", VOCAB, input_shape=(32,),
                          dtype=jnp.float32, tied_head=False)
    x, y, m = batch()
    p = v["params"]
    p2 = {**p, "lm_head": {"kernel": p["embed"].T}}

    def loss(bundle, variables):
        def f(params):
            logits, _ = bundle.apply_train({**variables, "params": params}, x, None)
            return nwp.loss(logits, y, m)
        return f

    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(loss(tied, v)))(p)
        g2 = jax.jit(jax.grad(loss(untied, {**v, "params": p2})))(p2)
    gather, head = g2["embed"], g2["lm_head"]["kernel"].T
    assert float(jnp.abs(gather).max()) > 0 and float(jnp.abs(head).max()) > 0
    np.testing.assert_allclose(g["embed"], gather + head,
                               atol=1e-6 * float(jnp.abs(head).max()))


def test_registered_defaults_are_the_published_widths():
    z = LATENT_MOE_PRESETS["zaya1_8b"]
    assert (z["dim"], z["heads"], z["kv_heads"], z["v_dim"], z["rope"],
            z["rope_theta"]) == (2048, 8, 2, 128, 64, 5e6)
    assert (z["n_routed"], z["top_k"], z["n_shared"], z["expert_width"],
            z["router_hidden"], z["held_count"]) == (16, 1, 0, 2048, 256, 8)
    assert z["cca_conv"] == [2, 2] and z["scaled_residual"]
    assert z["balance_rate"] == 130.0 and "skip_choice" not in z
    shapes = jax.eval_shape(create_model("zaya1_8b", 32784).init,
                            jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["params"]))
    assert n == 708_664_951
    attn = shapes["params"]["layer_0"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2048, 1024)      # half the width
    assert attn["conv1_kernel"].shape == (2, 10, 128, 128)
    assert shapes["params"]["layer_0"]["mlp"]["router"]["out_kernel"].shape == (256, 17)
    assert shapes["params"]["layer_0"]["mlp"]["gate"].shape == (8, 2048, 2048)


def test_zaya_round_counts_its_skipped_tokens_and_trains():
    """One packed FedAvg round of the tiny model: the loss is finite, the
    weights move, and the ``model`` counter group holds ``skipped.<layer>``
    beside ``rows.*`` and ``steps.*``, summed over the clients' steps."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import FedDataset
    from fedml_tpu.obs import model_counters

    rng = np.random.default_rng(0)
    k, n, t = 4, 4, 32
    ids = rng.integers(0, VOCAB, (k, n, t + 1)).astype(np.int32)
    ds = FedDataset(train_x=ids[..., :-1], train_y=ids[..., 1:],
                    train_mask=np.ones((k, n), np.float32),
                    train_counts=np.full(k, n), test_x=ids[0, :2, :-1],
                    test_y=ids[0, :2, 1:], test_mask=np.ones(2, np.float32),
                    class_num=VOCAB, task="nwp", name="tiny_zaya1")
    cfg = FedConfig(model="zaya1_tiny", dataset="tiny_zaya1", batch_size=2,
                    epochs=1, client_optimizer="sgd", lr=0.1, momentum=0.0,
                    dtype="float32", client_num_in_total=k,
                    client_num_per_round=2, pack_lanes=1, device_data="on",
                    comm_round=1, frequency_of_the_test=1_000_000,
                    async_rounds=True, seed=0)
    bundle = create_model("zaya1_tiny", VOCAB, input_shape=(t,),
                          dtype=jnp.float32)
    api = FedAvgAPI(ds, cfg, bundle)
    before = jax.device_get(api.variables["params"]["layer_1"]["mlp"]["router"])
    steps_a_client = n // 2         # each moves it by lr * rate * std(p) at most
    loss = float(jax.block_until_ready(api.run_round(1)))
    after = jax.device_get(api.variables["params"]["layer_1"]["mlp"]["router"])
    assert np.isfinite(loss)
    assert float(np.abs(after["fc1_kernel"] - before["fc1_kernel"]).max()) > 0
    # the balancing bias moves too, by the load and through the same step
    moved = after["bias"] - before["bias"]
    assert 0 < float(np.abs(moved).max()) < 0.1 * 4.0 * 0.25 * steps_a_client
    api.close()
    group = dict(model_counters().items())
    steps = 2 * (n // 2)                       # 2 clients of 2 steps
    for layer in ("layer_0", "layer_1", "layer_2"):
        assert group[f"steps.{layer}"] == steps
        rows = sum(v for key, v in group.items()
                   if key.startswith(f"rows.{layer}."))
        assert 0 <= group[f"skipped.{layer}"] <= steps * 2 * t
        assert rows + group[f"skipped.{layer}"] <= steps * 2 * t


# --- the presets the family had ------------------------------------------------

#: logits of each accepted tiny preset from ``init(key(0))`` on ``randint(
#: key(1))`` ids at the highest matmul precision, and a hash of its variable
#: tree's paths and shapes, as the PARENT of PR 39 read them on this
#: container (``sum``, ``sum |.|``, the first three): the router as a module,
#: the carry through the block loop and the shared way into the attention
#: kernels leave them where they were
PARENT = {
    "kanana2_tiny": (-12.533889770507812, 186.65805053710938,
                     [-0.22616584599018097, -0.06342216581106186,
                      -0.08431635797023773], "4c305cd446f9a439"),
    "ling3_tiny": (3.54532527923584, 356.4820556640625,
                   [-0.16103242337703705, -0.05267605930566788,
                    -0.16522690653800964], "97f5f1471dd747f9"),
    "laguna_tiny": (26.839027404785156, 370.9066467285156,
                    [-0.14704975485801697, 0.09074923396110535,
                     -0.1774010807275772], "f664e579476bde9f"),
    "granite4h_tiny": (3.3812942504882812, 51.2405891418457,
                       [-0.006914164405316114, -0.013858507387340069,
                        -0.02346670627593994], "8326d1cc809dabe8"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_accepted_presets_read_what_the_parent_read(name):
    import hashlib
    import json

    total, absolute, first, tree = PARENT[name]
    b = create_model(name, VOCAB, dtype=jnp.float32)
    v = b.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, b.input_shape[0]), 0, VOCAB)
    with jax.default_matmul_precision("highest"):
        logits, _ = b.apply_train(v, x, None)
    assert hashlib.sha256(json.dumps(
        jax.tree.map(lambda a: list(a.shape), v), sort_keys=True).encode()
    ).hexdigest()[:16] == tree
    # the same program on the same container: float32's last digits at most
    np.testing.assert_allclose(float(jnp.sum(jnp.abs(logits))), absolute,
                               rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(logits)), total,
                               atol=1e-6 * absolute)
    np.testing.assert_allclose(logits[0, 0, :3], first, atol=1e-6)
