"""One chip's share of a layer is a share: at a small size (hidden 64, 8
state-space heads in 4 groups, 4 query heads over 2 key-value heads, 16
experts of which 4 a token in a latent of 16) the partial results that all
the shares give, with what every chip computes alike counted once, add up to
the uncut reference layer, for each of the three kinds of sub-layer
(``benchmarks/references/nemotron3_super_120b.py``); the same sum under ONE
norm over all the groups' channels does not; and the program's modules on a
share read what the reference reads on it. Then the two-matrix rung of the
sparse layer against ``jax.grad`` of a plain loop over the experts, at every
row capacity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import nemotron3_super_120b as ref
from fedml_tpu.models import moe
from fedml_tpu.models.moe import SharedRoutedMoe
from fedml_tpu.models.transformer import GroupedAttention, Mamba2Mixer

D, T = 64, 24
OPS = ref.ops_of("reference")
SSD = dict(heads=8, head_dim=8, state=16)
ATTN = dict(heads=4, kv_heads=2, head_dim=16)
E, K, LATENT, WIDTH, SHARED = 16, 4, 16, 24, 48


def _normal(key, *shape, std=0.3):
    return std * jax.random.normal(key, shape, jnp.float32)


@pytest.fixture(scope="module")
def x():
    return _normal(jax.random.key(1), 2, T, D, std=1.0)


@pytest.fixture(scope="module")
def mamba_layer():
    """A whole mixer's weights: ``W_in`` columns ``[z | x | B | C | dt]`` of
    8 heads in 4 groups."""
    k = iter(jax.random.split(jax.random.key(2), 8))
    inner, gn, h = 64, 4 * 16, 8
    return {"in_proj": {"kernel": _normal(next(k), D, 2 * inner + 2 * gn + h)},
            "conv_kernel": _normal(next(k), 4, inner + 2 * gn),
            "conv_bias": _normal(next(k), inner + 2 * gn),
            "A_log": jnp.log(jax.random.uniform(next(k), (h,), minval=1.0,
                                                maxval=4.0)),
            "dt_bias": _normal(next(k), h), "D": jnp.ones((h,)),
            "norm": {"scale": 1.0 + _normal(next(k), inner)},
            "out_proj": {"kernel": _normal(next(k), inner, D)}}


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
        np.abs(a - b).max(), np.abs(b).max())


def test_mixer_shares_add_up_and_one_joint_norm_does_not(x, mamba_layer):
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.mamba(x, mamba_layer, OPS, eps=1e-5, groups=4, **SSD)
        shares = [ref.mamba(
            x, ref.mamba_share(mamba_layer, groups=4, group=g, **SSD), OPS,
            heads=2, head_dim=8, state=16, eps=1e-5)[0] for g in range(4)]
        joint, _ = ref.mamba(x, mamba_layer, OPS, eps=1e-5, groups=4,
                             norm_groups=1, **SSD)
    _close(sum(shares), whole)
    # a norm over all four groups' channels is another function: a share
    # would need the other chips' sums of squares
    gap = float(jnp.abs(joint - whole).max() / jnp.abs(whole).max())
    assert gap > 1e-2, gap


def test_program_mixer_reads_the_reference_on_a_share(x, mamba_layer):
    """``Mamba2Mixer`` as it stands (one group, a chunked recurrence) on
    group 2's share against the reference's token-by-token one."""
    share = ref.mamba_share(mamba_layer, groups=4, group=2, **SSD)
    mod = Mamba2Mixer(2, 8, 16, 4, 8, 1e-5, jnp.float32)
    shapes = jax.eval_shape(mod.init, jax.random.key(0), x)["params"]
    assert jax.tree.map(lambda a: a.shape, share) == jax.tree.map(
        lambda a: a.shape, shapes)
    with jax.default_matmul_precision("highest"):
        got = mod.apply({"params": share, "counters": {
            "decay": jnp.zeros(()), "steps": jnp.zeros(())}}, x)
        want, _ = ref.mamba(x, share, OPS, heads=2, head_dim=8, state=16,
                            eps=1e-5)
    _close(got, want, 1e-4)


@pytest.fixture(scope="module")
def attn_layer():
    k = iter(jax.random.split(jax.random.key(3), 4))
    return {"q_proj": {"kernel": _normal(next(k), D, 64)},
            "k_proj": {"kernel": _normal(next(k), D, 32)},
            "v_proj": {"kernel": _normal(next(k), D, 32)},
            "o_proj": {"kernel": _normal(next(k), 64, D)}}


def test_attention_shares_add_up(x, attn_layer):
    """A key-value head with the two query heads it serves, twice."""
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(x, attn_layer, OPS, **ATTN)
        shares = [ref.attention(
            x, ref.attention_share(attn_layer, kv=slice(g, g + 1), **ATTN),
            OPS, heads=2, kv_heads=1, head_dim=16) for g in range(2)]
        # the program's module on the second share
        got = GroupedAttention(2, 1, 16, 0, dtype=jnp.float32).apply(
            {"params": ref.attention_share(attn_layer, kv=slice(1, 2), **ATTN)},
            x)
    _close(sum(shares), whole)
    _close(got, shares[1], 1e-4)
    assert float(jnp.abs(shares[0]).max()) > 0.1 * float(jnp.abs(whole).max())


@pytest.fixture(scope="module")
def sparse_layer():
    k = iter(jax.random.split(jax.random.key(4), 8))
    return {"router": _normal(next(k), D, E),
            "e_score_correction_bias": _normal(next(k), E, std=0.05),
            "latent_in": {"kernel": _normal(next(k), D, LATENT)},
            "latent_out": {"kernel": _normal(next(k), LATENT, D)},
            "shared": {"up": {"kernel": _normal(next(k), D, SHARED)},
                       "down": {"kernel": _normal(next(k), SHARED, D)}},
            "up": _normal(next(k), E, LATENT, WIDTH),
            "down": _normal(next(k), E, WIDTH, LATENT)}


def test_expert_shares_add_up_with_the_shared_parts_counted_once(
        x, sparse_layer):
    """Four shares of four experts: the router, ``W_1``, ``W_2``'s product
    of the SUM and the shared MLP are every chip's alike."""
    kw = dict(top_k=K, scaling=5.0)
    xf = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        whole, rows, idx, _ = ref.sparse_mlp(x, sparse_layer, OPS, first=0, **kw)
        parts = [ref.sparse_parts(xf, ref.experts_share(sparse_layer, f, 4),
                                  OPS, first=f, **kw) for f in (0, 4, 8, 12)]
        latent = sum(p[0] for p in parts)
        total = jnp.matmul(latent, sparse_layer["latent_out"]["kernel"]
                           ) + parts[0][1]
    _close(total.reshape(x.shape), whole)
    # every share routes alike and every pair is some share's
    for p in parts[1:]:
        np.testing.assert_array_equal(p[3], parts[0][3])
        np.testing.assert_array_equal(p[1], parts[0][1])
    assert float(sum(p[2].sum() for p in parts)) == xf.shape[0] * K
    assert float(rows.sum()) == xf.shape[0] * K
    # counting the shared MLP once a share instead would be far off
    assert float(jnp.abs(parts[0][1]).max()) > 0.05 * float(
        jnp.abs(whole).max())


@pytest.mark.parametrize("first", [0, 8])
def test_program_sparse_layer_reads_the_reference_on_a_share(
        x, sparse_layer, first):
    """``SharedRoutedMoe`` told its form, its latent and the range it holds,
    on a share's weights, with the ``live_units`` counter beside it."""
    share = ref.experts_share(sparse_layer, first, 4)
    mod = SharedRoutedMoe(E, K, WIDTH, 1, 5.0, first, 4, jnp.float32,
                          form="relu2", latent=LATENT, shared_width=SHARED)
    stats = {"expert_rows": jnp.zeros((4,)), "steps": jnp.zeros(()),
             "live_units": jnp.zeros(())}
    with jax.default_matmul_precision("highest"):
        (got, carry), new = mod.apply({"params": share, "counters": stats}, x,
                                      True, mutable=["counters"])
        want, rows, _, live = ref.sparse_mlp(x, share, OPS, top_k=K,
                                             first=first, scaling=5.0)
    assert carry is None
    _close(got, want, 1e-4)
    np.testing.assert_array_equal(new["counters"]["expert_rows"], rows)
    assert float(new["counters"]["steps"]) == 1.0
    np.testing.assert_allclose(new["counters"]["live_units"], live, rtol=1e-3)
    assert 0.2 < float(live) < 0.8


# --- the two-matrix rung ---------------------------------------------------------

N_TOK, HELD = 16, 4
RUNGS = moe.row_rungs(N_TOK * K)


def _operands(total: int):
    """A rung's operands with exactly ``total`` (token, choice) pairs on the
    four held experts of sixteen."""
    rng = np.random.default_rng(total)
    idx = rng.integers(HELD, E, (N_TOK, K))              # all absent
    flat = rng.choice(N_TOK * K, total, replace=False)
    idx.reshape(-1)[flat] = rng.integers(0, HELD, total)
    local = jnp.asarray(idx.T)
    mine = local < HELD
    key = jnp.where(mine, local, HELD).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, HELD + 1, dtype=jnp.int32), 0)[:HELD]
    k = iter(jax.random.split(jax.random.key(total), 4))
    return (_normal(next(k), N_TOK, LATENT, std=1.0), order, jnp.argsort(order),
            sizes, mine, jax.random.uniform(next(k), (N_TOK, K)) + 0.1,
            _normal(next(k), HELD, LATENT, WIDTH),
            _normal(next(k), HELD, WIDTH, LATENT)), jnp.asarray(idx)


def _plain(xf, weights, w_up, w_down, idx):
    """Every held expert on every token, weighted where it was chosen."""
    out = 0.0
    for e in range(HELD):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=1)
        h = jnp.square(jax.nn.relu(xf @ w_up[e]))
        out = out + w_e[:, None] * (h @ w_down[e])
    return out


@pytest.mark.parametrize("total,rung", [(0, 0), (5, 0), (8, 0), (9, 1),
                                        (16, 1), (17, 2), (33, 3), (64, 3)])
def test_two_matrix_rung_against_the_plain_loop(total, rung):
    """Output and the gradients of the rows, the weights and both matrices,
    through ``routed_rows``' switch and its rebuilt backward, at the rung
    the count picks; the live units it counts are the plain loop's."""
    assert RUNGS == (8, 16, 32, 64)
    (xf, order, inv, sizes, mine, weights, w_up, w_down), idx = _operands(total)
    assert int(sizes.sum()) == total
    assert int(moe._rung_index(RUNGS, sizes)) == rung
    ct = _normal(jax.random.key(99), N_TOK, LATENT, std=1.0)

    def ours(xf, weights, w_up, w_down):
        out, (live,) = moe.routed_rows(RUNGS, "relu2", xf, order, inv, sizes,
                                       mine, weights, w_up, w_down)
        return jnp.sum(out * ct), (out, live)

    def plain(xf, weights, w_up, w_down):
        out = _plain(xf, weights, w_up, w_down, idx)
        return jnp.sum(out * ct), out

    with jax.default_matmul_precision("highest"):
        (_, (out, live)), got = jax.value_and_grad(
            ours, argnums=(0, 1, 2, 3), has_aux=True)(xf, weights, w_up, w_down)
        (_, want_out), want = jax.value_and_grad(
            plain, argnums=(0, 1, 2, 3), has_aux=True)(xf, weights, w_up, w_down)
        units = sum(float(jnp.sum((xf @ w_up[e] > 0)
                                  * jnp.any(idx == e, axis=1)[:, None]
                                  * jnp.sum(idx == e, axis=1)[:, None]))
                    for e in range(HELD))
    assert float(live) == units
    scale = max(float(jnp.abs(want_out).max()), 1e-6)
    np.testing.assert_allclose(out, want_out, atol=1e-5 * scale, rtol=0)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * max(float(jnp.abs(b).max()), 1e-6), rtol=0)
    if total:
        assert float(jnp.abs(got[2]).max()) > 0
    # the same rung by itself: a swiglu layer's rung is untouched by the form
    alone, (alone_live,) = moe._rung(RUNGS[rung], "relu2")(
        xf, order, inv, sizes, mine, weights, w_up, w_down)
    np.testing.assert_allclose(alone, out, atol=1e-6 * scale, rtol=0)
    assert float(alone_live) == units
    assert moe.EXPERT_FORMS["swiglu"][0] == ("gate", "up", "down")
