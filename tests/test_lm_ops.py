"""The ops under the sparse-expert LM on the CPU: the grouped matmul against
a per-expert loop (empty and overloaded experts), the row moves' hand-written
gradients, and the attention op with a value size of its own against the
plain softmax (XLA path, and the Pallas kernels interpreted)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.attention import attention
from fedml_tpu.ops.grouped_matmul import (embed_rows, fan_out_rows,
                                          grouped_matmul, permute_rows)


# --- the grouped matmul -----------------------------------------------------

@pytest.mark.parametrize("sizes", [
    (4, 4, 4, 4), (0, 16, 0, 0), (16, 0, 0, 0), (0, 0, 0, 16), (3, 0, 9, 1),
    (0, 0, 0, 0), (1, 2, 3, 4)])
def test_grouped_matmul_matches_the_per_expert_loop(sizes):
    """Empty experts, one expert with every row, rows that belong to none:
    forward and both gradients."""
    k1, k2, k3 = jax.random.split(jax.random.key(sum(sizes) + len(sizes)), 3)
    x = jax.random.normal(k1, (16, 8), jnp.float32)
    w = jax.random.normal(k2, (4, 8, 6), jnp.float32)
    c = jax.random.normal(k3, (16, 6), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    def loop(x, w, gs):
        ends = jnp.cumsum(gs)
        row = jnp.arange(x.shape[0])[:, None]
        return sum(jnp.where((row >= ends[g] - gs[g]) & (row < ends[g]),
                             x @ w[g], 0) for g in range(w.shape[0]))

    def run(fn):
        return jax.value_and_grad(
            lambda x, w: jnp.sum(fn(x, w, gs) * c), argnums=(0, 1))(x, w)

    (a, (dxa, dwa)), (b, (dxb, dwb)) = run(grouped_matmul), run(loop)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dxa, dxb, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dwa, dwb, rtol=1e-5, atol=1e-5)
    out = np.asarray(grouped_matmul(x, w, gs))
    assert not out[sum(sizes):].any()           # rows of no group are zero
    start = 0
    for g, n in enumerate(sizes):
        np.testing.assert_allclose(out[start:start + n],
                                   np.asarray(x[start:start + n] @ w[g]),
                                   rtol=1e-5, atol=1e-5)
        start += n


@pytest.mark.parametrize("op,kept", [
    ("permute", 12), ("fan_out", 12), ("embed", 12),
    ("permute", 5), ("fan_out", 5), ("permute", 1), ("fan_out", 1)])
def test_row_moves_have_the_gathers_own_gradient(op, kept):
    """Each move's hand-written VJP (gathers only) is the transpose XLA
    would derive from the plain gather; with only the first ``kept`` sorted
    slots carried (the sparse layer's row capacity), a slot past them is a
    zero row going out and takes no cotangent coming back."""
    key = jax.random.key(11)
    perm = jax.random.permutation(key, 12)
    inv = jnp.argsort(perm)
    c = jax.random.normal(jax.random.key(12), (12, 5))
    if op == "permute":
        x = jax.random.normal(key, (kept, 5))
        ours = lambda x: permute_rows(x, perm, inv[:kept])
        plain = lambda x: jnp.concatenate(
            [x, jnp.zeros((1, 5))])[jnp.minimum(perm, kept)]
    elif op == "fan_out":
        x = jax.random.normal(key, (4, 5))
        c = c[:kept]
        ours = lambda x: fan_out_rows(x, perm[:kept], inv)
        plain = lambda x: x[perm[:kept] % 4]
    else:
        x = jax.random.normal(key, (7, 5))
        ids = jax.random.randint(key, (3, 4), 0, 7)
        ours = lambda x: embed_rows(x, ids).reshape(12, 5)
        plain = lambda x: x[ids].reshape(12, 5)
    for f in (ours, plain):
        np.testing.assert_allclose(f(x), plain(x))
    ga = jax.grad(lambda x: jnp.sum(ours(x) * c))(x)
    gb = jax.grad(lambda x: jnp.sum(plain(x) * c))(x)
    np.testing.assert_allclose(ga, gb, rtol=1e-6, atol=1e-6)


# --- attention with a value size of its own ---------------------------------

def _plain_attention(q, k, v):
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("impl,d,dv,t,block", [
    ("xla", 24, 16, 64, 32), ("pallas", 24, 16, 64, 32),
    ("xla", 192, 128, 128, 64), ("pallas", 192, 128, 128, 64),
    ("pallas", 192, 128, 128, 128), ("pallas", 64, 64, 96, 32)])
def test_attention_with_value_size_of_its_own(impl, d, dv, t, block):
    """192-wide queries and keys (padded to 256 for the kernels), 128-wide
    values: forward and all three gradients against the plain softmax; the
    Pallas path (interpreted here) runs its own backward kernels."""
    ks = jax.random.split(jax.random.key(d + dv + t), 4)
    q, k = (jax.random.normal(ks[i], (2, 2, t, d)) for i in (0, 1))
    v, c = (jax.random.normal(ks[i], (2, 2, t, dv)) for i in (2, 3))

    def run(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c),
                                  argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: attention(
        q, k, v, impl=impl, block_q=block, block_k=block,
        interpret=impl == "pallas"))
    want = run(_plain_attention)
    assert got[1][0].shape == q.shape and got[1][2].shape == v.shape
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
