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

def _per_expert_loop(x, w, gs):
    ends = jnp.cumsum(gs)
    row = jnp.arange(x.shape[0])[:, None]
    return sum(jnp.where((row >= ends[g] - gs[g]) & (row < ends[g]),
                         jnp.dot(x, w[g], precision="highest"), 0)
               for g in range(w.shape[0]))


#: (rows, K, N) of the interpreted kernels' cases, None for the plain path's
#: (16 float32 rows of 8 against 4 experts of 8 x 6), and the groups' sizes
GROUPED_CASES = [
    (None, (4, 4, 4, 4)), (None, (0, 16, 0, 0)), (None, (16, 0, 0, 0)),
    (None, (0, 0, 0, 16)), (None, (3, 0, 9, 1)), (None, (0, 0, 0, 0)),
    (None, (1, 2, 3, 4)),
    # bf16 rows, float32 weights, the kernels interpreted (row tile 128):
    ((512, 256, 256), (100, 37, 200, 150)),     # edges inside the tiles
    ((512, 256, 256), (0, 130, 120, 250)),      # an empty group first
    ((512, 256, 256), (130, 0, 120, 250)),      # ... in the middle
    ((512, 256, 256), (130, 120, 250, 0)),      # ... last
    ((512, 256, 256), (0, 0, 512, 0)),          # every row in ONE group
    ((512, 256, 256), (0, 481, 0, 0)),          # an edge off the row tile
    ((512, 256, 256), (128, 128, 128, 128)),    # edges ON the row tile
    ((512, 256, 256), (0, 0, 0, 0)),            # no row at all
    ((512, 256, 256), (60, 3, 1, 70)),          # most rows past the last
    ((1024, 128, 256), (700, 20, 300, 4)),      # the tile follows the shape
    ((256, 128, 384), (90, 100, 0, 50)),        # the relu2 pair: up,
    ((256, 384, 128), (90, 100, 0, 50)),        # and down (panels of 384)
]


@pytest.mark.parametrize("shape,sizes", GROUPED_CASES)
def test_grouped_matmul_matches_the_per_expert_loop(monkeypatch, shape, sizes):
    """Empty experts, one expert with every row, rows that belong to none:
    forward and both gradients. The plain path on float32 operands, and the
    kernels (interpreted) on bf16 rows and float32 weights against the loop
    on the weights rounded to bf16: the output and ``d_rows`` in the rows'
    dtype, ``d_w`` the float32 accumulator unrounded, rows past the last
    group zeros going out and without effect on ``d_w`` whatever they
    hold."""
    import fedml_tpu.ops.grouped_matmul as gm
    kernels = shape is not None
    (m, k, n), dtype = shape or (16, 8, 6), jnp.bfloat16 if kernels else jnp.float32
    k1, k2, k3 = jax.random.split(jax.random.key(sum(sizes) + len(sizes)), 3)
    x = jax.random.normal(k1, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (4, k, n), jnp.float32)
    c = jax.random.normal(k3, (m, n), jnp.float32).astype(dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(m) < sum(sizes))[:, None]
    if kernels:
        monkeypatch.setattr(gm, "_pick_impl", lambda impl: "pallas")
        assert gm._tiles(m, k, n, 4, 2, 4) == (256 if m == 1024 else 128)
        # what a row of no group holds must not matter
        x, c = jnp.where(live, x, jnp.nan), jnp.where(live, c, jnp.nan)

    def run(fn, x, w, c):
        return jax.value_and_grad(lambda x, w: jnp.sum(
            fn(x, w, gs).astype(jnp.float32) * c), argnums=(0, 1))(x, w)

    a, (dxa, dwa) = run(grouped_matmul, x, w, jnp.where(live, c, 0))
    assert dxa.dtype == dtype and dwa.dtype == jnp.float32
    # the loop in float32 on the values the product sees, d_w unrounded
    seen = (jnp.where(live, x, 0).astype(jnp.float32),
            w.astype(dtype).astype(jnp.float32),
            jnp.where(live, c, 0).astype(jnp.float32))
    b, (dxb, dwb) = run(lambda *o: _per_expert_loop(*o).astype(dtype), *seen)
    tol = dict(rtol=2e-2, atol=2e-2) if kernels else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a, b, rtol=tol["rtol"])
    np.testing.assert_allclose(dxa.astype(jnp.float32), dxb, **tol)
    np.testing.assert_allclose(dwa, dwb, rtol=1e-5, atol=1e-4)
    out = np.asarray(grouped_matmul(x, w, gs).astype(jnp.float32))
    assert not out[sum(sizes):].any()           # rows of no group are zero
    assert not np.asarray(dxa.astype(jnp.float32))[sum(sizes):].any()
    start = 0
    for g, rows in enumerate(sizes):
        np.testing.assert_allclose(
            out[start:start + rows],
            np.asarray(jnp.dot(seen[0][start:start + rows], seen[1][g],
                               precision="highest")), **tol)
        start += rows


def test_grouped_matmul_keeps_the_plain_path_where_the_kernels_do_not_tile(
        monkeypatch):
    """A width off the 128-lane tile, a capacity off the row tile and two
    matrices beyond the VMEM a call may ask for take ``lax.ragged_dot``; a
    batching ``vmap`` takes it at any shape, forward and backward."""
    import fedml_tpu.ops.grouped_matmul as gm
    assert gm._tiles(512, 256, 200, 4, 2, 4) is None
    assert gm._tiles(500, 256, 256, 4, 2, 4) is None
    assert gm._tiles(8192, 4096, 4096, 8, 2, 4) is None
    assert gm._tiles(8192, 2048, 2048, 8, 2, 4) == 256
    assert gm._tiles(6144, 2048, 768, 16, 2, 4) == 256
    assert gm._tiles(1024, 2048, 2048, 8, 2, 4) == 128
    assert [gm._panel(n) for n in (512, 768, 2048, 2688, 200)] == [
        512, 384, 512, 384, 0]
    monkeypatch.setattr(gm, "_pick_impl", lambda impl: "pallas")
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (2, 128, 128), jnp.float32)
    w = jax.random.normal(ks[1], (3, 128, 128), jnp.float32)
    gs = jnp.asarray([[50, 0, 60], [1, 100, 27]], jnp.int32)

    def loss(x, w, gs):
        return jnp.sum(grouped_matmul(x, w, gs) ** 2)

    got = jax.vmap(jax.value_and_grad(loss, argnums=(0, 1)),
                   in_axes=(0, None, 0))(x, w, gs)
    want = [jax.value_and_grad(lambda x, w: jnp.sum(_per_expert_loop(
        x, w, s) ** 2), argnums=(0, 1))(a, w) for a, s in zip(x, gs)]
    for i, (v, (dx, dw)) in enumerate(want):
        np.testing.assert_allclose(got[0][i], v, rtol=1e-4)
        np.testing.assert_allclose(got[1][0][i], dx, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(got[1][1][i], dw, rtol=1e-4, atol=1e-3)


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
    ("pallas", 192, 128, 128, 128), ("pallas", 64, 64, 96, 32),
    # the one backward call: dq summed over 3 and 4 key tiles, dk / dv over
    # as many query tiles; a tile that is the whole sequence
    ("pallas", 192, 128, 384, 128), ("pallas", 24, 16, 128, 32),
    ("pallas", 24, 16, 64, 64)])
def test_attention_with_value_size_of_its_own(impl, d, dv, t, block):
    """192-wide queries and keys (padded to 256 for the kernels), 128-wide
    values: forward and all three gradients against the plain softmax; the
    Pallas path (interpreted here) runs its own backward kernel, from
    which dq, dk and dv all leave."""
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


# --- a window, and query heads that share a key-value head ------------------

def _plain_band_attention(q, k, v, window=None):
    """Heads repeated by index, the masked softmax of the whole matrix."""
    group, t = q.shape[1] // k.shape[1], q.shape[2]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    behind = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = behind >= 0 if window is None else (behind >= 0) & (behind < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("impl,heads,kv,t,block,window", [
    ("xla", 6, 1, 64, 32, 16), ("xla", 8, 2, 64, 32, None),
    ("pallas", 6, 1, 64, 32, 16),       # a window under the tile, 6 to one
    ("pallas", 8, 1, 64, 32, 32),       # equal to the tile, 8 to one
    ("pallas", 4, 2, 128, 32, 48),      # over the tile
    ("pallas", 2, 2, 64, 16, 5),        # under the sub-tile, equal heads
    ("pallas", 2, 1, 96, 32, 100),      # wider than the sequence: causal
    ("pallas", 6, 2, 64, 32, None),     # grouped heads without a window
    ("pallas", 3, 1, 96, 32, 40), ("pallas", 2, 2, 128, 64, 16),
    # the one backward call over three and four tiles: every head of a group
    # keeps its own dq while the key tiles pass, dk / dv sum over the group
    ("pallas", 6, 1, 96, 32, None), ("pallas", 8, 1, 128, 32, None),
    ("pallas", 8, 1, 128, 32, 16),      # a window under the tile, 8 to one
    ("pallas", 6, 1, 96, 32, 32),       # equal to the tile, 6 to one
    ("pallas", 12, 2, 128, 32, 48),     # over the tile, 6 to one of two
    ("pallas", 8, 2, 64, 64, None),     # a tile that is the whole sequence
    ("pallas", 6, 1, 64, 64, 24)])      # and under a window
def test_windowed_and_grouped_attention_against_the_plain_softmax(
        monkeypatch, impl, heads, kv, t, block, window):
    """Forward and all three gradients (a key-value head's summed over the
    query heads that read it, inside the backward kernel); the kernels
    interpreted, in sub-tiles of 8 so that a tile has plain, crossed and
    dead sub-tiles on both edges of the band."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")
    monkeypatch.setattr(att, "_SUB_Q", 8)
    monkeypatch.setattr(att, "_SUB_K", 8)
    ks = jax.random.split(jax.random.key(heads + t + (window or 0)), 4)
    q = jax.random.normal(ks[0], (2, heads, t, 16))
    k = jax.random.normal(ks[1], (2, kv, t, 16))
    v = jax.random.normal(ks[2], (2, kv, t, 8))
    c = jax.random.normal(ks[3], (2, heads, t, 8))

    def run(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c),
                                  argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: attention(
        q, k, v, impl=impl, block_q=block, block_k=block, window=window,
        interpret=impl == "pallas"))
    want = run(lambda q, k, v: _plain_band_attention(q, k, v, window))
    assert got[1][1].shape == k.shape and got[1][2].shape == v.shape
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,d,dv,group,limit", [
    # the cells' three: kanana's and ling3's latent layers (32 equal heads,
    # keys 192 padded to 256), laguna's full (6 a group) and window (8)
    (4096, 256, 128, 1, 40 << 20), (4096, 128, 128, 6, 56 << 20),
    (4096, 128, 128, 8, 64 << 20),
    (8192, 128, 128, 8, 96 << 20),      # the last a group of 8 keeps whole
    (16384, 128, 128, 8, None), (8192, 256, 128, 8, None),
    (32768, 256, 128, 1, 96 << 20), (65536, 256, 128, 1, None),
    (64, 16, 8, 6, (32 << 20) + 64 * 16 * 6 * 8)])
def test_the_backwards_form_follows_from_shapes_alone(t, d, dv, group, limit):
    """One call while a key-value head's dq (float32 scratch and the bf16
    block it leaves through, held twice: 8 bytes an element) fits 100 MiB
    less 32 for the rest; two kernels beyond. By hand."""
    from fedml_tpu.ops.attention import _bwd_vmem

    assert _bwd_vmem(t, d, dv, group) == limit
    if limit is not None:
        assert limit == group * t * d * 8 + (32 << 20) <= 100 << 20
    # float32 operands leave through a float32 block: 12 bytes an element
    assert _bwd_vmem(t, d, dv, group, itemsize=4) == (
        None if group * t * d * 12 > 68 << 20
        else group * t * d * 12 + (32 << 20))


def test_a_dq_that_does_not_fit_takes_the_two_kernel_form(monkeypatch):
    """The fallback of the shape function alone: with no room for dq the
    same kernel gives dk / dv and the dq kernel runs beside it, to the same
    gradients (6 query heads to one key-value head, a window over the tile,
    four tiles)."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")
    ks = jax.random.split(jax.random.key(34), 4)
    q = jax.random.normal(ks[0], (1, 6, 128, 16))
    k = jax.random.normal(ks[1], (1, 1, 128, 16))
    v, c = jax.random.normal(ks[2], (1, 1, 128, 8)), jax.random.normal(
        ks[3], (1, 6, 128, 8))

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(attention(
            q, k, v, impl="pallas", block_q=32, block_k=32, window=40,
            interpret=True) * c), argnums=(0, 1, 2))(q, k, v)

    def kernels():
        # a function of its own each time: make_jaxpr keeps a trace too
        return str(jax.make_jaxpr(lambda: grads())()).count("pallas_call")

    one, n_one = grads(), kernels()
    monkeypatch.setattr(att, "_BWD_VMEM_LIMIT", att._BWD_VMEM_REST)
    att._flash_with_vjp.cache_clear()       # JAX keeps a traced backward
    try:
        two, n_two = grads(), kernels()
    finally:
        att._flash_with_vjp.cache_clear()
    assert (n_one, n_two) == (2, 3)
    for a, b in zip(one, two):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tq,bq,bk,sq,sk,window", [
    (64, 32, 32, 8, 8, 16), (64, 32, 32, 8, 8, 5), (128, 32, 32, 8, 8, 48),
    (128, 32, 64, 8, 16, 40), (128, 64, 32, 16, 8, 40), (64, 16, 16, 16, 16, 5),
    (64, 32, 32, 8, 8, 100), (4096, 1024, 1024, 256, 256, 512),
    (4096, 512, 512, 256, 256, 512), (4096, 128, 128, 128, 128, 512)])
@pytest.mark.parametrize("over_queries", [False, True])
def test_a_windows_sub_tiles_are_those_the_band_leaves(tq, bq, bk, sq, sk,
                                                       window, over_queries):
    """Numpy brute force over the band against the kernels' own spans: a
    sub-tile is computed, once, exactly if one of its elements is inside the
    band; a span masks every edge that cuts it; the offsets a kernel tests
    for hold every live tile's; the sweep has a step for every live tile and
    names only live blocks."""
    import importlib

    att = importlib.import_module("fedml_tpu.ops.attention")
    behind = np.arange(tq)[:, None] - np.arange(tq)[None, :]
    keep = (behind >= 0) & (behind < window)
    live = set(zip(*np.nonzero(
        keep.reshape(tq // sq, sq, tq // sk, sk).any(axis=(1, 3)))))
    tiling = att._Tiling(True, bq, bk, sq, sk, window)
    nq, nk = tq // bq, tq // bk
    seen, live_tiles = [], set()
    for qb in range(nq):
        for kb in range(nk):
            d = qb * bq - kb * bk
            for when, group in att._tile_spans(d, tiling, over_queries):
                for rows, keys, mask in group if when else ():
                    live_tiles.add((qb, kb))
                    assert d in att._band_offsets(tiling)
                    r = slice(qb * bq + rows.start, qb * bq + rows.stop)
                    c = slice(kb * bk + keys.start, kb * bk + keys.stop)
                    assert mask & att._MASK_DIAGONAL or (behind[r, c] >= 0).all()
                    assert mask & att._MASK_EDGE or (behind[r, c] < window).all()
                    seen += [(i, j) for i in range(r.start // sq, r.stop // sq)
                             for j in range(c.start // sk, c.stop // sk)]
    assert len(seen) == len(set(seen)) and set(seen) == live
    assert att.executed_score_share(tq, tq, bq, bk, sq, sk, window=window) \
        == len(live) * sq * sk / tq ** 2
    # the grid: each block that stays put sweeps its live blocks, first to
    # last, and a step past them names the last one and stands for no block
    mine, swept = (nk, nq) if over_queries else (nq, nk)
    sweep = att._band_sweep(tiling, mine, swept, over_queries)
    for i in range(mine):
        tiles = sorted(b if over_queries else a for a, b in
                       ((kb, qb) for qb, kb in live_tiles)
                       if (a if over_queries else b) == i)
        assert tiles == list(range(tiles[0], tiles[-1] + 1))
        assert len(tiles) <= sweep
        d = -i * bk if over_queries else i * bq
        for step in range(sweep):
            block, index = att._band_block(step, d, tiling, swept, over_queries)
            if step < len(tiles):
                assert int(block) == int(index) == tiles[step]
            else:
                assert int(block) == att._NO_BLOCK and int(index) == tiles[-1]


def test_executed_score_share_with_a_window_by_hand():
    """T 4,096 under a window of 512 in sub-tiles of 256: the first block of
    256 queries meets 1 sub-tile, the second 2, the other 14 three each (one
    cut by the far edge, one whole, one on the diagonal): 45 of 256. The
    band itself is 1,966,336 pairs, 11.7% of the matrix. Without a window
    the share is what it was."""
    from fedml_tpu.ops.attention import executed_score_share as share

    assert share(4096, 4096, 1024, 1024, window=512) == 45 / 256
    assert share(4096, 4096, 512, 512, window=512) == 45 / 256
    assert share(4096, 4096, 1024, 1024, 1024, 1024, window=512) == 7 / 16
    assert 512 * 513 // 2 + (4096 - 512) * 512 == 1_966_336
    assert share(4096, 4096, 1024, 1024) == 0.53125
    assert share(4096, 4096, 1024, 1024, window=None) == 0.53125
    # a window as wide as the sequence is the causal mask
    assert share(4096, 4096, 1024, 1024, window=4096) == 0.53125


def test_the_partial_form_refuses_a_window():
    """A ring step knows no window: it says so, and never attends to the
    whole prefix in silence. A window is causal."""
    from fedml_tpu.ops.attention import attention_block_partial

    q = jnp.zeros((1, 1, 16, 8))
    with pytest.raises(NotImplementedError, match="window"):
        attention_block_partial(q, q, q, window=8)
    with pytest.raises(ValueError, match="causal"):
        attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="query heads"):
        attention(jnp.zeros((1, 3, 16, 8)), jnp.zeros((1, 2, 16, 8)),
                  jnp.zeros((1, 2, 16, 8)))
