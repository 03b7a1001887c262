"""ops/ kernels + sequence parallelism.

Parity ladder: naive softmax attention (textbook jnp) == xla blockwise
partials == pallas kernel (interpret mode on CPU) == ring attention over an
8-device shard_map — so the TPU kernel path and the sequence-parallel path
are both pinned to the same math the transformer trains with.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.attention import (
    attention,
    attention_block_partial,
    merge_partials,
    normalize_partial,
)
from fedml_tpu.ops.xent import masked_cross_entropy

# Every test of this file passes under jax 0.9.0 on CPU, in ~80 s. The
# Pallas kernels' forward parity tests (interpret mode, a few seconds) run
# in tier-1; the rest — XLA-only math, kernel offsets and grads,
# ring/Ulysses, whole transformers — is slow-marked for its seconds alone
# (the 870 s gate): run it when touching ops/ or parallel/sequence.py.
# tools/chip_kernels.py is the compiled counterpart on the chip.
slow = pytest.mark.slow


def naive_attention(q, k, v, causal=True):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(d)
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _qkv(b=2, h=2, t=64, d=32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
    return mk(), mk(), mk()


class TestAttention:
    @slow
    @pytest.mark.parametrize("causal", [True, False])
    def test_xla_matches_naive(self, causal):
        q, k, v = _qkv()
        out = attention(q, k, v, causal=causal, impl="xla")
        ref = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_pallas_interpret_matches_naive(self, causal):
        q, k, v = _qkv(t=128, d=64)
        out = attention(q, k, v, causal=causal, impl="pallas", interpret=True,
                        block_q=64, block_k=32)
        ref = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_pallas_registered_model_shapes(self):
        """The registered transformers ask the kernel for T=80 and T=20 at
        head_dim 32 — tiles that are the whole array, not (8, 128)
        multiples. Mosaic compiles both on the v5e (PR 21,
        tools/chip_kernels.py); this pins the wrapper's math there."""
        for t in (80, 20):
            q, k, v = _qkv(b=1, h=2, t=t, d=32)
            out = attention(q, k, v, causal=True, impl="pallas",
                            interpret=True)
            np.testing.assert_allclose(
                out, naive_attention(q, k, v), atol=1e-4)

    @slow
    def test_chunked_partials_merge_to_full(self):
        """Splitting K/V into chunks and merging partials == one-shot —
        the invariant ring attention relies on."""
        q, k, v = _qkv(t=64)
        n_chunks, tc = 4, 16
        acc = None
        for i in range(n_chunks):
            part = attention_block_partial(
                q, k[:, :, i * tc:(i + 1) * tc], v[:, :, i * tc:(i + 1) * tc],
                q_offset=0, k_offset=i * tc, causal=True, impl="xla")
            acc = part if acc is None else merge_partials(acc, part)
        out = normalize_partial(*acc)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    @slow
    def test_pallas_offsets_match_chunked_reference(self):
        """The kernel's q/k offsets (what ring attention feeds it) and its
        causal block-skip path: chunked pallas partials with nonzero
        k_offset must merge to the one-shot result, including a fully
        future (dead) chunk."""
        q, k, v = _qkv(t=64)
        n_chunks, tc = 4, 16
        acc = None
        for i in range(n_chunks):
            part = attention_block_partial(
                q, k[:, :, i * tc:(i + 1) * tc], v[:, :, i * tc:(i + 1) * tc],
                q_offset=0, k_offset=i * tc, causal=True, impl="pallas",
                interpret=True, block_q=32, block_k=8)
            acc = part if acc is None else merge_partials(acc, part)
        out = normalize_partial(*acc)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-4)

        # shifted query window: q rows 32..63 against the full K/V
        part = attention_block_partial(
            q[:, :, 32:], k, v, q_offset=32, k_offset=0, causal=True,
            impl="pallas", interpret=True, block_q=16, block_k=16)
        out2 = normalize_partial(*part)
        np.testing.assert_allclose(out2, ref[:, :, 32:], atol=1e-4)

    @slow
    def test_grad_flows(self):
        q, k, v = _qkv(t=32, d=16)

        def f(q):
            return jnp.sum(attention(q, k, v, impl="xla") ** 2)

        g = jax.grad(f)(q)
        assert np.all(np.isfinite(g))

    @slow
    def test_pallas_grad_matches_xla_grad(self):
        """The custom VJP (fwd pallas kernel, bwd XLA recompute) must agree
        with differentiating the XLA math directly."""
        q, k, v = _qkv(t=32, d=16, seed=7)

        def loss(impl, interpret):
            def f(args):
                q, k, v = args
                return jnp.sum(attention(q, k, v, impl=impl,
                                         interpret=interpret) ** 2)
            return f

        g_xla = jax.grad(loss("xla", False))((q, k, v))
        g_pal = jax.grad(loss("pallas", True))((q, k, v))
        for a, b in zip(g_xla, g_pal):
            np.testing.assert_allclose(a, b, atol=1e-4)


# the module (the package's attribute of that name is the function)
att = importlib.import_module("fedml_tpu.ops.attention")


@pytest.fixture()
def sub8(monkeypatch):
    """Compute sub-tiles of 8 x 8, so that interpret-mode sizes engage them
    (the chip's are 256 x 256 inside a 1024-wide tile)."""
    monkeypatch.setattr(att, "_SUB_Q", 8)
    monkeypatch.setattr(att, "_SUB_K", 8)


def _value_and_grads(fn, q, k, v, c):
    return jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c),
                              argnums=(0, 1, 2))(q, k, v)


class TestSubTiles:
    """Two-level tiling: the BlockSpec tile is 4 x 4 compute sub-tiles and
    the sequence 4 x 4 tiles, as the LM cell's 4,096 positions in 1024-wide
    tiles of 256-wide sub-tiles."""

    @pytest.mark.parametrize("causal,d,dv,block_q,block_k", [
        (True, 24, 16, 32, 32), (False, 24, 16, 32, 32),
        (True, 136, 16, 32, 32),        # keys padded 136 -> 256
        (True, 24, 16, 32, 64), (True, 24, 16, 64, 16)])
    def test_forward_and_gradients_match_xla(self, sub8, causal, d, dv,
                                             block_q, block_k):
        ks = jax.random.split(jax.random.key(d + block_k), 4)
        q, k = (jax.random.normal(ks[i], (2, 2, 128, d)) for i in (0, 1))
        v, c = (jax.random.normal(ks[i], (2, 2, 128, dv)) for i in (2, 3))
        got = _value_and_grads(lambda q, k, v: attention(
            q, k, v, causal=causal, impl="pallas", interpret=True,
            block_q=block_q, block_k=block_k), q, k, v, c)
        want = _value_and_grads(lambda q, k, v: attention(
            q, k, v, causal=causal, impl="xla"), q, k, v, c)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("q_offset,k_offset", [
        (13, 5), (5, 13), (37, 40), (64, 0), (0, 64), (0, 200)])
    def test_partial_with_a_ring_steps_offsets(self, sub8, q_offset, k_offset):
        """Shard starts that are no multiple of the sub-tile, a chunk wholly
        in the past (no mask anywhere) and one wholly in the future (rows
        that saw no key: m stays NEG_INF, l and o 0)."""
        q, k, v = _qkv(b=1, h=2, t=64, d=16, seed=q_offset + k_offset)
        kw = dict(q_offset=q_offset, k_offset=k_offset, causal=True)
        got = attention_block_partial(q, k, v, impl="pallas", interpret=True,
                                      block_q=32, block_k=32, **kw)
        want = attention_block_partial(q, k, v, impl="xla", **kw)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("tq,tk,bq,bk,sq,sk,q_offset,k_offset", [
        (4096, 4096, 1024, 1024, 256, 256, 0, 0),
        (4096, 4096, 1024, 1024, 128, 128, 0, 0),
        (4096, 4096, 1024, 1024, 512, 256, 0, 0),
        (128, 128, 32, 32, 8, 8, 0, 0), (128, 128, 32, 64, 8, 16, 0, 0),
        (64, 64, 32, 32, 8, 8, 13, 5), (64, 64, 32, 32, 8, 8, 5, 13),
        (64, 96, 32, 32, 16, 8, 37, 40), (64, 64, 32, 32, 8, 8, 0, 200),
        (64, 64, 16, 16, 16, 16, 3, 0)])
    @pytest.mark.parametrize("over_queries", [False, True])
    def test_visited_sub_tiles_are_those_the_mask_leaves(
            self, tq, tk, bq, bk, sq, sk, q_offset, k_offset, over_queries):
        """Numpy brute force over the mask against the kernels' own spans
        (forward and dq by query block, dk/dv by key block): a sub-tile is
        computed, once, exactly if one of its elements is unmasked; a span
        without the mask holds no masked element; the engagement number
        counts the same area."""
        keep = (q_offset + np.arange(tq)[:, None]
                >= k_offset + np.arange(tk)[None, :])
        tiles = keep.reshape(tq // sq, sq, tk // sk, sk)
        live = set(zip(*np.nonzero(tiles.any(axis=(1, 3)))))
        seen = []
        for qb in range(tq // bq):
            for kb in range(tk // bk):
                for when, group in att._tile_spans(
                        q_offset + qb * bq - k_offset - kb * bk,
                        att._Tiling(True, bq, bk, sq, sk), over_queries):
                    for rows, keys, masked in group if when else ():
                        r = slice(qb * bq + rows.start, qb * bq + rows.stop)
                        c = slice(kb * bk + keys.start, kb * bk + keys.stop)
                        assert masked or keep[r, c].all()
                        seen += [(i, j)
                                 for i in range(r.start // sq, r.stop // sq)
                                 for j in range(c.start // sk, c.stop // sk)]
        assert len(seen) == len(set(seen)) and set(seen) == live
        assert att.executed_score_share(
            tq, tk, bq, bk, sq, sk, True, q_offset, k_offset
        ) == len(live) * sq * sk / (tq * tk)

    def test_executed_score_share_of_the_lm_cell(self):
        share = att.executed_score_share
        assert share(4096, 4096, 1024, 1024, 256, 256) == 0.53125
        assert share(4096, 4096, 1024, 1024, 1024, 1024) == 0.625
        # a sub-tile follows the tile it is given, as the kernels' does
        assert share(4096, 4096, 128, 128, 256, 256) == share(
            4096, 4096, 128, 128, 128, 128) == 0.515625
        assert share(4096, 4096, 1024, 1024, causal=False) == 1.0

    @pytest.mark.parametrize("tq,tk,bq,bk,q_offset", [
        (128, 128, 32, 32, 0), (128, 128, 32, 64, 0), (128, 128, 64, 16, 0),
        (64, 128, 32, 32, 0), (128, 64, 32, 32, 0), (64, 64, 32, 32, 40),
        (64, 64, 32, 32, -200)])
    def test_a_dead_grid_step_names_the_nearest_live_block(self, tq, tk, bq,
                                                           bk, q_offset):
        """So that Pallas sees an unchanged block index and issues no DMA:
        the last live K/V block in the forward and dq sweeps (dead steps
        come last), the first live query block in dk/dv's (they come
        first); block 0 / the last block where the whole sweep is dead."""
        nq, nk = tq // bq, tk // bk
        live = np.array([[q_offset + qb * bq + bq - 1 >= kb * bk
                          for kb in range(nk)] for qb in range(nq)])
        for qb in range(nq):
            for kb in range(nk):
                want_k = kb if live[qb, kb] else max(
                    [j for j in range(nk) if live[qb, j]], default=0)
                assert int(att._last_live_key_block(
                    kb, q_offset + qb * bq, bq, bk, nk)) == want_k
                want_q = qb if live[qb, kb] else min(
                    [i for i in range(nq) if live[i, kb]], default=nq - 1)
                assert int(att._first_live_query_block(
                    qb, q_offset - kb * bk, bq, bk, nq)) == want_q

    @staticmethod
    def _kernel_primitives(fn, *args):
        """Primitive names inside every pallas_call of ``fn``'s jaxpr."""
        names = []

        def walk(jaxpr, inside):
            for eqn in jaxpr.eqns:
                if inside:
                    names.append(eqn.primitive.name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, inside or eqn.primitive.name == "pallas_call")

        walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
        return names

    @pytest.mark.parametrize("tile,sub,spans", [
        (16, None, 2), (64, None, 2), (128, None, 2), (32, 8, 21)])
    def test_a_tile_within_the_sub_tile_is_one_sub_tile(self, monkeypatch,
                                                        tile, sub, spans):
        """Every caller at the default tile of 128 and every smaller tile:
        the sub-tile chosen is the tile, and each kernel holds the tile
        twice, without the mask and with it, and no loop: 2 matmuls a span
        forward and 5 in the one backward call (s, dp, dv, dk, dq). Four
        sub-tiles a tile: the whole tile, the diagonal tile's 4 spans as one
        group, and in the forward, whose offsets may be anything, 4 x 4
        spans each under its condition."""
        if sub:
            monkeypatch.setattr(att, "_SUB_Q", sub)
            monkeypatch.setattr(att, "_SUB_K", sub)
        else:
            assert att._fit_block(att._SUB_Q, tile) == tile
            assert att._fit_block(att._SUB_K, tile) == tile
        q, k, v = _qkv(b=1, h=1, t=2 * tile, d=16)

        def grads(q, k, v):
            return jax.grad(lambda *a: jnp.sum(attention(
                *a, impl="pallas", interpret=True, block_q=tile,
                block_k=tile)), argnums=(0, 1, 2))(q, k, v)

        names = self._kernel_primitives(grads, q, k, v)
        # the backward knows its call has no offsets: of a crossed tile's
        # groups it builds the diagonal tile's alone (1 + 4 spans of 21)
        assert names.count("dot_general") == spans * 2 + min(spans, 5) * 5
        assert not {"while", "scan"} & set(names)


    def test_a_kernel_is_traced_once_for_all_its_call_sites(self, monkeypatch):
        """Each kernel call is an inner jit, so a model with one call a layer
        and pass traces the forward and the one backward kernel once (set-up
        time: the LM cell's round program holds 15 calls of many spans
        each)."""
        traced = []
        real = att._tile_spans
        monkeypatch.setattr(att, "_tile_spans", lambda d, tiling, over=False,
                            *more: (traced.append(over),
                                    real(d, tiling, over, *more))[1])
        q, k, v = _qkv(b=1, h=3, t=48, d=40)      # shapes no other test has

        def three_layers(q, k, v):
            for _ in range(3):
                q = attention(q, k, v, impl="pallas", interpret=True,
                              block_q=16, block_k=16)
            return jnp.sum(q)

        jax.make_jaxpr(jax.grad(three_layers, argnums=(0, 1, 2)))(q, k, v)
        assert sorted(traced) == [False, True]


class TestXent:
    def test_pallas_interpret_matches_xla(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 96, size=(64,)), jnp.int32)
        mask = jnp.asarray(rng.integers(0, 2, size=(64,)), jnp.float32)
        a = masked_cross_entropy(logits, labels, mask, impl="xla")
        b = masked_cross_entropy(logits, labels, mask, impl="pallas",
                                 interpret=True, block_n=16, block_v=32)
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_pallas_odd_vocab_pads_not_collapses(self):
        """Awkward V (e.g. 10004 = 4*41*61) must pad up to the block width,
        not halve the block down to a few lanes."""
        rng = np.random.default_rng(7)
        v = 1003  # prime-ish: no power-of-2 factor above 1
        logits = jnp.asarray(rng.normal(size=(8, v)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, size=(8,)), jnp.int32)
        a = masked_cross_entropy(logits, labels, impl="xla")
        b = masked_cross_entropy(logits, labels, impl="pallas",
                                 interpret=True, block_n=8, block_v=256)
        np.testing.assert_allclose(a, b, atol=1e-4)

    def test_pallas_large_vocab_shrinks_the_row_block(self):
        """The block is a whole padded vocabulary row: at V=50,304 the
        default 64 rows would need 25 MiB of scoped VMEM (the v5e allows
        16), so the wrapper halves the row block until the pair fits."""
        rng = np.random.default_rng(3)
        v = 50_304
        logits = jnp.asarray(rng.normal(size=(64, v)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, size=(64,)), jnp.int32)
        a = masked_cross_entropy(logits, labels, impl="xla")
        b = masked_cross_entropy(logits, labels, impl="pallas",
                                 interpret=True)
        np.testing.assert_allclose(a, b, atol=1e-4)

    @slow
    def test_grad_closed_form(self):
        """Custom VJP (softmax - onehot) == autodiff of log_softmax CE."""
        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 12, size=(16,)), jnp.int32)

        def f(impl, interpret):
            return lambda lg: jnp.sum(
                masked_cross_entropy(lg, labels, impl=impl, interpret=interpret))

        def ref(lg):
            logz = jax.nn.log_softmax(lg, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logz, labels[:, None], axis=-1))

        g_ref = jax.grad(ref)(logits)
        np.testing.assert_allclose(jax.grad(f("xla", False))(logits), g_ref, atol=1e-5)
        np.testing.assert_allclose(
            jax.grad(f("pallas", True))(logits), g_ref, atol=1e-5)

    @slow
    def test_seq_shape(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(2, 8, 10)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 10, size=(2, 8)), jnp.int32)
        out = masked_cross_entropy(logits, labels, impl="xla")
        assert out.shape == (2, 8)


@slow
class TestRingAttention:
    def test_ring_matches_single_device(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.mesh import client_mesh
        from fedml_tpu.parallel.sequence import ring_attention

        n = 8
        mesh = client_mesh(n, axis="sp")
        b, h, t, d = 2, 2, 64, 16  # global seq 64 -> 8 tokens/device
        q, k, v = _qkv(b=b, h=h, t=t, d=d, seed=3)

        def local(q, k, v):
            return ring_attention(q, k, v, axis_name="sp", axis_size=n,
                                  causal=True, impl="xla")

        ring = shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None, "sp"), P(None, None, "sp"), P(None, None, "sp")),
            out_specs=P(None, None, "sp"), check_vma=False,
        )
        out = jax.jit(ring)(q, k, v)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_ring_grads_match_single_device(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.mesh import client_mesh
        from fedml_tpu.parallel.sequence import ring_attention

        n = 4
        mesh = client_mesh(n, axis="sp")
        q, k, v = _qkv(b=1, h=1, t=32, d=8, seed=4)

        def ring_loss(q, k, v):
            def local(q, k, v):
                return ring_attention(q, k, v, axis_name="sp", axis_size=n,
                                      causal=True, impl="xla")
            out = shard_map(
                local, mesh=mesh,
                in_specs=(P(None, None, "sp"),) * 3,
                out_specs=P(None, None, "sp"), check_vma=False)(q, k, v)
            return jnp.sum(out ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.jit(jax.grad(ring_loss))(q, k, v)
        g_ref = jax.grad(ref_loss)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-4)


@slow
class TestTransformer:
    def test_forward_and_registry(self):
        from fedml_tpu.models import create_model

        bundle = create_model("transformer", 90, seq_len=16,
                              dim=32, heads=2, layers=2)
        rng = jax.random.key(0)
        variables = bundle.init(rng, batch_size=2)
        x = jnp.zeros((2, 16), jnp.int32)
        logits = bundle.apply_eval(variables, x)
        assert logits.shape == (2, 16, 90)
        assert np.all(np.isfinite(logits))

    def test_sp_training_step_matches_unsharded_loss(self):
        """One ('dp','sp') sequence-parallel train step: loss equals the
        unsharded computation and params actually move."""
        import optax

        from fedml_tpu.models.transformer import TransformerLM
        from fedml_tpu.parallel.sequence import make_sp_lm_train_step, sp_mesh
        from fedml_tpu.ops.xent import masked_cross_entropy

        vocab, b, t = 50, 4, 32
        mesh = sp_mesh(2, 4)
        mod_sp = TransformerLM(vocab_size=vocab, dim=32, heads=2, layers=2,
                               max_len=t, attn_impl="xla",
                               ring_axis="sp", ring_size=4)
        mod_ref = TransformerLM(vocab_size=vocab, dim=32, heads=2, layers=2,
                                max_len=t, attn_impl="xla")
        rngd = np.random.default_rng(5)
        x = jnp.asarray(rngd.integers(0, vocab, size=(b, t)), jnp.int32)
        y = jnp.asarray(rngd.integers(0, vocab, size=(b, t)), jnp.int32)
        mask = jnp.ones((b, t), jnp.float32)

        variables = mod_ref.init(jax.random.key(0), x[:1])
        tx = optax.sgd(0.1)
        opt_state = tx.init(variables["params"])

        # reference loss BEFORE the (donating) step consumes the buffers
        logits_ref = mod_ref.apply(variables, x)
        per = masked_cross_entropy(logits_ref, y, mask, impl="xla")
        ref_loss = float(jnp.sum(per) / jnp.sum(mask))
        params_before = jax.tree.map(np.asarray, variables["params"])

        step = make_sp_lm_train_step(mod_sp, tx, mesh, attn_impl="xla")
        new_vars, _, loss = step(dict(variables), opt_state, x, y, mask,
                                 jax.random.key(1))
        assert abs(float(loss) - ref_loss) < 1e-4
        moved = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - b))),
            new_vars["params"], params_before)
        assert max(jax.tree.leaves(moved)) > 0

    def test_remat_is_exact(self):
        """remat=True recomputes block activations on backward; loss and
        grads must be bit-identical to the non-remat module."""
        from fedml_tpu.models.transformer import TransformerLM

        kw = dict(vocab_size=31, dim=16, heads=2, layers=2, max_len=8,
                  attn_impl="xla")
        x = jnp.asarray(np.random.default_rng(0).integers(0, 31, (2, 8)),
                        jnp.int32)
        m0, m1 = TransformerLM(**kw), TransformerLM(remat=True, **kw)
        v = m0.init(jax.random.key(0), x)

        l0, g0 = jax.value_and_grad(
            lambda p: jnp.mean(m0.apply({"params": p}, x) ** 2))(v["params"])
        l1, g1 = jax.value_and_grad(
            lambda p: jnp.mean(m1.apply({"params": p}, x) ** 2))(v["params"])
        assert float(l0) == float(l1)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_remat_composes_with_sequence_parallel(self):
        """remat blocks under the ('dp','sp') ring-attention step."""
        import optax

        from fedml_tpu.models.transformer import TransformerLM
        from fedml_tpu.parallel.sequence import make_sp_lm_train_step, sp_mesh

        vocab, b, t = 40, 4, 16
        mesh = sp_mesh(2, 4)
        mod = TransformerLM(vocab_size=vocab, dim=16, heads=2, layers=2,
                            max_len=t, attn_impl="xla", ring_axis="sp",
                            ring_size=4, remat=True)
        init_mod = TransformerLM(vocab_size=vocab, dim=16, heads=2, layers=2,
                                 max_len=t)
        variables = init_mod.init(jax.random.key(0), jnp.zeros((1, t), jnp.int32))
        gen = np.random.default_rng(3)
        x = jnp.asarray(gen.integers(0, vocab, (b, t)), jnp.int32)
        y = jnp.asarray(gen.integers(0, vocab, (b, t)), jnp.int32)
        m = jnp.ones((b, t), jnp.float32)
        tx = optax.sgd(0.1)
        step = make_sp_lm_train_step(mod, tx, mesh, attn_impl="xla")
        _, _, loss = step(variables, tx.init(variables["params"]), x, y, m,
                          jax.random.key(1))
        assert np.isfinite(float(loss))

    def test_sp_training_step_grads_match_single_device(self):
        """The SP step's UPDATE must equal the single-device step's update
        (regression: a scalar psum inside the differentiated loss transposes
        to another psum and scales grads by the mesh size)."""
        import optax

        from fedml_tpu.models.transformer import TransformerLM
        from fedml_tpu.parallel.sequence import make_sp_lm_train_step, sp_mesh
        from fedml_tpu.ops.xent import masked_cross_entropy

        vocab, b, t = 50, 4, 32
        mesh = sp_mesh(2, 4)
        mod_sp = TransformerLM(vocab_size=vocab, dim=32, heads=2, layers=2,
                               max_len=t, attn_impl="xla",
                               ring_axis="sp", ring_size=4)
        mod_ref = TransformerLM(vocab_size=vocab, dim=32, heads=2, layers=2,
                                max_len=t, attn_impl="xla")
        rngd = np.random.default_rng(7)
        x = jnp.asarray(rngd.integers(0, vocab, size=(b, t)), jnp.int32)
        y = jnp.asarray(rngd.integers(0, vocab, size=(b, t)), jnp.int32)
        mask = jnp.asarray(rngd.random((b, t)) < 0.9, jnp.float32)

        variables = mod_ref.init(jax.random.key(0), x[:1])
        tx = optax.sgd(0.1)

        def ref_loss_fn(params):
            logits = mod_ref.apply({"params": params}, x)
            per = masked_cross_entropy(logits, y, mask, impl="xla")
            return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1.0)

        grads = jax.grad(ref_loss_fn)(variables["params"])
        upd, _ = tx.update(grads, tx.init(variables["params"]))
        ref_params = optax.apply_updates(variables["params"], upd)

        step = make_sp_lm_train_step(mod_sp, tx, mesh, attn_impl="xla")
        new_vars, _, _ = step(
            jax.tree.map(jnp.array, variables),
            tx.init(variables["params"]), x, y, mask, jax.random.key(1))
        jax.tree_util.tree_map(
            lambda a, r: np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5),
            new_vars["params"], ref_params)


@slow
class TestUlyssesAttention:
    """All-to-all (Ulysses) sequence parallelism must be exact — identical to
    single-device dense attention, like the ring (both are resharding
    strategies around the same math)."""

    def test_ulysses_matches_single_device(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.mesh import client_mesh
        from fedml_tpu.parallel.sequence import ulysses_attention

        n = 8
        mesh = client_mesh(n, axis="sp")
        b, h, t, d = 2, 8, 64, 16  # 8 heads over 8 devices, 8 tokens/device
        q, k, v = _qkv(b=b, h=h, t=t, d=d, seed=11)

        def local(q, k, v):
            return ulysses_attention(q, k, v, axis_name="sp", axis_size=n,
                                     causal=True, impl="xla")

        uly = shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None, "sp"),) * 3,
            out_specs=P(None, None, "sp"), check_vma=False,
        )
        out = jax.jit(uly)(q, k, v)
        ref = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    def test_ulysses_grads_match_single_device(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from fedml_tpu.parallel.mesh import client_mesh
        from fedml_tpu.parallel.sequence import ulysses_attention

        n = 4
        mesh = client_mesh(n, axis="sp")
        q, k, v = _qkv(b=1, h=4, t=32, d=8, seed=12)

        def uly_loss(q, k, v):
            def local(q, k, v):
                return ulysses_attention(q, k, v, axis_name="sp", axis_size=n,
                                         causal=True, impl="xla")
            out = shard_map(
                local, mesh=mesh,
                in_specs=(P(None, None, "sp"),) * 3,
                out_specs=P(None, None, "sp"), check_vma=False)(q, k, v)
            return jnp.sum(out ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

        g_uly = jax.jit(jax.grad(uly_loss))(q, k, v)
        g_ref = jax.grad(ref_loss)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_uly), np.asarray(g_ref), atol=1e-4)

    def test_ulysses_rejects_indivisible_heads(self):
        import pytest as _pytest

        from fedml_tpu.parallel.sequence import ulysses_attention

        q = jnp.zeros((1, 3, 8, 4), jnp.float32)
        with _pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, q, q, axis_name="sp", axis_size=4)

    def test_sp_lm_train_step_ulysses(self):
        """Full LM train step with sp_mode='ulysses' runs and matches the
        ring-mode step (same math, different resharding)."""
        import optax

        from fedml_tpu.models.transformer import TransformerLM
        from fedml_tpu.parallel.sequence import make_sp_lm_train_step, sp_mesh

        n_dp, n_sp = 2, 2
        mesh = sp_mesh(n_dp, n_sp)
        vocab, b, t = 16, 4, 16
        kw = dict(vocab_size=vocab, dim=16, heads=2, layers=1, max_len=t,
                  ring_axis="sp", ring_size=n_sp)
        rng = np.random.default_rng(13)
        x = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
        y = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
        m = jnp.ones((b, t), jnp.float32)
        init_mod = TransformerLM(vocab_size=vocab, dim=16, heads=2, layers=1, max_len=t)
        variables = init_mod.init(jax.random.key(0), jnp.zeros((1, t), jnp.int32))
        results = {}
        for mode in ("ring", "ulysses"):
            mod = TransformerLM(sp_mode=mode, **kw)
            tx = optax.sgd(0.1)
            # the step donates its state args — give each mode its own copy
            v_in = jax.tree.map(jnp.array, variables)
            opt = tx.init(v_in["params"])
            step = make_sp_lm_train_step(mod, tx, mesh)
            v2, _, loss = step(v_in, opt, x, y, m, jax.random.key(1))
            results[mode] = (jax.tree.map(np.asarray, v2), float(loss))
        assert np.isclose(results["ring"][1], results["ulysses"][1], rtol=1e-5)
        for a, b_ in zip(
            jax.tree.leaves(results["ring"][0]), jax.tree.leaves(results["ulysses"][0])
        ):
            np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-6)
