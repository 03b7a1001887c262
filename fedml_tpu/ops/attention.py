"""Blockwise (flash) attention for TPU.

No counterpart exists in the reference — its only sequence models are tiny
LSTMs (fedml_api/model/nlp/rnn.py:4-70, seq len 80/20). This op is what makes
long-context federated NLP first-class on TPU: one fused kernel streams K/V
blocks through VMEM with an online softmax, so attention never materializes
the [T, T] score matrix in HBM, and the partial-result form (unnormalized
output + running rowmax/rowsum) is exactly what ring attention over an 'sp'
mesh axis needs to merge chunks arriving over ICI
(:mod:`fedml_tpu.parallel.sequence`).

Shapes: ``q, k`` are ``[B, H, Tq, D]`` / ``[B, H, Tk, D]``, ``v`` is
``[B, H, Tk, Dv]`` with a value head size of its own (latent attention has
192-wide queries and keys and 128-wide values); the output is ``[B, H, Tq,
Dv]``. Causal masking uses GLOBAL positions ``q_offset + i >= k_offset + j``
so the same code serves single-device attention (offsets 0) and ring steps
(offsets are shard starts, traced scalars).

A query / key size over 128 that is not a multiple of the 128 lanes is
zero-PADDED to the next multiple before the kernels (192 -> 256): the MXU
contracts 128 at a time, so the padded pass costs what a 128 + 64 split
would, and zeros add nothing to a score. The kernels feed the MXU in the
inputs' own dtype (bf16 in, float32 accumulation; softmax in float32).

Two levels of tiling. The TILE (``block_q x block_k``, the BlockSpec) is
what one grid step fetches: it sets the HBM traffic and the step count, and
large is fast (1024 for the latent attention). The causal decision is taken
at the SUB-TILE (``_SUB_Q x _SUB_K``, 256 x 256, clamped to the tile): a tile
wholly under the diagonal is computed whole and without the mask, a tile
wholly above it is neither computed nor fetched (its grid step names the
block that is in VMEM already), and in a tile the diagonal crosses only the
sub-tiles with an unmasked element are computed (:func:`_tile_spans`;
:func:`executed_score_share` is the area that leaves: 53.1% of ``T x T`` at
T 4,096 where whole 1024-tiles execute 62.5%). A tile no larger than the
sub-tile (every caller at the default 128) is one sub-tile.

:func:`attention` on the Pallas path is the fused kernel forward AND
backward (a dq kernel and a dk/dv kernel that rebuild each score tile from
the saved log-sum-exp): no ``[Tq, Tk]`` tensor reaches HBM in either pass.
The partial form keeps its recompute-by-XLA backward (ring steps are short
chunks).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _pick_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


# ---------------------------------------------------------------------------
# XLA path: same online-softmax math in pure jnp. XLA fuses this into a few
# kernels; it is the CPU/GPU fallback and the reference for kernel tests.
# ---------------------------------------------------------------------------

def _xla_block_partial(q, k, v, q_offset, k_offset, causal, sm_scale):
    """One Q-shard vs one K/V-chunk -> unnormalized (o, m, l). [B,H,T,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        qpos = q_offset + jnp.arange(tq)
        kpos = k_offset + jnp.arange(tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,H,Tq]
    # rows that saw only masked keys: keep m at NEG_INF, contribute l=0
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                                   # [B,H,Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


# ---------------------------------------------------------------------------
# Pallas path
# ---------------------------------------------------------------------------

def _fit_block(block: int, t: int) -> int:
    b = min(block, t)
    while t % b:
        b //= 2
    return b


#: queries x keys of a compute sub-tile (clamped to the tile, halved until
#: it divides it). On the v5e at [2, 32, 4096], keys 192 -> 256, values 128,
#: tile 1024, the four kernel calls of a layer and step (forward twice, dk/dv,
#: dq) took 19.46 ms with the tile computed whole, 17.83 in sub-tiles of 256,
#: 18.03 of 512 and 112.4 of 128 (tools/attn_sweep.py; PERF.md, PR 27).
_SUB_Q, _SUB_K = 256, 256


class _Tiling(NamedTuple):
    """How the kernels cut the score area: the causal flag, the tile of a
    grid step and the sub-tile inside it (static; a jit key)."""

    causal: bool
    block_q: int
    block_k: int
    sub_q: int
    sub_k: int


def _tiling(causal, tq, tk, block_q, block_k) -> _Tiling:
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    return _Tiling(causal, bq, bk, _fit_block(_SUB_Q, bq),
                   _fit_block(_SUB_K, bk))


def _causal_ranges(d, sub_q, sub_k, n, over_queries=False):
    """The causal mask over one sweep of ``n`` blocks, ``q >= k`` kept.

    ``over_queries=False``: ONE block of ``sub_q`` queries against ``n``
    blocks of ``sub_k`` keys, the queries starting ``d`` positions after the
    first key. ``over_queries=True``: ``n`` blocks of ``sub_q`` queries, the
    first starting ``d`` after ONE block of ``sub_k`` keys (the same picture
    seen from its far corner: blocks counted from the end, queries and keys
    changing places). -> ``(plain, crossed)``, half-open index ranges:
    ``plain`` blocks lie wholly under the diagonal and need no mask,
    ``crossed`` ones hold it and do; a block in neither has no unmasked
    element and is not computed. ``d`` is an int or a traced int32: the
    grid's index maps, the kernels and :func:`executed_score_share` all
    decide here, at the scale of the sequence's tiles, of one tile, and of
    a tile's sub-tiles."""
    if over_queries:
        plain, crossed = _causal_ranges(n * sub_q - sub_k + d, sub_k, sub_q, n)
        return (n - plain[1], n), (n - crossed[1], n - crossed[0])

    def blocks(x):                    # clipped first: floor == truncation
        if isinstance(x, int):
            return min(max(x, 0), n * sub_k) // sub_k
        return jax.lax.div(jnp.clip(x, 0, n * sub_k), sub_k)

    n_plain = blocks(d + 1)
    return (0, n_plain), (n_plain, blocks(d + sub_q - 1 + sub_k))


def _tile_spans(d, tiling: _Tiling, over_queries=False):
    """What a kernel computes of ONE ``block_q x block_k`` tile whose first
    query lies ``d`` after its first key -> ``[(when, [(rows, keys, masked),
    ...])]``: groups of spans (static slices of the tile), each group under
    a condition (traced where ``d`` is) and run as one straight-line
    program. A tile wholly under the diagonal is one span without the mask.
    In a tile the diagonal crosses, each block of ``sub_q`` queries takes
    the keys up to its last live ``sub_k`` block in one span (forward and
    dq, which accumulate by query), or with ``over_queries`` each block of
    ``sub_k`` keys the queries from its first live ``sub_q`` block on
    (dk/dv, which accumulate by key): a sub-tile wholly above the diagonal
    is in no span. On the chip (PERF.md, PR 27) a loop over sub-tiles with
    traced bounds ran 1.1 to 2.6 times SLOWER than the tile computed whole,
    and each span under a condition of its own won a third of what the
    spans of ``d == 0``, known here, win as one group: that is the tile on
    the diagonal of every call without offsets or with offsets a multiple
    of the tile; any other crossed tile takes its spans one by one."""
    causal, block_q, block_k, sub_q, sub_k = tiling
    whole = slice(0, block_q), slice(0, block_k)
    if not causal:
        return [(True, [(*whole, False)])]
    (_, plain), (_, live) = _causal_ranges(d, block_q, block_k, 1)
    crossed = live - plain == 1
    groups = [(plain == 1, [(*whole, False)])]
    nsq, nsk = block_q // sub_q, block_k // sub_k
    if nsq == nsk == 1:               # the tile is one sub-tile
        return groups + [(crossed, [(*whole, True)])]

    def spans(d):
        """[(when, rows, keys)] of a crossed tile."""
        out = []
        if over_queries:
            for j in range(nsk):
                keys = slice(j * sub_k, (j + 1) * sub_k)
                _, (first, _) = _causal_ranges(d - keys.start, sub_q, sub_k,
                                               nsq, over_queries=True)
                out += [(first == i, slice(i * sub_q, block_q), keys)
                        for i in range(nsq)]
        else:
            for i in range(nsq):
                rows = slice(i * sub_q, (i + 1) * sub_q)
                _, (_, width) = _causal_ranges(d + rows.start, sub_q, sub_k,
                                               nsk)
                out += [(width == w, rows, slice(0, w * sub_k))
                        for w in range(1, nsk + 1)]
        return out

    groups.append((crossed & (d == 0), [(rows, keys, True)
                                        for when, rows, keys in spans(0)
                                        if when]))
    return groups + [(crossed & (d != 0) & when, [(rows, keys, True)])
                     for when, rows, keys in spans(d)]


def _last_live_key_block(kb, d, block_q, block_k, nk):
    """Index map of K/V under the causal mask: a dead grid step (key block
    ``kb`` wholly above the query block that starts ``d`` after key 0) names
    the last live block of its sweep, which is in VMEM already, so no DMA."""
    _, (_, live) = _causal_ranges(d, block_q, block_k, nk)
    return jnp.minimum(kb, jnp.maximum(live - 1, 0))


def _first_live_query_block(qb, d, block_q, block_k, nq):
    """The same for the dk/dv kernel's query-side inputs: its dead steps
    come first in the sweep and name the first live query block."""
    _, (live, _) = _causal_ranges(d, block_q, block_k, nq, over_queries=True)
    return jnp.maximum(qb, jnp.minimum(live, nq - 1))


def executed_score_share(tq: int, tk: int, block_q: int = 128,
                         block_k: int = 128, sub_q: int = _SUB_Q,
                         sub_k: int = _SUB_K, causal: bool = True,
                         q_offset: int = 0, k_offset: int = 0) -> float:
    """Share of the ``tq x tk`` score area the kernels compute, summed over
    their own spans (the causal mask itself needs just over a half): 0.625
    at T 4,096 with 1024-wide tiles computed whole, 0.53125 in 256-wide
    sub-tiles."""
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    tiling = _Tiling(causal, bq, bk, _fit_block(sub_q, bq),
                     _fit_block(sub_k, bk))
    area = 0
    for qb in range(tq // bq):
        for kb in range(tk // bk):
            spans = _tile_spans(q_offset + qb * bq - k_offset - kb * bk,
                                tiling)
            area += sum((rows.stop - rows.start) * (keys.stop - keys.start)
                        for when, group in spans if when
                        for rows, keys, _ in group)
    return area / (tq * tk)


def _pad_qk(q, k):
    """Zero-pad a query / key size over 128 to a multiple of the 128 lanes
    (192 -> 256): scores are unchanged, the MXU's passes are the same."""
    d = q.shape[-1]
    if d <= 128 or d % 128 == 0:
        return q, k
    pad = [(0, 0)] * 3 + [(0, -d % 128)]
    return jnp.pad(q, pad), jnp.pad(k, pad)


def _scores(q, kblk, q_start, k_start, *, masked, sm_scale):
    """float32 scores of one span, NEG_INF where ``masked`` and the key lies
    ahead of the query."""
    s = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if masked:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return s


def _run_spans(update, d, tiling, over_queries=False):
    """``update(rows, keys, masked)`` for each span of the tile that is due."""
    import jax.experimental.pallas as pl

    for when, spans in _tile_spans(d, tiling, over_queries):
        @pl.when(when)
        def _group(spans=spans):
            for span in spans:
                update(*span)


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, m_s, l_s, acc_s, *,
                  sm_scale: float, nk: int, tiling: _Tiling):
    """Grid point = (batch*heads, q_block, k_block) with the k dimension
    'arbitrary' (sequential): running rowmax/rowsum/accumulator live in
    VMEM scratch across the k sweep, so VMEM holds only one (bq, d) query
    tile and one (bk, d) K/V tile at a time — sequence length is bounded
    by HBM, not by VMEM (the previous full-K/V-resident block spec OOMed
    scoped vmem at T=8192). A tile wholly above the causal diagonal is in
    no span: scratch carries through unchanged."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qoff_ref[0] + qb * tiling.block_q
    k_start = koff_ref[0] + kb * tiling.block_k

    def update(rows, keys, masked):
        vblk = v_ref[0, keys, :]
        s = _scores(q_ref[0, rows, :], k_ref[0, keys, :],
                    q_start + rows.start, k_start + keys.start,
                    masked=masked, sm_scale=sm_scale)
        m_prev = m_s[rows, :1]                                # [rows, 1]
        l_prev = l_s[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:    # a row with no key yet: s - m_new == 0, p must not be 1
            p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[rows, :] = acc_s[rows, :] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[rows, :] = jnp.broadcast_to(m_new, (s.shape[0], 128))
        l_s[rows, :] = jnp.broadcast_to(l_new, (s.shape[0], 128))

    _run_spans(update, q_start - k_start, tiling)

    @pl.when(kb == nk - 1)
    def _emit():
        o_ref[0] = acc_s[...]
        # m/l are row-broadcast across the 128-lane dim of their outputs
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def _sweep_last():
    """Only the last grid dimension (the sweep) carries scratch state,
    re-initialized at its step 0: the two before it may split across
    Megacore cores."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _vmem_spec(block, index_map):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)


# Each kernel call is a jitted function of its own: a model traces one
# kernel per layer and pass, and an inner jit traces a kernel's many spans
# once for all its call sites (without it the LM cell's round program took
# 15.0 s to trace where its parent took 6.6: PERF.md, PR 27). The trace
# names a kernel's calls after its function.

@functools.partial(jax.jit, static_argnames=("tiling", "sm_scale", "interpret"))
def _flash_fwd(qoff, koff, q, k, v, *, tiling: _Tiling, sm_scale: float,
               interpret: bool):
    """``q, k [BH, T, D]``, ``v [BH, Tk, Dv]``, offsets ``int32[1]`` ->
    float32 ``(o [BH, Tq, Dv], m, l [BH, Tq, 128])``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), tk, dv = q.shape, k.shape[1], v.shape[2]
    causal, bq, bk = tiling[:3]
    nk = tk // bk

    def q_of(bh, qb, kb, qoff, koff):
        return bh, qb, 0

    def k_of(bh, qb, kb, qoff, koff):
        if causal:
            kb = _last_live_key_block(kb, qoff[0] + qb * bq - koff[0],
                                      bq, bk, nk)
        return bh, kb, 0

    return pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, nk=nk,
                          tiling=tiling),
        # the offsets are prefetched scalars, so that K/V's index map sees
        # them too (ring steps pass traced shard starts)
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, tq // bq, nk),
            in_specs=[_vmem_spec((1, bq, d), q_of),
                      _vmem_spec((1, bk, d), k_of),
                      _vmem_spec((1, bk, dv), k_of)],
            out_specs=[_vmem_spec((1, bq, dv), q_of),
                       _vmem_spec((1, bq, 128), q_of),
                       _vmem_spec((1, bq, 128), q_of)],
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running rowmax
                pltpu.VMEM((bq, 128), jnp.float32),   # running rowsum
                pltpu.VMEM((bq, dv), jnp.float32),    # unnormalized output
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32),
        ],
        compiler_params=_sweep_last(), interpret=interpret,
    )(qoff, koff, q, k, v)


def _pallas_block_partial(q, k, v, q_offset, k_offset, causal, sm_scale,
                          block_q: int, block_k: int, interpret: bool):
    q, k = _pad_qk(q, k)
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    o, m, l = _flash_fwd(
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        jnp.asarray(k_offset, jnp.int32).reshape(1),
        q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
        v.reshape(b * h, tk, dv),
        tiling=_tiling(causal, tq, tk, block_q, block_k), sm_scale=sm_scale,
        interpret=interpret)
    return (o.reshape(b, h, tq, dv),
            m[..., 0].reshape(b, h, tq),
            l[..., 0].reshape(b, h, tq))


# ---------------------------------------------------------------------------
# Fused backward (full attention, offsets 0): scores are rebuilt span by span
# from the saved log-sum-exp, so neither pass holds a [Tq, Tk] tensor.
# ---------------------------------------------------------------------------

def _bwd_span(q, kblk, vblk, do, lse, delta, q_start, k_start, *,
              masked, sm_scale):
    """-> (p, ds) of one span, float32."""
    s = _scores(q, kblk, q_start, k_start, masked=masked, sm_scale=sm_scale)
    p = jnp.exp(s - lse)                       # masked: exp(-1e30) == 0
    dp = jax.lax.dot_general(
        do, vblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, nq, tiling):
    """Grid (batch*heads, k_block, q_block), the q sweep sequential: one
    K/V tile stays put while the query tiles stream past it."""
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q_start, k_start = qb * tiling.block_q, kb * tiling.block_k

    def update(rows, keys, masked):
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        p, ds = _bwd_span(q, k_ref[0, keys, :], v_ref[0, keys, :], do,
                          lse_ref[0, rows, :][:, :1],
                          delta_ref[0, rows, :][:, :1],
                          q_start + rows.start, k_start + keys.start,
                          masked=masked, sm_scale=sm_scale)
        dv_s[keys, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_s[keys, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _run_spans(update, q_start - k_start, tiling, over_queries=True)

    @pl.when(qb == nq - 1)
    def _emit():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_s, *, sm_scale, nk, tiling):
    """Grid (batch*heads, q_block, k_block), the k sweep sequential."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q_start, k_start = qb * tiling.block_q, kb * tiling.block_k

    def update(rows, keys, masked):
        kblk = k_ref[0, keys, :]
        _, ds = _bwd_span(q_ref[0, rows, :], kblk, v_ref[0, keys, :],
                          do_ref[0, rows, :], lse_ref[0, rows, :][:, :1],
                          delta_ref[0, rows, :][:, :1],
                          q_start + rows.start, k_start + keys.start,
                          masked=masked, sm_scale=sm_scale)
        dq_s[rows, :] += jax.lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _run_spans(update, q_start - k_start, tiling)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_in_specs(tiling: _Tiling, d, dv, q_of, k_of):
    bq, bk = tiling.block_q, tiling.block_k
    return [_vmem_spec((1, bq, d), q_of), _vmem_spec((1, bk, d), k_of),
            _vmem_spec((1, bk, dv), k_of), _vmem_spec((1, bq, dv), q_of),
            _vmem_spec((1, bq, 128), q_of), _vmem_spec((1, bq, 128), q_of)]


@functools.partial(jax.jit, static_argnames=("tiling", "sm_scale", "interpret"))
def _flash_dkv(q, k, v, do, lse, delta, *, tiling: _Tiling, sm_scale: float,
               interpret: bool):
    """``[BH, T, .]`` operands, ``lse`` / ``delta`` row-broadcast over 128
    lanes -> ``(dk, dv)`` in the dtypes of ``k``, ``v``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), tk, dv = q.shape, k.shape[1], v.shape[2]
    causal, bq, bk = tiling[:3]
    nq = tq // bq

    def q_of(bh, kb, qb):
        if causal:
            qb = _first_live_query_block(qb, -kb * bk, bq, bk, nq)
        return bh, qb, 0

    def k_of(bh, kb, qb):
        return bh, kb, 0

    return pl.pallas_call(
        functools.partial(_flash_dkv_kernel, sm_scale=sm_scale, nq=nq,
                          tiling=tiling),
        grid=(bh, tk // bk, nq),
        in_specs=_bwd_in_specs(tiling, d, dv, q_of, k_of),
        out_specs=[_vmem_spec((1, bk, d), k_of), _vmem_spec((1, bk, dv), k_of)],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=_sweep_last(), interpret=interpret,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.jit, static_argnames=("tiling", "sm_scale", "interpret"))
def _flash_dq(q, k, v, do, lse, delta, *, tiling: _Tiling, sm_scale: float,
              interpret: bool):
    """The operands of :func:`_flash_dkv` -> ``dq`` in the dtype of ``q``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), tk, dv = q.shape, k.shape[1], v.shape[2]
    causal, bq, bk = tiling[:3]
    nk = tk // bk

    def q_of(bh, qb, kb):
        return bh, qb, 0

    def k_of(bh, qb, kb):
        if causal:
            kb = _last_live_key_block(kb, qb * bq, bq, bk, nk)
        return bh, kb, 0

    return pl.pallas_call(
        functools.partial(_flash_dq_kernel, sm_scale=sm_scale, nk=nk,
                          tiling=tiling),
        grid=(bh, tq // bq, nk),
        in_specs=_bwd_in_specs(tiling, d, dv, q_of, k_of),
        out_specs=[_vmem_spec((1, bq, d), q_of)],
        out_shape=[jax.ShapeDtypeStruct((bh, tq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_sweep_last(), interpret=interpret,
    )(q, k, v, do, lse, delta)[0]


def _pallas_flash_bwd(q, k, v, out, lse, do, causal, sm_scale,
                      block_q: int, block_k: int, interpret: bool):
    """q, k already padded. -> (dq, dk, dv) in the inputs' dtypes."""
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def lanes(a):           # [B,H,Tq] -> row-broadcast over the 128 lanes
        return jnp.broadcast_to(a.reshape(b * h, tq, 1), (b * h, tq, 128))

    args = (q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
            v.reshape(b * h, tk, dv), do.astype(q.dtype).reshape(b * h, tq, dv),
            lanes(lse), lanes(delta))
    common = dict(tiling=_tiling(causal, tq, tk, block_q, block_k),
                  sm_scale=sm_scale, interpret=interpret)
    dk, dvv = _flash_dkv(*args, **common)
    dq = _flash_dq(*args, **common)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dvv.reshape(b, h, tk, dv))


@functools.lru_cache(maxsize=None)
def _flash_with_vjp(causal: bool, sm_scale: float, block_q: int,
                    block_k: int, interpret: bool):
    """Full attention (offsets 0) on the Pallas path, kernels both ways.
    Saved for the backward: q, k, v, the output and the log-sum-exp."""

    def run(q, k, v):
        o, m, l = _pallas_block_partial(q, k, v, 0, 0, causal, sm_scale,
                                        block_q, block_k, interpret)
        den = jnp.where(l == 0.0, 1.0, l)
        return (o / den[..., None]).astype(q.dtype), m + jnp.log(den)

    @jax.custom_vjp
    def f(q, k, v):
        return run(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = run(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        d = q.shape[-1]
        qp, kp = _pad_qk(q, k)
        dq, dk, dv = _pallas_flash_bwd(qp, kp, v, out, lse, do, causal,
                                       sm_scale, block_q, block_k, interpret)
        return dq[..., :d], dk[..., :d], dv

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _partial_with_vjp(causal: bool, sm_scale: float, impl: str,
                      block_q: int, block_k: int, interpret: bool):
    """Partial-attention fn with a custom VJP: forward = fused pallas kernel
    (or the XLA block math), backward = recompute via the XLA math (the
    standard flash-attention trade: no [Tq, Tk] tensor saved in fwd; bwd
    rebuilds scores once). Offsets travel as float32 scalars so custom_vjp
    can hand back ordinary zero cotangents for them."""

    def run_fwd(q, k, v, qoff, koff):
        qi = qoff.astype(jnp.int32)
        ki = koff.astype(jnp.int32)
        if impl == "xla":
            return _xla_block_partial(q, k, v, qi, ki, causal, sm_scale)
        return _pallas_block_partial(q, k, v, qi, ki, causal, sm_scale,
                                     block_q, block_k, interpret)

    @jax.custom_vjp
    def f(q, k, v, qoff, koff):
        return run_fwd(q, k, v, qoff, koff)

    def fwd(q, k, v, qoff, koff):
        return f(q, k, v, qoff, koff), (q, k, v, qoff, koff)

    def bwd(res, ct):
        q, k, v, qoff, koff = res
        qi = qoff.astype(jnp.int32)
        ki = koff.astype(jnp.int32)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _xla_block_partial(q_, k_, v_, qi, ki,
                                                  causal, sm_scale),
            q, k, v)
        dq, dk, dv = vjp(ct)
        return dq, dk, dv, jnp.zeros_like(qoff), jnp.zeros_like(koff)

    f.defvjp(fwd, bwd)
    return f


def attention_block_partial(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    q_offset=0, k_offset=0, causal: bool = True,
    sm_scale: Optional[float] = None, impl: str = "auto",
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Attention of a Q shard against one K/V chunk -> partial result
    ``(o_unnormalized, rowmax m, rowsum l)``, each fp32. Merge partials from
    several chunks with :func:`merge_partials`, finish with
    :func:`normalize_partial`. Differentiable (custom VJP, recompute-style
    backward)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    impl = _pick_impl(impl)
    f = _partial_with_vjp(causal, float(sm_scale), impl, block_q, block_k,
                          interpret)
    return f(q, k, v, jnp.asarray(q_offset, jnp.float32),
             jnp.asarray(k_offset, jnp.float32))


def merge_partials(a, b):
    """Online-softmax merge of two partial results (associative)."""
    oa, ma, la = a
    ob, mb, lb = b
    m = jnp.maximum(ma, mb)
    wa = jnp.where(ma <= NEG_INF / 2, 0.0, jnp.exp(ma - m))
    wb = jnp.where(mb <= NEG_INF / 2, 0.0, jnp.exp(mb - m))
    return (oa * wa[..., None] + ob * wb[..., None], m, la * wa + lb * wb)


def normalize_partial(o, m, l, out_dtype=None):
    """Finish: divide the accumulated unnormalized output by the rowsum."""
    den = jnp.where(l == 0.0, 1.0, l)[..., None]
    out = o / den
    return out.astype(out_dtype) if out_dtype is not None else out


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, sm_scale: Optional[float] = None,
    impl: str = "auto", block_q: int = 128, block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Full fused attention, ``q, k [B, H, T, D]``, ``v [B, H, T, Dv]`` ->
    ``[B, H, T, Dv]`` (q.dtype). On the Pallas path forward and backward are
    kernels (:func:`_flash_with_vjp`); the XLA path is the plain block math
    and its autodiff."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if _pick_impl(impl) == "pallas":
        return _flash_with_vjp(causal, float(sm_scale), block_q, block_k,
                               interpret)(q, k, v)
    o, m, l = attention_block_partial(
        q, k, v, causal=causal, sm_scale=sm_scale, impl=impl,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return normalize_partial(o, m, l, out_dtype=q.dtype)
