"""Blockwise (flash) attention for TPU.

No counterpart exists in the reference — its only sequence models are tiny
LSTMs (fedml_api/model/nlp/rnn.py:4-70, seq len 80/20). This op is what makes
long-context federated NLP first-class on TPU: one fused kernel streams K/V
blocks through VMEM with an online softmax, so attention never materializes
the [T, T] score matrix in HBM, and the partial-result form (unnormalized
output + running rowmax/rowsum) is exactly what ring attention over an 'sp'
mesh axis needs to merge chunks arriving over ICI
(:mod:`fedml_tpu.parallel.sequence`).

Shapes: ``q`` is ``[B, H, Tq, D]``, ``k`` ``[B, G, Tk, D]`` and ``v`` ``[B,
G, Tk, Dv]`` with a value head size of its own (latent attention has
192-wide queries and keys and 128-wide values); the output is ``[B, H, Tq,
Dv]``. ``H / G`` consecutive query heads read one key-value head (grouped
queries; ``G == H`` is every head its own): the kernels' index maps name the
shared head, nothing is repeated in HBM, and the backward kernel sums a
key-value head's gradient over its group in VMEM. Causal masking uses GLOBAL
positions ``q_offset + i >= k_offset + j`` so the same code serves
single-device attention (offsets 0) and ring steps (offsets are shard
starts, traced scalars).

``window``: a query sees the ``window`` keys up to and including its own
(``0 <= i - j < window``), in :func:`attention` alone (the partial form
refuses one). The band is narrow, so everything about it is static: the few
offsets at which a tile meets the band, each with its spans
(:func:`_band_spans`: a sub-tile outside the band on EITHER side is in no
span, a span masks the edges that cut it), and a sweep that starts at a
block's first live tile and is as long as the most live tiles any block
meets (:func:`_band_sweep`: 2 steps a block for a band of 512 in tiles of
1024, where the causal sweep takes 4), so neither side's dead tiles are
fetched or computed.

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
backward: ONE backward call rebuilds each live score tile once from the
saved log-sum-exp, and dq, dk and dv all leave it (a key tile stays put, dk
and dv leave with it, a key-value head's whole dq waits in VMEM for the
other key tiles: :func:`_bwd_vmem`; a sequence too long for that takes a
dk/dv and a dq kernel, each rebuilding the tile): no ``[Tq, Tk]`` tensor
reaches HBM in either pass. The partial form keeps its recompute-by-XLA
backward (ring steps are short chunks).
"""

from __future__ import annotations

import functools
import math
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


def _xla_attention(q, k, v, causal, window, sm_scale):
    """Full attention under a window and / or with fewer key-value heads
    than query heads, plainly: heads repeated by index, the masked softmax
    of the whole score matrix, JAX's own backward."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        behind = jnp.arange(q.shape[2])[:, None] - jnp.arange(k.shape[2])
        keep = behind >= 0
        if window is not None:
            keep &= behind < window
        s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


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
    #: keys a query sees, itself included (``0 <= q - k < window``); None: all
    window: Optional[int] = None


def _tiling(causal, tq, tk, block_q, block_k, window=None) -> _Tiling:
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    return _Tiling(causal, bq, bk, _fit_block(_SUB_Q, bq),
                   _fit_block(_SUB_K, bk), window)


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


def _band_ranges(d, sub_q, sub_k, n, window, over_queries=False):
    """:func:`_causal_ranges` under a window, ``0 <= q - k < window`` kept:
    the same sweep of ``n`` blocks -> ``(live, plain)``, half-open index
    ranges. ``live`` blocks hold an unmasked element: the sweep is dead
    before them (behind the window) and after them (ahead of the queries),
    and neither side is fetched or computed. ``plain`` blocks lie wholly
    inside the band (possibly none: ``plain[0] >= plain[1]``); a live block
    before ``plain[0]`` is cut by the window's far edge and one from
    ``plain[1]`` on by the diagonal (``over_queries``: the diagonal cuts the
    blocks before ``plain[0]``, the far edge those from ``plain[1]`` on)."""
    if over_queries:
        live, plain = _band_ranges(n * sub_q - sub_k + d, sub_k, sub_q, n,
                                   window)
        return (n - live[1], n - live[0]), (n - plain[1], n - plain[0])

    def blocks(x):
        if isinstance(x, int):
            return min(max(x, 0), n * sub_k) // sub_k
        return jax.lax.div(jnp.clip(x, 0, n * sub_k), sub_k)

    return ((blocks(d + 1 - window), blocks(d + sub_q - 1 + sub_k)),
            (blocks(d + sub_q - 1 - window + sub_k), blocks(d + 1)))


#: what a span masks: the diagonal (a key ahead of its query), the window's
#: far edge (a key ``window`` or more behind it), or both. ``True`` is the
#: diagonal alone, which is all a call without a window knows.
_MASK_DIAGONAL, _MASK_EDGE = 1, 2


def _band_spans(d: int, tiling: _Tiling, over_queries=False):
    """The spans ``[(rows, keys, mask), ...]`` of ONE tile under a window,
    for a static ``d``: each block of ``sub_q`` queries takes its live
    ``sub_k`` blocks, first to last, in one span (``over_queries``: each
    block of keys its live blocks of queries), masked by the edges that cut
    it; a tile wholly inside the band is one span without a mask, a tile
    outside it has none."""
    _, block_q, block_k, sub_q, sub_k, window = tiling
    nsq, nsk = block_q // sub_q, block_k // sub_k
    out = []
    for i in range(nsk if over_queries else nsq):
        if over_queries:
            keys = slice(i * sub_k, (i + 1) * sub_k)
            (lo, hi), (diag, edge) = _band_ranges(
                d - keys.start, sub_q, sub_k, nsq, window, over_queries=True)
            rows = slice(lo * sub_q, hi * sub_q)
            mask = (lo < diag) * _MASK_DIAGONAL + (hi > edge) * _MASK_EDGE
        else:
            rows = slice(i * sub_q, (i + 1) * sub_q)
            (lo, hi), (edge, diag) = _band_ranges(
                d + rows.start, sub_q, sub_k, nsk, window)
            keys = slice(lo * sub_k, hi * sub_k)
            mask = (hi > diag) * _MASK_DIAGONAL + (lo < edge) * _MASK_EDGE
        if lo < hi:
            out.append((rows, keys, mask))
    whole = slice(0, block_q), slice(0, block_k)
    swept = nsk if over_queries else nsq
    if len(out) == swept and all(
            mask == 0 and (rows if over_queries else keys)
            == whole[not over_queries] for rows, keys, mask in out):
        return [(*whole, 0)]
    return out


def _band_offsets(tiling: _Tiling):
    """Every ``d`` (first query less first key) at which a tile of a call
    WITHOUT offsets holds a live element under the window: multiples of the
    tiles' common divisor, so each is static and so are its spans."""
    step = math.gcd(tiling.block_q, tiling.block_k)
    first = -((tiling.block_q - 1) // step) * step
    return range(first, tiling.window + tiling.block_k - 1, step)


def _tile_spans(d, tiling: _Tiling, over_queries=False, no_offsets=False):
    """What a kernel computes of ONE ``block_q x block_k`` tile whose first
    query lies ``d`` after its first key -> ``[(when, [(rows, keys, masked),
    ...])]``: groups of spans (static slices of the tile), each group under
    a condition (traced where ``d`` is) and run as one straight-line
    program. A tile wholly under the diagonal is one span without the mask.
    In a tile the diagonal crosses, each block of ``sub_q`` queries takes
    the keys up to its last live ``sub_k`` block in one span (forward and
    the dq kernel, whose key tiles stream), or with ``over_queries`` each
    block of ``sub_k`` keys the queries from its first live ``sub_q`` block
    on (the backward, whose query tiles do): a sub-tile wholly above the
    diagonal is in no span. On the chip (PERF.md, PR 27) a loop over
    sub-tiles with traced bounds ran 1.1 to 2.6 times SLOWER than the tile
    computed whole, and each span under a condition of its own won a third
    of what the spans of ``d == 0``, known here, win as one group: that is
    the tile on the diagonal of every call without offsets or with offsets
    a multiple of the tile; any other crossed tile takes its spans one by
    one. ``no_offsets``: the kernel knows its call has none (the backward),
    so with equal tiles ``d`` is a multiple of the tile, no other tile is
    crossed, and the one-by-one groups are not built at all: 5 span bodies
    in the kernel's program where 21 stood (the one backward call at
    256-wide keys read 27.1 ms with them and 6.5 without: PERF.md, PR
    34)."""
    causal, block_q, block_k, sub_q, sub_k, window = tiling
    whole = slice(0, block_q), slice(0, block_k)
    if not causal:
        return [(True, [(*whole, False)])]
    if window is not None:
        # a band is narrow: the few offsets at which a tile meets it are
        # known here, each with static spans under one condition
        offsets = [d] if isinstance(d, int) else _band_offsets(tiling)
        return [(d == at, spans) for at in offsets
                if (spans := _band_spans(at, tiling, over_queries))]
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
    if no_offsets and block_q == block_k:
        return groups
    return groups + [(crossed & (d != 0) & when, [(rows, keys, True)])
                     for when, rows, keys in spans(d)]


def _last_live_key_block(kb, d, block_q, block_k, nk):
    """Index map of K/V under the causal mask: a dead grid step (key block
    ``kb`` wholly above the query block that starts ``d`` after key 0) names
    the last live block of its sweep, which is in VMEM already, so no DMA."""
    _, (_, live) = _causal_ranges(d, block_q, block_k, nk)
    return jnp.minimum(kb, jnp.maximum(live - 1, 0))


def _first_live_query_block(qb, d, block_q, block_k, nq):
    """The same for the backward kernel's query-side inputs: its dead steps
    come first in the sweep and name the first live query block."""
    _, (live, _) = _causal_ranges(d, block_q, block_k, nq, over_queries=True)
    return jnp.maximum(qb, jnp.minimum(live, nq - 1))


def _band_sweep(tiling: _Tiling, n_mine: int, n_swept: int,
                over_queries=False) -> int:
    """Grid steps of one sweep under a window (a call without offsets): the
    most live blocks any of the ``n_mine`` blocks that stay put meets among
    the ``n_swept`` that stream past. The sweep starts at a block's first
    live one (:func:`_band_block`), so a band of 512 at T 4,096 in tiles of
    1024 takes 2 steps a block, not 4."""
    _, bq, bk, _, _, window = tiling
    spans = [_band_ranges(-i * bk if over_queries else i * bq, bq, bk,
                          n_swept, window, over_queries)[0]
             for i in range(n_mine)]
    return max(1, max(hi - lo for lo, hi in spans))


#: the block of a dead sweep step: 2**20 blocks off (times a tile of 1024
#: still an int32), far ahead of the queries as a key block and far behind
#: the window as a query block
_NO_BLOCK = 1 << 20


def _band_block(step, d, tiling: _Tiling, n, over_queries=False):
    """``(block, index)`` of sweep step ``step`` under a window: the block
    the step stands for, counted from the sweep's first live one, and the
    block its index map names: the same, held to the live range, so that a
    dead step names a block that is in VMEM already. A step past the last
    live block stands for :data:`_NO_BLOCK`, whose offset no span has (a
    query block past the sequence's end would lie inside the band)."""
    _, bq, bk, _, _, window = tiling
    (lo, hi), _ = _band_ranges(d, bq, bk, n, window, over_queries)
    block = lo + step
    return (jnp.where(block < hi, block, _NO_BLOCK),
            jnp.minimum(block, jnp.maximum(hi - 1, 0)))


def executed_score_share(tq: int, tk: int, block_q: int = 128,
                         block_k: int = 128, sub_q: int = _SUB_Q,
                         sub_k: int = _SUB_K, causal: bool = True,
                         q_offset: int = 0, k_offset: int = 0,
                         window: Optional[int] = None) -> float:
    """Share of the ``tq x tk`` score area the kernels compute, summed over
    their own spans (the causal mask itself needs just over a half): 0.625
    at T 4,096 with 1024-wide tiles computed whole, 0.53125 in 256-wide
    sub-tiles; under a window of 512, 0.17578 (the band itself is 0.11720
    of the area)."""
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    tiling = _Tiling(causal, bq, bk, _fit_block(sub_q, bq),
                     _fit_block(sub_k, bk), window)
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


def _scores(q, kblk, q_start, k_start, *, masked, sm_scale, window=None,
            by_key=False):
    """float32 scores of one span, NEG_INF where ``masked`` says so: the key
    lies ahead of the query (``_MASK_DIAGONAL``, which ``True`` is), or
    ``window`` or more behind it (``_MASK_EDGE``). ``[queries, keys]``, or
    with ``by_key`` the same tile transposed, ``k q^T``."""
    a, b = (kblk, q) if by_key else (q, kblk)
    s = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if masked:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                  int(by_key))
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                  int(not by_key))
        keep = qpos >= kpos if masked & _MASK_DIAGONAL else None
        if masked & _MASK_EDGE:
            near = qpos - kpos < window
            keep = near if keep is None else keep & near
        s = jnp.where(keep, s, NEG_INF)
    return s


def _run_spans(update, d, tiling, over_queries=False, no_offsets=False):
    """``update(rows, keys, masked)`` for each span of the tile that is due."""
    import jax.experimental.pallas as pl

    for when, spans in _tile_spans(d, tiling, over_queries, no_offsets):
        @pl.when(when)
        def _group(spans=spans):
            for span in spans:
                update(*span)


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, m_s, l_s, acc_s, *,
                  sm_scale: float, nk: int, sweep: int, tiling: _Tiling):
    """Grid point = (batch*heads, q_block, sweep step) with the sweep
    'arbitrary' (sequential): running rowmax/rowsum/accumulator live in
    VMEM scratch across the k sweep, so VMEM holds only one (bq, d) query
    tile and one (bk, d) K/V tile at a time — sequence length is bounded
    by HBM, not by VMEM (the previous full-K/V-resident block spec OOMed
    scoped vmem at T=8192). A tile wholly above the causal diagonal is in
    no span: scratch carries through unchanged. Step ``i`` of the sweep is
    key block ``i`` of ``nk``; under a window, the ``i``-th from the query
    block's first live one (``sweep`` steps hold every live block)."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    step = kb = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qoff_ref[0] + qb * tiling.block_q
    if tiling.window is not None:
        kb, _ = _band_block(step, q_start - koff_ref[0], tiling, nk)
    k_start = koff_ref[0] + kb * tiling.block_k

    def update(rows, keys, masked):
        vblk = v_ref[0, keys, :]
        s = _scores(q_ref[0, rows, :], k_ref[0, keys, :],
                    q_start + rows.start, k_start + keys.start,
                    masked=masked, sm_scale=sm_scale, window=tiling.window)
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

    @pl.when(step == sweep - 1)
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
    """``q [BH, T, D]``, ``k [BG, Tk, D]``, ``v [BG, Tk, Dv]`` (``H / G``
    consecutive query heads read one key-value head), offsets ``int32[1]``
    -> float32 ``(o [BH, Tq, Dv], m, l [BH, Tq, 128])``. Under a window the
    offsets are 0 (the ring form refuses one)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), tk, dv = q.shape, k.shape[1], v.shape[2]
    causal, bq, bk = tiling[:3]
    nk, group = tk // bk, bh // k.shape[0]
    sweep = (nk if tiling.window is None
             else _band_sweep(tiling, tq // bq, nk))

    def q_of(bh, qb, kb, qoff, koff):
        return bh, qb, 0

    def k_of(bh, qb, kb, qoff, koff):
        if tiling.window is not None:
            _, kb = _band_block(kb, qoff[0] + qb * bq - koff[0], tiling, nk)
        elif causal:
            kb = _last_live_key_block(kb, qoff[0] + qb * bq - koff[0],
                                      bq, bk, nk)
        return (bh // group if group > 1 else bh), kb, 0

    return pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, nk=nk,
                          sweep=sweep, tiling=tiling),
        # the offsets are prefetched scalars, so that K/V's index map sees
        # them too (ring steps pass traced shard starts)
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, tq // bq, sweep),
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
                          block_q: int, block_k: int, interpret: bool,
                          window=None):
    q, k = _pad_qk(q, k)
    b, h, tq, d = q.shape
    g, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    o, m, l = _flash_fwd(
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        jnp.asarray(k_offset, jnp.int32).reshape(1),
        q.reshape(b * h, tq, d), k.reshape(b * g, tk, d),
        v.reshape(b * g, tk, dv),
        tiling=_tiling(causal, tq, tk, block_q, block_k, window),
        sm_scale=sm_scale, interpret=interpret)
    return (o.reshape(b, h, tq, dv),
            m[..., 0].reshape(b, h, tq),
            l[..., 0].reshape(b, h, tq))


# ---------------------------------------------------------------------------
# Fused backward (full attention, offsets 0): scores are rebuilt span by span
# from the saved log-sum-exp, so neither pass holds a [Tq, Tk] tensor, and
# each live span is rebuilt ONCE: dq, dk and dv all take their update from
# the one (p, ds) built there.
# ---------------------------------------------------------------------------

def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_span(q, kblk, vblk, do, lse, delta, q_start, k_start, *,
              masked, sm_scale, window=None):
    """-> (p, ds) of one span, float32 and TRANSPOSED, ``[keys, queries]``
    (``lse``, ``delta`` ``[1, queries]``): dk and dv take them as they are
    (``ds @ q``, ``p @ do``), so no ``[queries, keys]`` tile is turned for
    them, and only dq contracts over the leading dimension."""
    s = _scores(q, kblk, q_start, k_start, masked=masked, sm_scale=sm_scale,
                window=window, by_key=True)
    p = jnp.exp(s - lse)                       # masked: exp(-1e30) == 0
    dp = _dot(vblk, do, ((1,), (1,)))
    return p, p * (dp - delta) * sm_scale


def _lane_tiles(d: int):
    """The columns of a ``d``-wide operand in 128-lane tiles. dk and dq
    leave their products a tile of columns at a time: at keys of 256 (192
    padded) the one backward call with 256-wide products took 27.2 ms where
    6.6 does the same work (v5e, sub-tiles of 256; any two of its three
    accumulations, or sub-tiles of 512, read 5.2 - 6.9: PERF.md, PR 34)."""
    return [slice(i, min(i + 128, d)) for i in range(0, d, 128)]


#: VMEM the one-call backward may ask for (a v5e core has 128 MiB, the
#: scoped default is 16), and what of it is kept for everything but ``dq``:
#: the double-buffered tiles, the dk / dv accumulators and a span's
#: temporaries, which fit the 16 MiB default in the two-kernel form.
_BWD_VMEM_LIMIT, _BWD_VMEM_REST = 100 << 20, 32 << 20


def _bwd_vmem(t: int, d: int, dv: int, group: int,
              itemsize: int = 2) -> Optional[int]:
    """The form the backward of ``group`` query heads a key-value head takes
    at ``t`` queries of (padded) size ``d`` and values of ``dv``, from shapes
    alone -> the VMEM limit of the ONE call that keeps a key-value head's
    whole ``dq`` in VMEM (float32 scratch, plus the output block it is cast
    into, which the pipeline holds twice), or None where that does not fit
    and dk/dv and dq are two kernels, each rebuilding the scores. ``dv``
    sizes nothing that grows with ``t``: dk and dv leave a key block at a
    time. Two sequences of 4,096: 40 MiB at 32 equal heads of 256, 56 at 6
    heads of 128 a group, 64 at 8; at 8 heads of 128 a group the two-kernel
    form starts over 8,704 positions, at equal heads of 256 over 34,816."""
    del dv
    need = group * t * d * (4 + 2 * itemsize) + _BWD_VMEM_REST
    return need if need <= _BWD_VMEM_LIMIT else None


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *rest, sm_scale, nq, nk, sweep, group,
                      tiling):
    """Grid (batch*kv heads, k_block, head of the group, sweep step), the
    last two sequential: one K/V tile stays put while the query tiles of
    each of its ``group`` query heads stream past it, and their sum is its
    gradient. ``rest`` is ``(dq_ref, dk_s, dv_s, dq_s)``: the key blocks are
    sequential too, and ``dq_s [group * nq, block_q, d]`` keeps the whole
    ``dq`` of the key-value head's query heads in float32 from its first
    key block to its last, when it leaves through ``dq_ref`` (whose block a
    key-value head's steps all name). Without the two (``(dk_s, dv_s)``: a
    sequence whose ``dq`` does not fit) this is the dk/dv kernel alone."""
    import jax.experimental.pallas as pl

    dq_ref, dk_s, dv_s, dq_s = rest if len(rest) == 4 else (None, *rest, None)
    kb, head, step = (pl.program_id(i) for i in (1, 2, 3))
    first = (head == 0) & (step == 0)
    last = (head == group - 1) & (step == sweep - 1)

    @pl.when(first)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    if dq_s is not None:
        @pl.when(first & (kb == 0))
        def _init_dq():
            dq_s[...] = jnp.zeros_like(dq_s)

    qb = at = step
    if tiling.window is not None:
        qb, at = _band_block(step, -kb * tiling.block_k, tiling, nq,
                             over_queries=True)
    q_start, k_start = qb * tiling.block_q, kb * tiling.block_k

    def update(rows, keys, masked):
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        p, ds = _bwd_span(q, k_ref[0, keys, :], v_ref[0, keys, :], do,
                          lse_ref[0, :1, rows], delta_ref[0, :1, rows],
                          q_start + rows.start, k_start + keys.start,
                          masked=masked, sm_scale=sm_scale,
                          window=tiling.window)
        ds = ds.astype(q.dtype)
        dv_s[keys, :] += _dot(p.astype(do.dtype), do, ((1,), (0,)))
        for cols in _lane_tiles(q.shape[1]):
            dk_s[keys, cols] += _dot(ds, q_ref[0, rows, cols], ((1,), (0,)))
            if dq_s is not None:
                dq_s[head * nq + at, rows, cols] += _dot(
                    ds, k_ref[0, keys, cols], ((0,), (0,)))

    _run_spans(update, q_start - k_start, tiling, over_queries=True,
               no_offsets=True)

    @pl.when(last)
    def _emit():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    if dq_s is not None:
        @pl.when(last & (kb == nk - 1))
        def _emit_dq():
            dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_s, *, sm_scale, nk, sweep, tiling):
    """Grid (batch*heads, q_block, sweep step), the k sweep sequential (the
    forward kernel's sweep)."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    step = kb = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    if tiling.window is not None:
        kb, _ = _band_block(step, qb * tiling.block_q, tiling, nk)
    q_start, k_start = qb * tiling.block_q, kb * tiling.block_k

    def update(rows, keys, masked):
        _, ds = _bwd_span(q_ref[0, rows, :], k_ref[0, keys, :],
                          v_ref[0, keys, :], do_ref[0, rows, :],
                          lse_ref[0, :1, rows], delta_ref[0, :1, rows],
                          q_start + rows.start, k_start + keys.start,
                          masked=masked, sm_scale=sm_scale,
                          window=tiling.window)
        ds = ds.astype(q_ref.dtype)
        for cols in _lane_tiles(q_ref.shape[2]):
            dq_s[rows, cols] += _dot(ds, k_ref[0, keys, cols], ((0,), (0,)))

    _run_spans(update, q_start - k_start, tiling, no_offsets=True)

    @pl.when(step == sweep - 1)
    def _emit():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_in_specs(tiling: _Tiling, d, dv, q_of, k_of):
    """q, k, v, do in tiles; lse and delta ``block_q`` positions along the
    lanes (8 equal rows), under the query tile's index."""
    bq, bk = tiling.block_q, tiling.block_k

    def row_of(*grid):
        head, qb, _ = q_of(*grid)
        return head, 0, qb

    return [_vmem_spec((1, bq, d), q_of), _vmem_spec((1, bk, d), k_of),
            _vmem_spec((1, bk, dv), k_of), _vmem_spec((1, bq, dv), q_of),
            _vmem_spec((1, 8, bq), row_of), _vmem_spec((1, 8, bq), row_of)]


@functools.partial(jax.jit, static_argnames=("tiling", "sm_scale", "interpret",
                                             "vmem_limit"))
def _flash_bwd(q, k, v, do, lse, delta, *, tiling: _Tiling, sm_scale: float,
               interpret: bool, vmem_limit: Optional[int]):
    """``q, do [BH, T, .]``, ``k, v [BG, Tk, .]``, ``lse`` / ``delta``
    ``[BH, 8, T]`` (:func:`_bwd_operands`) -> ``(dq, dk, dv)`` in the
    shapes and dtypes of ``q``, ``k``, ``v``: a key-value head's gradient
    is summed over its ``H / G`` query heads inside the kernel.
    ``vmem_limit`` is :func:`_bwd_vmem`'s word on the form: ONE kernel call
    under that limit, or with None the same kernel for dk / dv alone and
    :func:`_flash_dq`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), (bg, tk, _), dv = q.shape, k.shape, v.shape[2]
    bq, bk = tiling.block_q, tiling.block_k
    nq, nk, group = tq // bq, tk // bk, bh // bg
    sweep = (nq if tiling.window is None
             else _band_sweep(tiling, nk, nq, over_queries=True))
    fused = vmem_limit is not None

    def q_of(bg, kb, head, qb):
        if tiling.window is not None:
            _, qb = _band_block(qb, -kb * bk, tiling, nq, over_queries=True)
        elif tiling.causal:
            qb = _first_live_query_block(qb, -kb * bk, bq, bk, nq)
        return bg * group + head, qb, 0

    def k_of(bg, kb, head, qb):
        return bg, kb, 0

    out_specs = [_vmem_spec((1, bk, d), k_of), _vmem_spec((1, bk, dv), k_of)]
    out_shape = [jax.ShapeDtypeStruct((bg, tk, d), k.dtype),
                 jax.ShapeDtypeStruct((bg, tk, dv), v.dtype)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, dv), jnp.float32)]
    if fused:
        # dq as the kernel writes it: a key-value head's query heads and
        # their query blocks in one block, [BH, T, D] as it lies in memory
        out_specs.append(_vmem_spec((1, group * nq, bq, d),
                                    lambda bg, *_: (bg, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bg, group * nq, bq, d),
                                              q.dtype))
        scratch.append(pltpu.VMEM((group * nq, bq, d), jnp.float32))
    dk, dvv, *dq = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, sm_scale=sm_scale, nq=nq, nk=nk,
                          sweep=sweep, group=group, tiling=tiling),
        grid=(bg, nk, group, sweep),
        in_specs=_bwd_in_specs(tiling, d, dv, q_of, k_of),
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        # dq's scratch runs through a key-value head's key blocks; without
        # it they are as free as the heads
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary" if fused else
                                 "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if fused:
        return dq[0].reshape(q.shape), dk, dvv
    return (_flash_dq(q, k, v, do, lse, delta, tiling=tiling,
                      sm_scale=sm_scale, interpret=interpret), dk, dvv)


@functools.partial(jax.jit, static_argnames=("tiling", "sm_scale", "interpret"))
def _flash_dq(q, k, v, do, lse, delta, *, tiling: _Tiling, sm_scale: float,
              interpret: bool):
    """The operands of :func:`_flash_bwd` -> ``dq`` in the dtype of ``q``:
    the second kernel of a sequence whose ``dq`` does not fit VMEM."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, tq, d), tk, dv = q.shape, k.shape[1], v.shape[2]
    causal, bq, bk = tiling[:3]
    nk, group = tk // bk, bh // k.shape[0]
    sweep = (nk if tiling.window is None
             else _band_sweep(tiling, tq // bq, nk))

    def q_of(bh, qb, kb):
        return bh, qb, 0

    def k_of(bh, qb, kb):
        if tiling.window is not None:
            _, kb = _band_block(kb, qb * bq, tiling, nk)
        elif causal:
            kb = _last_live_key_block(kb, qb * bq, bq, bk, nk)
        return (bh // group if group > 1 else bh), kb, 0

    return pl.pallas_call(
        functools.partial(_flash_dq_kernel, sm_scale=sm_scale, nk=nk,
                          sweep=sweep, tiling=tiling),
        grid=(bh, tq // bq, sweep),
        in_specs=_bwd_in_specs(tiling, d, dv, q_of, k_of),
        out_specs=[_vmem_spec((1, bq, d), q_of)],
        out_shape=[jax.ShapeDtypeStruct((bh, tq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_sweep_last(), interpret=interpret,
    )(q, k, v, do, lse, delta)[0]


def _bwd_operands(q, k, v, out, lse, do):
    """-> the backward kernels' operands: heads folded into the batch,
    ``do`` in the dtype of ``q``, ``lse`` and ``delta = sum(do * out)`` as
    ``[BH, 8, T]``: the positions along the lanes, a head's row held 8
    times (one float32 tile of sublanes; handed ``[BH, 1, T]`` XLA turned
    the forward's whole ``[BH, T, 128]`` statistics to reach it, two
    copies of 268 MB at 64 heads)."""
    b, h, tq, d = q.shape
    g, tk, dv = k.shape[1], k.shape[2], v.shape[3]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def rows(a):            # [B,H,Tq] -> [BH, 8, Tq]
        return jnp.broadcast_to(a.reshape(b * h, 1, tq), (b * h, 8, tq))

    return (q.reshape(b * h, tq, d), k.reshape(b * g, tk, d),
            v.reshape(b * g, tk, dv), do.astype(q.dtype).reshape(b * h, tq, dv),
            rows(lse), rows(delta))


def _pallas_flash_bwd(q, k, v, out, lse, do, causal, sm_scale,
                      block_q: int, block_k: int, interpret: bool,
                      window=None):
    """q, k already padded. -> (dq, dk, dv) in the inputs' shapes and
    dtypes."""
    (_, h, tq, d), (_, g, tk, _) = q.shape, k.shape
    dq, dk, dvv = _flash_bwd(
        *_bwd_operands(q, k, v, out, lse, do),
        tiling=_tiling(causal, tq, tk, block_q, block_k, window),
        sm_scale=sm_scale, interpret=interpret,
        vmem_limit=_bwd_vmem(tq, d, v.shape[3], h // g, q.dtype.itemsize))
    return dq.reshape(q.shape), dk.reshape(k.shape), dvv.reshape(v.shape)


@functools.lru_cache(maxsize=None)
def _flash_with_vjp(causal: bool, sm_scale: float, block_q: int,
                    block_k: int, interpret: bool,
                    window: Optional[int] = None):
    """Full attention (offsets 0) on the Pallas path, kernels both ways.
    Saved for the backward: q, k, v, the output and the log-sum-exp."""

    def run(q, k, v):
        o, m, l = _pallas_block_partial(q, k, v, 0, 0, causal, sm_scale,
                                        block_q, block_k, interpret, window)
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
                                       sm_scale, block_q, block_k, interpret,
                                       window)
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
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Attention of a Q shard against one K/V chunk -> partial result
    ``(o_unnormalized, rowmax m, rowsum l)``, each fp32. Merge partials from
    several chunks with :func:`merge_partials`, finish with
    :func:`normalize_partial`. Differentiable (custom VJP, recompute-style
    backward). The partial form knows no window and refuses one: a ring
    step that ignored it would attend to the whole prefix."""
    if window is not None:
        raise NotImplementedError(
            "attention_block_partial has no window: a ring over sequence "
            "shards would have to skip the shards behind it")
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
    interpret: bool = False, window: Optional[int] = None,
) -> jax.Array:
    """Full fused attention, ``q [B, H, T, D]``, ``k [B, G, T, D]``, ``v [B,
    G, T, Dv]`` -> ``[B, H, T, Dv]`` (q.dtype); ``H / G`` consecutive query
    heads read one key-value head. ``window``: a query sees the ``window``
    keys up to and including its own position (causal only). On the Pallas
    path forward and backward are kernels (:func:`_flash_with_vjp`); the XLA
    path is the plain block math and its autodiff."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if q.shape[1] % k.shape[1] or v.shape[1] != k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} key "
                         f"and {v.shape[1]} value heads")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is causal and at least 1 wide")
    if _pick_impl(impl) == "pallas":
        return _flash_with_vjp(causal, float(sm_scale), block_q, block_k,
                               interpret, window)(q, k, v)
    if window is not None or q.shape[1] != k.shape[1]:
        return _xla_attention(q, k, v, causal, window, sm_scale)
    o, m, l = attention_block_partial(
        q, k, v, causal=causal, sm_scale=sm_scale, impl=impl,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return normalize_partial(o, m, l, out_dtype=q.dtype)
