"""The delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692), computed in chunks: the first op here whose state runs
along the sequence.

A head keeps a state ``S [dk, dv]`` (float32, zeros before position 0). At
position ``t``, with a key ``k_t`` (L2-normalised), a value ``v_t``, a
per-channel decay ``alpha_t = exp(g_t)`` in ``(0, 1]^dk`` and a step
``beta_t`` in ``(0, 1)``::

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_recurrent` is that, token by token (the tests' yardstick).
:func:`kda_chunked` computes the same in chunks of ``C`` positions (the WY
form). With ``G_i`` the sum of ``g`` from the chunk's first position to
``i`` (so ``G_i <= 0``) and ``u_i = beta_i (v_i - S~_i^T k_i)`` the delta
position ``i`` writes (``S~_i`` the decayed state it meets), the chunk
unrolls to ``S_i = diag(e^{G_i}) S_0 + sum_{j<=i} diag(e^{G_i-G_j}) k_j
u_j^T``, so::

    A_ij = beta_i sum_c k_ic k_jc e^{G_ic - G_jc}     (j <  i)
    P_ij =        sum_c q_ic k_jc e^{G_ic - G_jc}     (j <= i)
    (I + A) [W_v | W_k] = [beta v | beta (k e^{G})]   (a triangular solve)
    U   = W_v - W_k S_0
    O   = (q e^{G}) S_0 + P U
    S_C = diag(e^{G_C}) S_0 + (k e^{G_C - G})^T U

Everything but the three lines with ``S_0`` is computed for all chunks at
once; a ``lax.scan`` over the chunks carries the state.

**No ``exp(+G)`` is ever formed.** ``e^{G_i - G_j}`` does not factor into
``e^{G_i} e^{-G_j}`` safely: at the gate's bound of -5 a position, 64
positions are ``e^{320}``. Rows are taken in sub-blocks of ``sub``
positions, each about a reference row ``r`` (its first): ``e^{G_i - G_r}``
on the row side is at most 1, and ``e^{G_r - G_j}`` on the column side is
at most 1 for the columns before the sub-block, at most ``e^{5 (sub - 1)}``
inside it (``e^{75}`` at 16: float32 and bfloat16 share the exponent), and
is SET to zero for the columns after it, which the causal mask drops
anyway. So a sub-block's row of ``A`` or ``P`` is one matmul of bounded
operands, exact in exponent.

The state, the cumulative log-decays and the solve are float32; the
matmuls take their operands in ``dtype`` (the module's) and accumulate in
float32. The backward pass is JAX's own through the scan. The intra-chunk
part is recomputed (``jax.checkpoint``), and so are the chunk steps between
two kept states: it keeps a state every ``keep`` chunks, ``T / (C keep)`` of
them a head, and replays the ``keep`` steps after each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: positions a chunk and a sub-block, and the chunk steps between two states
#: the backward pass keeps. Alone on the v5e at [1, 32, 4096, 128]
#: (``tools/kda_sweep.py``; PERF.md, PR 30) forward + backward take 19.4 ms
#: at chunk 32, 26.1 at 64, 49.1 at 128 with every chunk's state kept; but
#: kept a chunk, 32 costs the hybrid LM's round program 2.76 GB more
#: temporaries than 64 (12,020.6 against 9,263.6 MB compiled:
#: ``tools/round_fit.py``) and it no longer fits the chip. Kept every 4th
#: chunk, with the 3 between replayed, it compiles to 9,029.4 MB
KDA_CHUNK = 32
KDA_SUB = 16
KDA_KEEP = 4


def kda_recurrent(q, k, v, g, beta):
    """``q, k, g [B, H, T, dk]``, ``v [B, H, T, dv]``, ``beta [B, H, T]`` ->
    ``o [B, H, T, dv]``: the recurrence itself, one position at a time, in
    float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    hi = lax.Precision.HIGHEST

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=hi))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=hi)

    b, h, _, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    _, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)


def _mm(eq: str, a, b, dtype):
    # float32 operands mean float32 products: the TPU's default would round
    # them to bfloat16 all the same
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=(lax.Precision.HIGHEST
                                 if dtype == jnp.float32 else None))


def _intra(q, k, v, g, beta, sub: int, dtype):
    """All chunks at once. ``q, k, g [..., N, C, dk]``, ``v [..., N, C,
    dv]``, ``beta [..., N, C]`` -> the scan's per-chunk operands ``(w_v
    [.., C, dv], w_k [.., C, dk], p [.., C, C], q_in [.., C, dk], k_out
    [.., C, dk], decay [.., dk])``."""
    f32 = jnp.float32
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    n_sub = c // sub
    qf, kf, bf = q.astype(f32), k.astype(f32), beta.astype(f32)[..., None]
    G = jnp.cumsum(g.astype(f32), axis=-2)                    # <= 0
    lead = G.shape[:-2]

    def blocks(a):                                            # [.., I, sub, dk]
        return a.reshape(lead + (n_sub, sub, dk))

    # the reference row of each sub-block: its first
    ref = blocks(G)[..., 0, :]                                # [.., I, dk]
    row = jnp.exp(blocks(G) - ref[..., None, :])              # <= 1
    # columns of sub-block I: every position up to its last, about ref_I
    pos = jnp.arange(c)
    seen = (pos < (jnp.arange(n_sub)[:, None] + 1) * sub)[..., None]  # [I, C, 1]
    diff = ref[..., :, None, :] - G[..., None, :, :]          # [.., I, C, dk]
    col = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)
    k_col = kf[..., None, :, :] * col
    a = _mm("...isd,...ijd->...isj", blocks(kf) * row, k_col, dtype).reshape(
        lead + (c, c))
    p = _mm("...isd,...ijd->...isj", blocks(qf) * row, k_col, dtype).reshape(
        lead + (c, c))
    a = jnp.where(pos[:, None] > pos[None, :], a, 0.0) * bf
    p = jnp.where(pos[:, None] >= pos[None, :], p, 0.0)
    decayed = jnp.exp(G)                                      # from the start
    rhs = jnp.concatenate([bf * v.astype(f32), bf * kf * decayed], axis=-1)
    w = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True)
    g_last = G[..., -1:, :]
    # what the scan only multiplies goes to it in the matmuls' dtype
    return (w[..., :dv], w[..., dv:].astype(dtype), p.astype(dtype),
            (qf * decayed).astype(dtype),
            (kf * jnp.exp(g_last - G)).astype(dtype),
            jnp.exp(g_last[..., 0, :]))


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "keep", "dtype"))
def kda_chunked(q, k, v, g, beta, *, chunk: int = KDA_CHUNK,
                sub: int = KDA_SUB, keep: int = KDA_KEEP, dtype=jnp.bfloat16):
    """The recurrence of :func:`kda_recurrent` in chunks of ``chunk``
    positions (clamped to ``T``; ``T`` a multiple of it, ``chunk`` of
    ``sub``), a state kept for the backward pass every ``keep`` chunks (every
    chunk where ``keep`` does not divide their number). ``g`` is the
    log-decay, ``<= 0``; returns ``o`` in float32."""
    b, h, t, dk = q.shape
    c = min(chunk, t)
    s = min(sub, c)
    if t % c or c % s:
        raise ValueError(f"kda_chunked: T {t} is no multiple of the chunk "
                         f"{c}, or the chunk none of the sub-block {s}")
    n = t // c

    def chunks(a):
        return a.reshape(a.shape[:2] + (n, c) + a.shape[3:])

    parts = jax.checkpoint(functools.partial(_intra, sub=s, dtype=dtype))(
        *(chunks(a) for a in (q, k, v, g, beta)))

    def step(state, x):
        w_v, w_k, p, q_in, k_out, decay = x
        u = w_v - _mm("bhck,bhkv->bhcv", w_k, state, dtype)
        o = (_mm("bhck,bhkv->bhcv", q_in, state, dtype)
             + _mm("bhcj,bhjv->bhcv", p, u, dtype))
        state = (state * decay[..., None]
                 + _mm("bhck,bhcv->bhkv", k_out, u, dtype))
        return state, o

    # the backward keeps a state every ``keep`` chunks and replays between
    kp = keep if n % keep == 0 else 1

    @jax.checkpoint
    def steps(state, xs):
        return lax.scan(step, state, xs)

    def grouped(a):
        a = jnp.moveaxis(a, 2, 0)
        return a.reshape((n // kp, kp) + a.shape[1:])

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(steps, s0, tuple(grouped(a) for a in parts))
    o = o.reshape((n,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, v.shape[-1])
