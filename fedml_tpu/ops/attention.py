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

:func:`attention` on the Pallas path is the fused kernel forward AND
backward (a dq kernel and a dk/dv kernel that rebuild each score tile from
the saved log-sum-exp): no ``[Tq, Tk]`` tensor reaches HBM in either pass.
The partial form keeps its recompute-by-XLA backward (ring steps are short
chunks).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

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


def _pad_qk(q, k):
    """Zero-pad a query / key size over 128 to a multiple of the 128 lanes
    (192 -> 256): scores are unchanged, the MXU's passes are the same."""
    d = q.shape[-1]
    if d <= 128 or d % 128 == 0:
        return q, k
    pad = [(0, 0)] * 3 + [(0, -d % 128)]
    return jnp.pad(q, pad), jnp.pad(k, pad)


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, m_s, l_s, acc_s, *,
                  causal: bool, sm_scale: float,
                  block_q: int, block_k: int, nk: int):
    """Grid point = (batch*heads, q_block, k_block) with the k dimension
    'arbitrary' (sequential): running rowmax/rowsum/accumulator live in
    VMEM scratch across the k sweep, so VMEM holds only one (bq, d) query
    tile and one (bk, d) K/V tile at a time — sequence length is bounded
    by HBM, not by VMEM (the previous full-K/V-resident block spec OOMed
    scoped vmem at T=8192)."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qoff_ref[0] + qb * block_q
    k_start = koff_ref[0] + kb * block_k
    # causal: skip k blocks entirely above the diagonal (their mask is all
    # -inf); scratch then carries through unchanged.
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _update():
        q = q_ref[0]                                          # [bq, D]
        kblk = k_ref[0]                                       # [bk, D]
        vblk = v_ref[0]                                       # [bk, Dv]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                          # [bq, bk]
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_s[:, :1]                                   # [bq, 1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(kb == nk - 1)
    def _emit():
        o_ref[0] = acc_s[...]
        # m/l are row-broadcast across the 128-lane dim of their outputs
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


def _pallas_block_partial(q, k, v, q_offset, k_offset, causal, sm_scale,
                          block_q: int, block_k: int, interpret: bool):
    import jax.experimental.pallas as pl

    q, k = _pad_qk(q, k)
    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, dv)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff = jnp.asarray(k_offset, jnp.int32).reshape(1)

    nk = tk // bk
    grid = (b * h, tq // bq, nk)
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale,
        block_q=bq, block_k=bk, nk=nk)
    from jax.experimental.pallas import tpu as pltpu
    smem = pltpu.SMEM
    vmem = pltpu.VMEM

    def spec(block, index_map):
        return pl.BlockSpec(block, index_map, memory_space=vmem)

    o, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec(memory_space=smem),
            spec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0)),
            spec((1, bk, d), lambda bh, qb, kb: (bh, kb, 0)),
            spec((1, bk, dv), lambda bh, qb, kb: (bh, kb, 0)),
        ],
        out_specs=[
            spec((1, bq, dv), lambda bh, qb, kb: (bh, qb, 0)),
            spec((1, bq, 128), lambda bh, qb, kb: (bh, qb, 0)),
            spec((1, bq, 128), lambda bh, qb, kb: (bh, qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), jnp.float32),
            jax.ShapeDtypeStruct((b * h, tq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b * h, tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running rowmax
            pltpu.VMEM((bq, 128), jnp.float32),   # running rowsum
            pltpu.VMEM((bq, dv), jnp.float32),    # unnormalized output
        ],
        compiler_params=pltpu.CompilerParams(
            # only the kb sweep carries scratch state (re-initialized at
            # kb==0), so bh and qb may split across Megacore cores
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, koff, qr, kr, vr)
    return (o.reshape(b, h, tq, dv),
            m[..., 0].reshape(b, h, tq),
            l[..., 0].reshape(b, h, tq))



# ---------------------------------------------------------------------------
# Fused backward (full attention, offsets 0): scores are rebuilt tile by tile
# from the saved log-sum-exp, so neither pass holds a [Tq, Tk] tensor.
# ---------------------------------------------------------------------------

def _bwd_tile(q, kblk, vblk, do, lse, delta, q_start, k_start, *,
              causal, sm_scale, block_q, block_k):
    """-> (p, ds) of one [bq, bk] tile, float32."""
    s = jax.lax.dot_general(
        q, kblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jnp.exp(s - lse)                       # masked: exp(-1e30) == 0
    dp = jax.lax.dot_general(
        do, vblk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_s, dv_s, *, causal, sm_scale,
                      block_q, block_k, nq):
    """Grid (batch*heads, k_block, q_block), the q sweep sequential: one
    K/V tile stays put while the query tiles stream past it."""
    import jax.experimental.pallas as pl

    kb = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q_start, k_start = qb * block_q, kb * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _update():
        q, do = q_ref[0], do_ref[0]
        p, ds = _bwd_tile(q, k_ref[0], v_ref[0], do, lse_ref[0][:, :1],
                          delta_ref[0][:, :1], q_start, k_start,
                          causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k)
        dv_s[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_s[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _emit():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_s, *, causal, sm_scale, block_q, block_k, nk):
    """Grid (batch*heads, q_block, k_block), the k sweep sequential."""
    import jax.experimental.pallas as pl

    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q_start, k_start = qb * block_q, kb * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _update():
        kblk = k_ref[0]
        _, ds = _bwd_tile(q_ref[0], kblk, v_ref[0], do_ref[0],
                          lse_ref[0][:, :1], delta_ref[0][:, :1],
                          q_start, k_start, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k)
        dq_s[...] += jax.lax.dot_general(
            ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _pallas_flash_bwd(q, k, v, out, lse, do, causal, sm_scale,
                      block_q: int, block_k: int, interpret: bool):
    """q, k already padded. -> (dq, dk, dv) in the inputs' dtypes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    bq, bk = _fit_block(block_q, tq), _fit_block(block_k, tk)
    nq, nk = tq // bq, tk // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def lanes(a):           # [B,H,Tq] -> row-broadcast over the 128 lanes
        return jnp.broadcast_to(a.reshape(b * h, tq, 1), (b * h, tq, 128))

    args = (q.reshape(b * h, tq, d), k.reshape(b * h, tk, d),
            v.reshape(b * h, tk, dv), do.astype(q.dtype).reshape(b * h, tq, dv),
            lanes(lse), lanes(delta))

    def spec(block, index_map):
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)

    def in_specs(q_of, k_of):
        return [spec((1, bq, d), q_of), spec((1, bk, d), k_of),
                spec((1, bk, dv), k_of), spec((1, bq, dv), q_of),
                spec((1, bq, 128), q_of), spec((1, bq, 128), q_of)]

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    common = dict(causal=causal, sm_scale=sm_scale, block_q=bq, block_k=bk)
    dk, dvv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, nq=nq, **common),
        grid=(b * h, nk, nq),
        in_specs=in_specs(lambda bh, kb, qb: (bh, qb, 0),
                          lambda bh, kb, qb: (bh, kb, 0)),
        out_specs=[spec((1, bk, d), lambda bh, kb, qb: (bh, kb, 0)),
                   spec((1, bk, dv), lambda bh, kb, qb: (bh, kb, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(*args)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, nk=nk, **common),
        grid=(b * h, nq, nk),
        in_specs=in_specs(lambda bh, qb, kb: (bh, qb, 0),
                          lambda bh, qb, kb: (bh, kb, 0)),
        out_specs=[spec((1, bq, d), lambda bh, qb, kb: (bh, qb, 0))],
        out_shape=[jax.ShapeDtypeStruct((b * h, tq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(*args)[0]
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
