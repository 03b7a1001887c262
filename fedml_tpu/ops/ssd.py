"""The selective state-space recurrence of Mamba-2 (the SSD form: Dao and
Gu, arXiv:2405.21060) with a SCALAR decay a head and ``B``, ``C`` shared by
all the heads of a layer (one group), computed in chunks.

A head keeps a state ``S [P, N]`` (float32, zeros before position 0). At
position ``t``, with an input ``x_t [P]``, a step ``dt_t > 0``, the head's
rate ``A = -exp(A_log) < 0`` and the layer's ``B_t, C_t [N]``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

:func:`ssd_recurrent` is that, token by token (the tests' yardstick).
:func:`ssd_chunked` computes the same in chunks of ``Q`` positions. With
``a_i`` the sum of ``dt A`` from the chunk's first position to ``i`` (so
``a_i <= 0``) and ``S_0`` the state the chunk starts from::

    L_ij = exp(a_i - a_j)  (j <= i, else 0)
    Y    = (L * (C B^T)) (dt x) + exp(a) (C S_0^T)
    S_Q  = exp(a_Q) S_0 + (exp(a_Q - a) dt x)^T B

Every exponent is ``<= 0``: ``exp(a_i - a_j)`` is formed from the
difference and never as ``exp(a_i) exp(-a_j)`` (at ``dt A`` = -1.6 a
position a chunk of 256 is ``e^{410}``). ``C B^T`` is ONE ``[Q, Q]`` product
a chunk for all the heads.

Unlike the delta rule (``ops/kda.py``), what a chunk writes does not depend
on the state it meets: every chunk's ``(exp(a_Q - a) dt x)^T B`` is computed
at once, the scan over chunks is ``S' = exp(a_Q) S + local`` on ``[P, N]``
and nothing else, and every chunk's start state is kept (2 MB a chunk at 64
heads of 64 x 128: no replay between kept states is worth its code). So the
two ops share their conventions (no ``exp`` of a positive sum, the state,
the running sums and ``L`` in float32) and one function, the product with
operands in the module's dtype under float32 accumulation (``kda._mm``).

Plain ``jax.numpy`` with JAX's own backward; a block's ``nn.remat`` bounds
what the backward keeps to one layer's intermediates (``L`` for every head
and chunk: ``T x Q x H`` float32, 268 MB at 4,096 x 256 x 64).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedml_tpu.ops.kda import _mm

#: positions a chunk. The published kernel's ``mamba_chunk_size`` is 256;
#: the chunk changes the arithmetic's order and not the function. Alone on
#: the v5e at [1, 4096, 64, 64] x 128 (``tools/ssd_sweep.py``; PERF.md,
#: PR 37), forward / forward + backward in ms: 64: 2.68 / 5.79, 128: 1.19 /
#: 2.80, 256: 1.02 / 2.22 (the scan over chunks and the narrow products
#: cost more than the masked decays save)
SSD_CHUNK = 256


def ssd_recurrent(x, dt, a_log, b, c, d):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (positive: after its softplus),
    ``a_log, d [H]``, ``b, c [B, T, N]`` -> ``y [B, T, H, P]``: the
    recurrence itself, one position at a time, in float32."""
    f32 = jnp.float32
    x, dt, a_log, b, c, d = (v.astype(f32) for v in (x, dt, a_log, b, c, d))
    rate = -jnp.exp(a_log)
    hi = lax.Precision.HIGHEST

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (s * jnp.exp(dtt * rate)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, ct, precision=hi)

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], f32)
    _, y = lax.scan(step, s0, tuple(jnp.moveaxis(v, 1, 0)
                                    for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


@functools.partial(jax.jit, static_argnames=("chunk", "dtype"))
def ssd_chunked(x, dt, a_log, b, c, d, *, chunk: int = SSD_CHUNK,
                dtype=jnp.bfloat16):
    """The recurrence of :func:`ssd_recurrent` in chunks of ``chunk``
    positions (clamped to ``T``; a last partial chunk is filled with
    positions of ``dt`` 0, which write nothing and are cut off). Matmul
    operands in ``dtype``; returns ``y`` in float32."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    q = min(chunk, t)
    n = -(-t // q)

    def chunks(v):
        v = jnp.pad(v, ((0, 0), (0, n * q - t)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape((bsz, n, q) + v.shape[2:])

    xc, dtc, bc, cc = chunks(x), chunks(dt.astype(f32)), chunks(b), chunks(c)
    # a [B, n, H, Q]: heads before positions, so that L's minor axes are Q, Q
    a = jnp.cumsum(jnp.moveaxis(dtc, 3, 2) * -jnp.exp(a_log.astype(f32))[:, None],
                   axis=-1)
    xdt = xc.astype(f32) * dtc[..., None]                       # [B, n, Q, H, P]
    pos = jnp.arange(q)
    seen = pos[:, None] >= pos[None, :]
    l = jnp.exp(jnp.where(seen, a[..., :, None] - a[..., None, :], -jnp.inf))
    cb = _mm("bnis,bnjs->bnij", cc, bc, dtype)                  # [B, n, Q, Q]
    y = _mm("bnhij,bnjhp->bnihp", l * cb[:, :, None], xdt, dtype)
    # what each chunk writes by its end, and the scan over chunks
    to_end = jnp.moveaxis(jnp.exp(a[..., -1:] - a), 2, 3)       # [B, n, Q, H]
    local = _mm("bnjhp,bnjs->bnhps", xdt * to_end[..., None], bc, dtype)

    def carry(s, inp):
        decay, wrote = inp
        return s * decay[..., None, None] + wrote, s

    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    _, starts = lax.scan(carry, s0, (jnp.moveaxis(jnp.exp(a[..., -1]), 1, 0),
                                     jnp.moveaxis(local, 1, 0)))
    y = y + (_mm("bnis,nbhps->bnihp", cc, starts, dtype)
             * jnp.moveaxis(jnp.exp(a), 2, 3)[..., None])
    y = y.reshape(bsz, n * q, h, p)[:, :t]
    return y + d.astype(f32)[:, None] * x.astype(f32)
