"""Fused masked softmax cross-entropy.

Counterpart of the per-trainer loss code in the reference
(my_model_trainer_classification.py:19-53 uses ``nn.CrossEntropyLoss``
eagerly per batch). On TPU the large-vocab case (stackoverflow NWP, 10k+
vocab; transformer LM heads) wants the log-softmax fused with the gold-label
gather so the [N, V] probabilities never round-trip HBM: one pass computes
rowmax, logsumexp and the label logit per 2-D tile.

``impl='xla'`` is the jnp reference (classification losses in
fedml_tpu/core/tasks.py use the same math); ``'pallas'`` is the TPU kernel;
``'auto'`` picks by backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.ops.attention import _pick_impl

#: bytes the double-buffered logits tile may take. The kernel's block is a
#: whole padded vocabulary row, so its VMEM grows with V; Mosaic's scoped
#: limit on a v5e is 16 MiB (64 rows of V=32,768 in f32 is exactly over).
_VMEM_TILE_BUDGET = 12 * 1024 * 1024


def _xla_xent(logits, labels):
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logz, labels[..., None].astype(jnp.int32), axis=-1)
    return -gold[..., 0]


def _xent_kernel(logits_ref, labels_ref, out_ref, *, block_n: int, block_v: int):
    """One grid point handles block_n rows; V is streamed in block_v slices
    with a running (rowmax, sum-exp, gold-logit) triple."""
    import jax.experimental.pallas as pl

    v_total = logits_ref.shape[1]
    nv = v_total // block_v
    labels = labels_ref[0].reshape(block_n, 1)

    m0 = jnp.full((block_n, 1), -1e30, jnp.float32)
    s0 = jnp.zeros((block_n, 1), jnp.float32)
    g0 = jnp.zeros((block_n, 1), jnp.float32)

    def body(i, carry):
        m, s, g = carry
        blk = logits_ref[pl.ds(0, block_n), pl.ds(i * block_v, block_v)]
        blk = blk.astype(jnp.float32)
        m_new = jnp.maximum(m, jnp.max(blk, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        s = s * alpha + jnp.sum(jnp.exp(blk - m_new), axis=-1, keepdims=True)
        vids = i * block_v + jax.lax.broadcasted_iota(
            jnp.int32, (block_n, block_v), 1)
        hit = (vids == labels).astype(jnp.float32)
        g = g + jnp.sum(blk * hit, axis=-1, keepdims=True)
        return m_new, s, g

    m, s, g = jax.lax.fori_loop(0, nv, body, (m0, s0, g0))
    loss = m + jnp.log(s) - g                                # [bn, 1]
    out_ref[0] = jnp.broadcast_to(loss, (block_n, 128))


def _pallas_xent(logits, labels, block_n: int, block_v: int, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, v = logits.shape
    bn = min(block_n, n)
    while n % bn:
        bn //= 2
    # Keep the vocab block wide regardless of V's factorization (a 10004
    # vocab must not collapse the block to 4 lanes): pad V up to a block
    # multiple with -1e30 columns — exp(-1e30 - m) == 0, so padding columns
    # never perturb the running (max, sumexp) and labels never hit them.
    bv = min(block_v, -(-v // 128) * 128)
    v_pad = -(-v // bv) * bv
    if v_pad != v:
        logits = jnp.pad(logits, ((0, 0), (0, v_pad - v)),
                         constant_values=-1e30)
    v = v_pad
    # large vocabularies: fewer rows per block until the tile pair fits
    # (halving keeps bn a divisor of n; the floor is one sublane tile)
    min_rows = 8 if logits.dtype.itemsize >= 4 else 16
    while (bn % 2 == 0 and bn // 2 >= min_rows
           and 2 * bn * v * logits.dtype.itemsize > _VMEM_TILE_BUDGET):
        bn //= 2

    out = pl.pallas_call(
        functools.partial(_xent_kernel, block_n=bn, block_v=bv),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, v), lambda i: (i, 0), memory_space=pltpu.VMEM),
            # labels ride as [n/bn, 1, bn] so the block's trailing dims
            # (1, bn) EQUAL the array's — TPU lowering requires trailing
            # block dims divisible by (8, 128) or exactly the array dims
            # (a (1, bn) block over a [n/bn, bn] array fails that check;
            # interpret mode never enforces it)
            pl.BlockSpec((1, 1, bn), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bn, 128), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n // bn, bn, 128), jnp.float32),
        interpret=interpret,
    )(logits, labels.astype(jnp.int32).reshape(n // bn, 1, bn))
    return out[..., 0].reshape(n)


@functools.lru_cache(maxsize=None)
def _xent_with_vjp(impl: str, block_n: int, block_v: int, interpret: bool):
    """CE with custom VJP. Backward is the closed form
    ``d loss_i / d logits = softmax(logits_i) - onehot(label_i)`` — no
    recompute of the forward reduction. Labels travel as float32 so
    custom_vjp hands back an ordinary zero cotangent."""

    @jax.custom_vjp
    def f(logits, labels_f):
        labels = labels_f.astype(jnp.int32)
        if impl == "xla":
            return _xla_xent(logits, labels)
        return _pallas_xent(logits, labels, block_n, block_v, interpret)

    def fwd(logits, labels_f):
        return f(logits, labels_f), (logits, labels_f)

    def bwd(res, ct):
        logits, labels_f = res
        labels = labels_f.astype(jnp.int32)
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        dlogits = (ct[..., None] * (p - onehot)).astype(logits.dtype)
        return dlogits, jnp.zeros_like(labels_f)

    f.defvjp(fwd, bwd)
    return f


def masked_cross_entropy(
    logits: jax.Array, labels: jax.Array, mask=None, *,
    impl: str = "auto", block_n: int = 64, block_v: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Per-example CE loss ``[...,]`` fp32; masked entries are zeroed.

    ``logits [..., V]``, integer ``labels [...]``, optional ``mask [...]``.
    Differentiable w.r.t. ``logits`` (closed-form custom VJP).
    """
    shape = labels.shape
    v = logits.shape[-1]
    flat_logits = logits.reshape(-1, v)
    flat_labels = labels.reshape(-1)
    f = _xent_with_vjp(_pick_impl(impl), block_n, block_v, interpret)
    per = f(flat_logits, flat_labels.astype(jnp.float32))
    per = per.reshape(shape)
    if mask is not None:
        per = per * mask.astype(per.dtype)
    return per
