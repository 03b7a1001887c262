"""Decoder-only transformer LM — the TPU-first upgrade of the reference's
RNN family (fedml_api/model/nlp/rnn.py:4-70 only ships 80/20-token LSTMs).

Attention goes through :mod:`fedml_tpu.ops.attention` (fused blockwise
kernel, MXU-shaped). When ``ring_axis`` is set the module must be applied
inside a ``shard_map`` over that mesh axis: the sequence is sharded, K/V
rotate around the ring (fedml_tpu/parallel/sequence.py), and
``pos_offset`` gives the shard's global position for positional embeddings
and causal masks — this is the framework's long-context path.

Registered as ``transformer`` (char-level shakespeare default) and
``transformer_nwp`` (stackoverflow word-level default) so every federated
algorithm can train it like any other zoo model.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import ModelBundle, register_model
from fedml_tpu.obs.tracer import SCOPE_LM_ATTN, SCOPE_LM_DENSE
from fedml_tpu.ops.attention import attention


class SelfAttention(nn.Module):
    dim: int
    heads: int
    attn_impl: str = "auto"
    ring_axis: Optional[str] = None
    ring_size: int = 1
    sp_mode: str = "ring"            # ring | ulysses (all-to-all)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        b, t, _ = h.shape
        d = self.dim // self.heads
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads_first(a):
            return a.reshape(b, t, self.heads, d).transpose(0, 2, 1, 3)

        q, k, v = heads_first(q), heads_first(k), heads_first(v)
        if self.ring_axis is not None and self.ring_size > 1:
            from fedml_tpu.parallel.sequence import sequence_attention

            o = sequence_attention(q, k, v, axis_name=self.ring_axis,
                                   axis_size=self.ring_size, causal=True,
                                   impl=self.attn_impl, mode=self.sp_mode)
        else:
            o = attention(q, k, v, causal=True, impl=self.attn_impl)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, self.dim)
        return nn.Dense(self.dim, dtype=self.dtype, name="out")(o)


class Block(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    dropout: float = 0.0
    attn_impl: str = "auto"
    ring_axis: Optional[str] = None
    ring_size: int = 1
    sp_mode: str = "ring"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool):
        a = SelfAttention(self.dim, self.heads, self.attn_impl,
                          self.ring_axis, self.ring_size, self.sp_mode,
                          self.dtype,
                          name="attn")(nn.LayerNorm(dtype=self.dtype)(h))
        if self.dropout:
            a = nn.Dropout(self.dropout, deterministic=not train)(a)
        h = h + a
        m = nn.Dense(self.mlp_ratio * self.dim, dtype=self.dtype)(
            nn.LayerNorm(dtype=self.dtype)(h))
        m = nn.gelu(m)
        m = nn.Dense(self.dim, dtype=self.dtype)(m)
        if self.dropout:
            m = nn.Dropout(self.dropout, deterministic=not train)(m)
        return h + m


class TransformerLM(nn.Module):
    vocab_size: int
    dim: int = 256
    heads: int = 8
    layers: int = 4
    mlp_ratio: int = 4
    max_len: int = 4096
    dropout: float = 0.0
    attn_impl: str = "auto"
    ring_axis: Optional[str] = None     # set to 'sp' for sequence parallelism
    ring_size: int = 1
    sp_mode: str = "ring"               # ring (ppermute) | ulysses (all-to-all)
    remat: bool = False                 # rematerialize blocks on backward
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, pos_offset=0):
        t = x.shape[1]
        h = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="tok_embed")(x.astype(jnp.int32))
        pos = pos_offset + jnp.arange(t)
        h = h + nn.Embed(self.max_len, self.dim, dtype=self.dtype,
                         name="pos_embed")(pos)[None]
        # remat: drop each block's activations on the forward pass and
        # recompute them during backward — long-context training is HBM-bound
        # on activations (B x T x D per layer), and the recompute rides the
        # MXU headroom the small per-block matmuls leave anyway.
        block_cls = (nn.remat(Block, static_argnums=(2,)) if self.remat
                     else Block)
        for i in range(self.layers):
            h = block_cls(self.dim, self.heads, self.mlp_ratio, self.dropout,
                          self.attn_impl, self.ring_axis, self.ring_size,
                          self.sp_mode, self.dtype, name=f"block{i}")(h, train)
        h = nn.LayerNorm(dtype=self.dtype)(h)
        return nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(h)


def _bundle(name, vocab, seq_len, **kw):
    sizes = dict(dim=kw.pop("dim", 256), heads=kw.pop("heads", 8),
                 layers=kw.pop("layers", 4), dropout=kw.pop("dropout", 0.0),
                 mlp_ratio=kw.pop("mlp_ratio", 4))
    module = TransformerLM(vocab_size=vocab, max_len=max(4096, seq_len),
                           attn_impl=kw.pop("attn_impl", "auto"),
                           ring_axis=kw.pop("ring_axis", None),
                           ring_size=kw.pop("ring_size", 1),
                           sp_mode=kw.pop("sp_mode", "ring"),
                           remat=kw.pop("remat", False),
                           dtype=kw.pop("dtype", jnp.float32), **sizes)
    return ModelBundle(
        name=name, module=module, input_shape=(seq_len,),
        input_dtype=jnp.int32, task="nwp",
        uses_dropout=sizes["dropout"] > 0,
    )


@register_model("transformer")
def _transformer(output_dim: int = 90, seq_len: int = 80, **kw):
    return _bundle("transformer", output_dim or 90, seq_len, **kw)


@register_model("transformer_nwp")
def _transformer_nwp(output_dim: int = 10004, seq_len: int = 20, **kw):
    return _bundle("transformer_nwp", output_dim or 10004, seq_len, **kw)


# ---------------------------------------------------------------------------
# The blocks of today's open decoder LMs: RMSNorm pre-norm, SwiGLU, rotary
# positions, latent attention (DeepSeek-V2/V3's MLA). No biases anywhere.
# models/moe.py builds the sparse-expert LM out of them.
# ---------------------------------------------------------------------------

def _normal(std: float = 0.02):
    return nn.initializers.normal(std)


class Linear(nn.Module):
    """``x @ kernel``: float32 parameter, operands in ``dtype``, float32
    accumulation, result in ``out_dtype`` (default ``dtype``)."""

    features: int
    dtype: Any = jnp.float32
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(), (x.shape[-1], self.features),
                            jnp.float32)
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                    preferred_element_type=jnp.float32)
        return y.astype(self.out_dtype or self.dtype)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``, statistics in float32."""

    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (y * scale).astype(self.dtype)


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        g = Linear(self.width, self.dtype, name="gate")(x)
        u = Linear(self.width, self.dtype, name="up")(x)
        return Linear(x.shape[-1], self.dtype, name="down")(nn.silu(g) * u)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last axis of ``x [..., T, R]``, INTERLEAVED
    pairs: channels ``(2i, 2i+1)`` turn by ``pos * theta^(-2i/R)``. (The
    published ``rope_interleave`` code first moves the even channels to the
    front half and then turns halves; applied to queries and keys alike that
    is this rotation under one fixed permutation of the channels, and every
    ``q . k`` is the same.) Computed in float32, returned in ``x.dtype``."""
    t, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


#: queries and keys per TILE of the attention kernels (what a grid step
#: fetches): on the v5e, forward and backward at [2, 32, 4096] took 97.0 ms
#: at the op's default of 128, 25.3 at 512, 20.6 at 1024 (PERF.md, PR 26);
#: 2048 does not fit the kernels' 16 MB of VMEM; shorter sequences clamp it.
#: Inside a tile the kernels compute in 256-wide sub-tiles of their own
#: choosing (``ops/attention.py``; PERF.md, PR 27: 19.46 -> 17.83 ms)
_LATENT_ATTN_BLOCK = 1024


class LatentAttention(nn.Module):
    """Multi-head latent attention without a query bottleneck
    (``q_lora_rank`` null). ``q = W_q x`` as ``heads`` of ``nope + rope``;
    ``[c, k_r] = W_kva x`` with ``c`` the ``kv_rank``-wide compressed
    key-value and ``k_r`` ONE rotary key for all heads; ``c <- RMSNorm(c)``;
    ``[k_nope, v] = W_kvb c`` per head; rotary on ``q_rope`` and ``k_r``;
    ``k = [k_nope, k_r]``; causal softmax of ``q . k / sqrt(nope + rope)``;
    the ``heads * v_dim`` output goes through ``W_o``."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, dim = x.shape
        h, dn, dr, dv = self.heads, self.nope, self.rope, self.v_dim
        with jax.named_scope(SCOPE_LM_DENSE):
            q = Linear(h * (dn + dr), self.dtype, name="q_proj")(x)
            ckr = Linear(self.kv_rank + dr, self.dtype, name="kv_a")(x)
        q = q.reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
        c = RMSNorm(self.eps, self.dtype, name="kv_norm")(
            ckr[..., :self.kv_rank])
        with jax.named_scope(SCOPE_LM_DENSE):
            kv = Linear(h * (dn + dv), self.dtype, name="kv_b")(c)
        kv = kv.reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
        k_r = rotary(ckr[:, None, :, self.kv_rank:], self.rope_theta)  # [B,1,T,dr]
        q = jnp.concatenate(
            [q[..., :dn], rotary(q[..., dn:], self.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))], axis=-1)
        with jax.named_scope(SCOPE_LM_ATTN):
            o = attention(q, k, kv[..., dn:], causal=True,
                          block_q=_LATENT_ATTN_BLOCK,
                          block_k=_LATENT_ATTN_BLOCK)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dv)
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="o_proj")(o)
