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

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models import COUNTERS, ModelBundle, register_model
from fedml_tpu.obs.tracer import (SCOPE_LM_ATTN, SCOPE_LM_ATTN_WINDOW,
                                  SCOPE_LM_CCA_MIX, SCOPE_LM_DENSE,
                                  SCOPE_LM_KDA, SCOPE_LM_KDA_PREP,
                                  SCOPE_LM_SSD, SCOPE_LM_SSD_PREP)
from fedml_tpu.ops.attention import attention
from fedml_tpu.ops.kda import kda_chunked
from fedml_tpu.ops.ssd import SSD_CHUNK, ssd_chunked


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


def column_products(x, kernel, widths, dtype):
    """``x @ kernel[:, a:b]`` for each of the neighbouring column ranges of
    ``widths``, each a product of its own with ``Linear``'s precision."""
    edges = np.cumsum((0,) + tuple(widths))
    x = x.astype(dtype)
    return tuple(
        jnp.dot(x, kernel[:, a:b].astype(dtype),
                preferred_element_type=jnp.float32).astype(dtype)
        for a, b in zip(edges[:-1], edges[1:]))


class ColumnLinear(nn.Module):
    """``Linear``'s one ``kernel`` of ``sum(widths)`` columns (the same leaf,
    initialiser and column order) issued as one product a column range:
    each consumer of a joint projection takes the output of its own product
    and nothing is cut from a shared output after the fact."""

    widths: tuple
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(),
                            (x.shape[-1], sum(self.widths)), jnp.float32)
        return column_products(x, kernel, self.widths, self.dtype)


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
    """``down(silu(gate(x)) * up(x))``. ``stats``: -> ``(y, ())``, the
    form's statistics beside the result, as every entry of
    :data:`MLP_FORMS` gives them (this form keeps none)."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, stats: bool = False):
        g = Linear(self.width, self.dtype, name="gate")(x)
        u = Linear(self.width, self.dtype, name="up")(x)
        y = Linear(x.shape[-1], self.dtype, name="down")(nn.silu(g) * u)
        return (y, ()) if stats else y


class Relu2Mlp(nn.Module):
    """``down(relu(up(x))^2)``: an MLP of two matrices, no gate (the
    NemotronH family's ``relu2``). ``stats``: -> ``(y, (live,))``, the
    number of hidden units that are positive before the square (float32, no
    gradient): what a kernel that skips dead units would have to compute."""

    width: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, stats: bool = False):
        u = Linear(self.width, self.dtype, name="up")(x)
        y = Linear(x.shape[-1], self.dtype, name="down")(
            jnp.square(nn.relu(u)))
        return (y, (jnp.sum(u > 0, dtype=jnp.float32),)) if stats else y


#: an MLP's form by name: gated with SiLU (three matrices) or a squared
#: ReLU between two. Called with ``stats=True`` each gives ``(y, stats)``,
#: the same tuple of statistics as the form's entry in
#: ``moe.EXPERT_FORMS`` gives for an expert's rows
MLP_FORMS = {"swiglu": SwiGLU, "relu2": Relu2Mlp}


def yarn_frequencies(r: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``r / 2`` pair frequencies of a rotary width ``r`` under YaRN
    (arXiv:2309.00071), as ``transformers``' ``_compute_yarn_parameters``
    computes them: ``f_i = theta^(-2i/r)``; a pair that turns more than
    ``beta_fast`` times over the ``original`` positions keeps ``f_i``, one
    that turns less than ``beta_slow`` times takes ``f_i / factor``, and
    between the two pair indices (floor and ceiling of ``r ln(original / (2
    pi beta)) / (2 ln theta)``) the two are blended linearly. float64 on the
    host: the numbers are the model's constants."""
    f = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def pair(turns):
        return r * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f * (1 - ramp) + f / factor * ramp


def rotary(x: jax.Array, theta: float, inv_freq=None,
           scale: float = 1.0) -> jax.Array:
    """Rotary embedding over the last axis of ``x [..., T, R]``, INTERLEAVED
    pairs: channels ``(2i, 2i+1)`` turn by ``pos * theta^(-2i/R)``, or by
    ``pos * inv_freq[i]`` where the pairs' frequencies are given
    (:func:`yarn_frequencies`); cosine and sine times ``scale`` (YaRN's
    attention factor). (The published ``rope_interleave`` code first moves
    the even channels to the front half and then turns halves; applied to
    queries and keys alike that is this rotation under one fixed permutation
    of the channels, and every ``q . k`` is the same.) Computed in float32,
    returned in ``x.dtype``."""
    t, r = x.shape[-2], x.shape[-1]
    if inv_freq is None:
        inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


#: queries and keys per TILE of the attention kernels (what a grid step
#: fetches): on the v5e, forward and backward at [2, 32, 4096] took 97.0 ms
#: at the op's default of 128, 25.3 at 512, 20.6 at 1024 (PERF.md, PR 26);
#: 2048 does not fit the kernels' 16 MB of VMEM; shorter sequences clamp it.
#: Inside a tile the kernels compute in 256-wide sub-tiles of their own
#: choosing (``ops/attention.py``; PERF.md, PR 27: 19.46 -> 17.83 ms).
#: A window layer takes the same tile: under a band of 512 at T 4,096 a
#: layer-step took 16.83 ms at 1024 and 19.37 at 512 (PERF.md, PR 32)
_ATTN_BLOCK = 1024


class LatentAttention(nn.Module):
    """Multi-head latent attention without a query bottleneck
    (``q_lora_rank`` null). ``q = W_q x`` as ``heads`` of ``nope + rope``;
    ``[c, k_r] = W_kva x`` with ``c`` the ``kv_rank``-wide compressed
    key-value and ``k_r`` ONE rotary key for all heads; ``c <- RMSNorm(c)``;
    ``[k_nope, v] = W_kvb c`` per head; rotary on ``q_rope`` and ``k_r``;
    ``k = [k_nope, k_r]``; causal softmax of ``q . k / sqrt(nope + rope)``;
    the ``heads * v_dim`` output goes through ``W_o``.

    ``qk_norm``: each head's whole query and key (``nope + rope`` wide) go
    through an RMSNorm of their own before the rotary part is turned (so the
    rotary key is turned a head, after its norm). ``out_gate``: each head's
    output is RMS-normalised and multiplied by one sigmoid gate a head
    (``W_g x``) before ``W_o``."""

    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    dtype: Any = jnp.float32
    qk_norm: bool = False
    out_gate: bool = False

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
        if self.qk_norm:
            def turned(a):
                return jnp.concatenate(
                    [a[..., :dn], rotary(a[..., dn:], self.rope_theta)], -1)

            q = turned(RMSNorm(self.eps, self.dtype, name="q_norm")(q))
            k = turned(RMSNorm(self.eps, self.dtype, name="k_norm")(
                jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                    ckr[:, None, :, self.kv_rank:], (b, h, t, dr))], -1)))
        else:
            k_r = rotary(ckr[:, None, :, self.kv_rank:], self.rope_theta)  # [B,1,T,dr]
            q = jnp.concatenate(
                [q[..., :dn], rotary(q[..., dn:], self.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))], axis=-1)
        with jax.named_scope(SCOPE_LM_ATTN):
            o = attention(q, k, kv[..., dn:], causal=True,
                          block_q=_ATTN_BLOCK,
                          block_k=_ATTN_BLOCK)
        o = o.transpose(0, 2, 1, 3)
        if self.out_gate:
            o = HeadGate(self.eps, self.dtype, name="out_gate")(o, x)
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="o_proj")(
                o.reshape(b, t, h * dv))


class HeadGate(nn.Module):
    """``o [B, T, H, dv]`` RMS-normalised a head (one scale over ``dv``;
    not where ``norm`` is False) and multiplied by one sigmoid gate a head,
    ``sigmoid(W_g x)`` with ``W_g [D, H]``."""

    eps: float = 1e-6
    dtype: Any = jnp.float32
    norm: bool = True

    @nn.compact
    def __call__(self, o, x):
        with jax.named_scope(SCOPE_LM_DENSE):
            gate = Linear(o.shape[-2], self.dtype, jnp.float32, name="proj")(x)
        if self.norm:
            o = RMSNorm(self.eps, jnp.float32, name="norm")(o)
        return (o * jax.nn.sigmoid(gate)[..., None]).astype(self.dtype)


def heads_attention(q, k, v, head_dim: int, rotary_dim: int,
                    rope_theta: float = 10000.0, inv_freq=None,
                    rope_scale: float = 1.0, window: Optional[int] = None,
                    scale: Optional[float] = None, dtype: Any = None):
    """Grouped-query attention from projected ``q [B, T, H * head_dim]`` and
    ``k``, ``v [B, T, G * head_dim]`` (heads side by side along the last
    axis) -> ``[B, T, H, head_dim]``: the one way into ``ops.attention`` of
    every module whose query heads share key-value heads. The first
    ``rotary_dim`` channels of every head of ``q`` and ``k`` turn
    (:func:`rotary`), causal softmax of ``q . k * scale`` over ``window``
    keys, under ``fedml.lm.attn`` (``fedml.lm.attn_window`` with a window);
    the head transposes and rotary stay the step's. ``dtype``: what ``q``
    and ``k`` are cast to after they have turned (a caller that hands them
    over in float32)."""
    b, t, d, r = q.shape[0], q.shape[1], head_dim, rotary_dim

    def heads(a):
        return a.reshape(b, t, a.shape[-1] // d, d).transpose(0, 2, 1, 3)

    def turned(a):
        if r:
            first = rotary(a[..., :r], rope_theta, inv_freq, rope_scale)
            a = first if r == d else jnp.concatenate(
                [first, a[..., r:]], axis=-1)
        return a if dtype is None else a.astype(dtype)

    q, k, v = turned(heads(q)), turned(heads(k)), heads(v)
    with jax.named_scope(SCOPE_LM_ATTN if window is None
                         else SCOPE_LM_ATTN_WINDOW):
        o = attention(q, k, v, causal=True, window=window, sm_scale=scale,
                      block_q=_ATTN_BLOCK, block_k=_ATTN_BLOCK)
    return o.transpose(0, 2, 1, 3)


class GroupedAttention(nn.Module):
    """Grouped-query attention, full or under a sliding window. ``q = W_q
    x`` as ``heads`` heads of ``head_dim``, ``k`` and ``v`` as ``kv_heads``;
    query head ``i`` reads key-value head ``i // (heads / kv_heads)``. The
    first ``rotary_dim`` channels of every head of ``q`` and ``k`` turn
    (:func:`rotary`: by ``rope_theta``, or by ``inv_freq`` and ``scale``
    where YaRN gives them), the others pass; ``rotary_dim`` 0: no channel
    turns (a position-free layer). Causal softmax of ``q . k * scale``
    (``None``: ``head_dim^-0.5``) over the ``window`` keys up to the query's
    own (``None``: all of them). ``gate``: each head's output times
    ``sigmoid(x W_g)``, one gate a head and no norm, before ``W_o``. No
    biases."""

    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float = 10000.0
    inv_freq: Optional[tuple] = None
    rope_scale: float = 1.0
    window: Optional[int] = None
    gate: bool = False
    dtype: Any = jnp.float32
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, x):
        b, t, dim = x.shape
        h, g, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope(SCOPE_LM_DENSE):
            q = Linear(h * d, self.dtype, name="q_proj")(x)
            k = Linear(g * d, self.dtype, name="k_proj")(x)
            v = Linear(g * d, self.dtype, name="v_proj")(x)
        o = heads_attention(q, k, v, d, self.rotary_dim, self.rope_theta,
                            self.inv_freq, self.rope_scale, self.window,
                            self.scale)
        if self.gate:
            o = HeadGate(dtype=self.dtype, norm=False, name="out_gate")(o, x)
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="o_proj")(
                o.reshape(b, t, h * d))


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution along ``T``: ``x [B, T, C]``, ``w [K,
    C]`` -> ``y_t = sum_i w[i] x_{t-K+1+i}`` (zeros before position 0), in
    float32."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i] for i in range(k))


def shift_back(x: jax.Array, n: int = 1) -> jax.Array:
    """``x [B, T, ...]`` read ``n`` positions earlier: ``y_t = x_{t-n}``,
    zeros before position 0."""
    if not n:
        return x
    pad = ((0, 0), (n, 0)) + ((0, 0),) * (x.ndim - 2)
    return jnp.pad(x[:, :x.shape[1] - n], pad)


def headwise_conv(u: jax.Array, w: jax.Array, dtype: Any) -> jax.Array:
    """Causal convolution along ``T`` that mixes the channels INSIDE each
    head: ``u [B, T, J, C]``, ``w [K, J, C, C]`` -> ``y_t[j] = sum_i
    u_{t-K+1+i}[j] w[i, j]`` (zeros before position 0), one batched product
    a tap with operands in ``dtype`` and float32 accumulation."""
    k = w.shape[0]
    u, w = u.astype(dtype), w.astype(dtype)
    return sum(jnp.einsum("btjc,jcd->btjd", shift_back(u, k - 1 - i), w[i],
                          preferred_element_type=jnp.float32)
               for i in range(k))


def cca_mix(q, k, v, w0, b0, w1, b1, temp, heads: int, kv_heads: int,
            dtype: Any):
    """What compressed convolutional attention does between its projections
    and its scores (:class:`CompressedConvAttention` has the equations):
    projected ``q [B, T, H e]``, ``k`` and ``v [B, T, G e]`` -> the mixed,
    normalised ``q [B, T, H, e]`` and ``k [B, T, G, e]`` in float32 and
    ``v`` with its later heads read one position earlier."""
    b, t = q.shape[:2]
    h, g, f32 = heads, kv_heads, jnp.float32
    e = q.shape[-1] // h
    m_q = (q.astype(f32).reshape(b, t, g, h // g, e)
           + k.astype(f32).reshape(b, t, g, 1, e)) / 2
    m_k = jnp.mean(m_q, axis=3)
    u = causal_conv(jnp.concatenate([q, k], axis=-1), w0) + b0
    y = headwise_conv(u.reshape(b, t, h + g, e), w1, dtype) + b1

    def unit(a):
        return a * (jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-12) * e ** 0.5)

    late = (g // 2) * e
    return (unit(y[:, :, :h] + m_q.reshape(b, t, h, e)),
            unit(y[:, :, h:] + m_k) * temp[:, None],
            jnp.concatenate([v[..., :late], shift_back(v[..., late:])],
                            axis=-1))


def fan_in_uniform(fan_in: int):
    """``torch.nn.Conv1d``'s and ``torch.nn.Linear``'s default, for weights
    and biases alike: uniform over ``+- fan_in^-0.5``."""
    bound = fan_in ** -0.5

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class CompressedConvAttention(nn.Module):
    """Compressed convolutional attention (arXiv:2510.04476), grouped-query
    form: attention in a latent of ``heads * head_dim`` channels, narrower
    than the model, whose queries and keys are mixed along the sequence and
    inside each head before the scores. ``q~ = W_q x`` as ``heads`` heads of
    ``head_dim``, ``k~ = W_k x`` as ``kv_heads``; the means, taken BEFORE the
    mixing, ``m_q[i] = (q~[i] + k~[i // r]) / 2`` (``r`` query heads a
    key-value head) and ``m_k[g]`` the mean of its group's ``m_q``; ``z =
    [q~ ; k~]`` goes through a depthwise causal convolution of ``conv[0]``
    positions with bias (:func:`causal_conv`) and a head-wise one of
    ``conv[1]`` positions with bias (:func:`headwise_conv`: the positions
    before a sequence's first are zeros for both); ``q = y_q + m_q``, ``k =
    y_k + m_k``; each head of both is set to length ``sqrt(head_dim)``, a
    key head times its learned temperature ``k_temp``, in float32 (seeded
    at 2: at 1 the seeded scores are N(0, 1), attention over a prefix is a
    running mean and every token of a sequence hands the layers after it
    the same vector); rotary over the first ``rotary_dim`` channels; the
    later half of the value heads reads the position BEFORE its own
    (``v_proj``'s
    columns of the first ``kv_heads // 2`` heads are ``W_v1``, the others
    ``W_v2``; a linear map commutes with the shift, so the shifted heads
    are the projection's output read one position earlier); causal softmax
    of ``q . k / sqrt(head_dim)``; ``W_o`` from the latent back to the
    model's width. No bias in a projection. The convolutions start as
    ``torch.nn.Conv1d``'s default does, so that every tap is seen."""

    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float = 10000.0
    conv: tuple = (2, 2)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, dim = x.shape
        h, g, e, f32 = self.heads, self.kv_heads, self.head_dim, jnp.float32
        k0, k1 = self.conv
        with jax.named_scope(SCOPE_LM_DENSE):
            q = Linear(h * e, self.dtype, name="q_proj")(x)
            k = Linear(g * e, self.dtype, name="k_proj")(x)
            v = Linear(g * e, self.dtype, name="v_proj")(x)
        with jax.named_scope(SCOPE_LM_CCA_MIX):
            w0 = self.param("conv0_kernel", fan_in_uniform(k0),
                            (k0, (h + g) * e), f32)
            b0 = self.param("conv0_bias", fan_in_uniform(k0), ((h + g) * e,),
                            f32)
            w1 = self.param("conv1_kernel", fan_in_uniform(k1 * e),
                            (k1, h + g, e, e), f32)
            b1 = self.param("conv1_bias", fan_in_uniform(k1 * e), (h + g, e),
                            f32)
            temp = self.param("k_temp", nn.initializers.constant(2.0), (g,),
                              f32)
            q, k, v = cca_mix(q, k, v, w0, b0, w1, b1, temp, h, g, self.dtype)
        o = heads_attention(q.reshape(b, t, h * e), k.reshape(b, t, g * e), v,
                            e, self.rotary_dim, self.rope_theta,
                            dtype=self.dtype)
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="o_proj")(
                o.reshape(b, t, h * e))


def slow_decay_bias(key: jax.Array, shape, lower_bound: float) -> jax.Array:
    """``dt_bias`` at which the lower-bounded gate rests on a slow decay:
    a channel's ``-g`` at a zero pre-activation is drawn log-uniform over
    ``[0.001, 0.1]`` (``alpha`` 0.999 .. 0.905, the public KDA init's ``dt``
    range at a rate of 1) and the bias is its logit under the bound."""
    rate = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    share = rate / -lower_bound
    return jnp.log(share) - jnp.log1p(-share)


class DeltaAttention(nn.Module):
    """A linear-attention mixer by the delta rule with a per-channel decay
    (Kimi Delta Attention, ``ops/kda.py``). ``q, k, v = SiLU(conv(W x))``,
    a causal depthwise convolution of ``conv`` positions each; heads of
    ``head_dim``; ``q`` and ``k`` L2-normalised a head, ``q`` scaled by
    ``head_dim^-0.5``; the log-decay ``g = lower_bound * sigmoid(exp(A_log)
    * (W_f x + dt_bias))`` a channel (``A_log`` a head), so ``alpha =
    exp(g)`` lies in ``[e^lower_bound, 1)``; ``beta = sigmoid(W_b x)`` a
    head; the scan; then the output's norm and gate (:class:`HeadGate`)
    and ``W_o``. ``dt_bias`` starts where a channel's decay is slow
    (:func:`slow_decay_bias`), as the public KDA code's does: a state that
    is gone after a position or two is no linear attention."""

    heads: int
    head_dim: int
    conv: int = 4
    lower_bound: float = -5.0
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, dim = x.shape
        h, d = self.heads, self.head_dim
        with jax.named_scope(SCOPE_LM_DENSE):
            q, k, v, f = (Linear(h * d, self.dtype, name=n)(x)
                          for n in ("q_proj", "k_proj", "v_proj", "f_proj"))
            beta = Linear(h, self.dtype, jnp.float32, name="b_proj")(x)
        with jax.named_scope(SCOPE_LM_KDA_PREP):
            def heads(a):
                return a.reshape(b, t, h, d).transpose(0, 2, 1, 3)

            def conv(a, name):
                w = self.param(name, _normal(), (self.conv, h * d),
                               jnp.float32)
                return heads(nn.silu(causal_conv(a, w)))

            def unit(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + self.eps)

            q = unit(conv(q, "q_conv")) * d ** -0.5
            k = unit(conv(k, "k_conv"))
            v = conv(v, "v_conv")
            a_log = self.param("A_log", nn.initializers.zeros, (h,),
                               jnp.float32)
            dt_bias = self.param(
                "dt_bias",
                lambda key, shape, dtype: slow_decay_bias(
                    key, shape, self.lower_bound).astype(dtype),
                (h * d,), jnp.float32)
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None, None]
                * heads(f.astype(jnp.float32) + dt_bias))
            beta = jax.nn.sigmoid(beta).transpose(0, 2, 1)
        with jax.named_scope(SCOPE_LM_KDA):
            o = kda_chunked(q.astype(self.dtype), k.astype(self.dtype),
                            v.astype(self.dtype), g, beta, dtype=self.dtype)
        with jax.named_scope(SCOPE_LM_KDA_PREP):
            o = HeadGate(self.eps, self.dtype, name="out_gate")(
                o.transpose(0, 2, 1, 3), x)
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="o_proj")(
                o.reshape(b, t, h * d))


def log_uniform_steps(key: jax.Array, shape, low: float = 0.001,
                      high: float = 0.1) -> jax.Array:
    """``dt_bias`` of Mamba-2's public init: a head's step ``dt`` at a zero
    pre-activation is drawn log-uniform over ``[low, high]`` and the bias is
    its inverse softplus, ``dt + log(1 - exp(-dt))``."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(low),
                                    jnp.log(high)))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """A Mamba-2 state-space mixer (arXiv:2405.21060; one group: ``B`` and
    ``C`` are shared by all heads). ``[z | xBC | dt] = W_in u``, widths
    ``H P``, ``H P + 2 N``, ``H``; ``xBC = SiLU(conv(xBC) + b_conv)``, a
    causal depthwise convolution of ``conv`` positions over the channels of
    ``x``, ``B``, ``C``; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head; the recurrence (``ops/ssd.py``) with the skip ``D
    x``; ``y = RMSNorm(y * SiLU(z)) * w``, the gate BEFORE the norm and one
    norm over all ``H P`` channels; ``out = W_out y``. The recurrence's
    parameters start as the public code's do: ``dt`` log-uniform over
    [0.001, 0.1] (:func:`log_uniform_steps`), ``A`` uniform over [1, 16],
    ``D`` 1; the convolution as ``torch.nn.Conv1d``'s default, weights and
    bias uniform over ``+- conv^-0.5``.

    The joint projection is issued as one product a consumer (``z``, ``x``,
    ``B | C``, ``dt``) over column slices of the one ``in_proj/kernel``
    (:class:`ColumnLinear`; the depthwise convolution then runs over ``x``
    and over ``B | C`` with their own columns of ``conv_kernel``, the same
    function channel by channel), because XLA, which keeps or computes
    again an op's WHOLE result, computed a single ``[T, 2 H P + 2 N + H]``
    product 9.3 times a layer-step for its consumers' different lifetimes
    where four passes are needed: 36% of the bf16 peak on the required
    work, 73% as four products (``PERF.md``, PR 38).

    The ``counters`` collection carries ``decay``, the mean over positions
    and heads of ``exp(dt A)`` summed over the training steps, and
    ``steps``: how long a state lives (``decay^Q`` is what is left of it a
    chunk of ``Q`` positions later)."""

    heads: int
    head_dim: int
    state: int
    conv: int = 4
    chunk: int = SSD_CHUNK
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, train: bool = False):
        b, t, dim = u.shape
        h, p, n = self.heads, self.head_dim, self.state
        inner, f32 = h * p, jnp.float32
        with jax.named_scope(SCOPE_LM_DENSE):
            z, x, bc, dt = ColumnLinear((inner, inner, 2 * n, h), self.dtype,
                                        name="in_proj")(u)
        with jax.named_scope(SCOPE_LM_SSD_PREP):
            bound = self.conv ** -0.5

            def uniform(key, shape, dtype):
                return jax.random.uniform(key, shape, dtype, -bound, bound)

            w = self.param("conv_kernel", uniform, (self.conv, inner + 2 * n),
                           f32)
            w_b = self.param("conv_bias", uniform, (inner + 2 * n,), f32)
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(jax.random.uniform(
                    key, shape, dtype, 1.0, 16.0)), (h,), f32)
            dt_bias = self.param(
                "dt_bias", lambda key, shape, dtype: log_uniform_steps(
                    key, shape).astype(dtype), (h,), f32)
            skip = self.param("D", nn.initializers.ones, (h,), f32)
            x = nn.silu(causal_conv(x, w[:, :inner]) + w_b[:inner])
            x = x.astype(self.dtype).reshape(b, t, h, p)
            bc = nn.silu(causal_conv(bc, w[:, inner:]) + w_b[inner:])
            bc = bc.astype(self.dtype)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            decay = self.variable(COUNTERS, "decay", lambda: jnp.zeros((), f32))
            steps = self.variable(COUNTERS, "steps", lambda: jnp.zeros((), f32))
            if train and not self.is_initializing():
                decay.value = decay.value + jnp.mean(
                    jnp.exp(-dt * jnp.exp(a_log)))
                steps.value = steps.value + 1.0
        with jax.named_scope(SCOPE_LM_SSD):
            y = ssd_chunked(x, dt, a_log, bc[..., :n], bc[..., n:], skip,
                            chunk=self.chunk, dtype=self.dtype)
        with jax.named_scope(SCOPE_LM_SSD_PREP):
            y = RMSNorm(self.eps, self.dtype, name="norm")(
                y.reshape(b, t, inner) * nn.silu(z.astype(f32)))
        with jax.named_scope(SCOPE_LM_DENSE):
            return Linear(dim, self.dtype, name="out_proj")(y)
