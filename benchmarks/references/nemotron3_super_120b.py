"""Plain reference for ``nemotron3_super_120b``: one of 64 chips' share of
the first pipeline stage of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(``model_type nemotron_h``), written out in ``jax.numpy``. Every size is read
from the configuration's ``model`` block; the equations are the published
config's and the NemotronH family's public modelling code's, with what the
keys leave open listed under ``assumed`` in the configuration's file:

- a layer is ONE pre-norm residual sub-layer, ``h = h + f(RMSNorm(h))``,
  ``f`` by ``mixers[i]`` / ``mlps[i]`` (the published
  ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``*`` attention, ``E``
  a sparse MLP); an embedding, a final RMSNorm and an untied head; eps
  ``layer_norm_epsilon``; no learned or rotary positions; no bias but the
  convolution's;
- ``M`` (:func:`mamba`; Mamba-2, arXiv:2405.21060, ``G`` groups): ``[z | x |
  B | C | dt] = W_in u`` of widths ``H P``, ``H P``, ``G N``, ``G N``, ``H``;
  ``[x | B | C] = silu(conv([x | B | C]) + b_conv)``, the convolution causal
  and depthwise over ``ssd_conv`` positions; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` a head; head ``h`` reads ``B``, ``C`` of
  group ``h // (H / G)``; then, a head, with the state ``S [P, N]`` zero
  before position 0, **token by token**::

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
      y_t = S_t C_t + D x_t

  a ``lax.scan`` over positions inside a rematerialised scan over blocks;
  ``y = RMSNorm_g(y * silu(z)) * w``, the gate BEFORE the norm and the
  norm's statistics over each GROUP's ``H P / G`` channels by itself;
  ``out = W_out y``. This chip holds ONE group (16 heads), so its share is a
  one-group mixer; the same function computes the whole layer at a small
  size, and :func:`mamba_share` cuts a group's share out of a whole
  layer's weights (the shares-add-up test);
- ``*`` (:func:`attention`): grouped-query attention, ``heads`` query heads
  over ``kv_heads`` key-value heads of ``v_dim``, no rotary, causal softmax
  of ``q . k * v_dim^-0.5``: the full score matrix of every head, a block
  of queries at a time; :func:`attention_share` cuts a key-value head with
  its query heads out of a whole layer;
- ``E`` (:func:`sparse_parts`, :func:`sparse_mlp`): ``s = sigmoid(W_r x)``
  in float32 over all ``n_routed`` experts; the ``top_k`` with the largest
  ``s + b`` (``b`` the correction bias: no gradient; no group limit);
  weights ``s_i / sum of the chosen s`` times ``routed_scaling``; ``x_l =
  W_1 x`` in the latent; an expert is ``W_down relu(W_up x_l)^2``; ``out =
  W_2 (sum_i w_i expert_i(x_l)) + W_sd relu(W_su x)^2``, the shared MLP on
  the full width. Every HELD expert computes every token, with the weight
  zero where it was not chosen: a loop over the held experts, no sort, no
  capacity; :func:`experts_share` cuts a range of experts out of a layer;
- loss: softmax cross-entropy of the next id, averaged over the tokens of
  the real sequences of a batch; plain SGD, no momentum.

Departures from the published model, each also in the configuration's file:
(1) depth: the first eleven entries of the pattern; (2) the share: one group
of the mixers' heads, one key-value head with 4 query heads, experts
``held_first .. held_first + held_count - 1``; the router keeps its width,
its choices and its normalisation; what the absent chips would add is left
out and the partial sum goes on; (3) the vocabulary is this chip's slice;
(4) the final norm and the head sit on this stage; (5) each layer, each block
of queries, each block of positions of the scan and each expert is
recomputed in the backward pass (``jax.checkpoint``): memory, not values;
(6) ``decay`` / ``expert_rows`` / ``live_units`` / ``steps`` in the
``counters`` collection count as the program's variable tree does; (7) no
multi-token-prediction module.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves. ``local_train`` returns HOST trees
(``harness/check.py`` keeps the state, the new tree, its weighted part and
the sum at once: four copies of 2.8 GB beside a client's training do not fit
the chip).

The configuration states: a bfloat16 module (matmul operands and activations
bf16, float32 accumulation), router scores, softmax and the scan's state in
float32, norm statistics, ``dt`` and the decays in float32, float32
parameters and aggregation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision (the recurrence's products take bf16 operands, its state stays
#: float32). The controls have to fail. Three are the nearest precision below
#: one the configuration states: ``act_fp8_scaled`` rounds the operands of
#: every module matmul to e4m3 after scaling the largest magnitude to 128,
#: gradients passing unrounded; ``params_bf16`` keeps parameters and
#: aggregate in bf16, ``local_bf16`` the parameters through local training.
#: Three are not precisions but what the configuration exists for:
#: ``relu_plain`` is ``stated`` with the experts and the shared MLP without
#: the square (``relu`` where ``relu2`` is published); ``scale_plain`` is
#: ``stated`` with a ``routed_scaling`` of 1; ``state_cut`` is ``stated``
#: with the recurrence's state set to zero every ``ssd_chunk`` positions
#: (what a program reads that loses the carry between its chunks). All
#: rounding is by ``lax.reduce_precision``, which XLA keeps.
VARIANTS = ("reference", "stated", "act_fp8_scaled", "params_bf16",
            "local_bf16", "relu_plain", "scale_plain", "state_cut")
CONTROLS = ("act_fp8_scaled", "params_bf16", "local_bf16", "relu_plain",
            "scale_plain", "state_cut")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix
_Q_BLOCK = 512
#: steps of :func:`balanced_bias`, and the sequences it is balanced on
_BALANCE_STEPS = 300
_BALANCE_SEQS = 2


def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix and the table
    normal(0, 0.02), norm scales 1; the recurrence as Mamba-2's public code
    starts it: ``dt`` log-uniform over [0.001, 0.1] with ``dt_bias`` its
    inverse softplus, ``A_log = log(U[1, 16])``, ``D`` 1, the convolution's
    weights and bias uniform over ``+- ssd_conv^-0.5``; counters 0; then each
    sparse layer's correction bias from the same key, BALANCED
    (:func:`_balanced`): the published bias is a trained one, which holds the
    experts' loads even, and a seeded router without it sends a few experts
    most of the tokens (a held expert 47% of them on one seed read, none on
    another: ``PERF.md``, PR 44)."""
    m = config["model"]
    d, h, g, hd = (int(m[k]) for k in ("dim", "heads", "kv_heads", "v_dim"))
    sh, sp, sn, kc = (int(m[k]) for k in ("ssd_heads", "ssd_head_dim",
                                          "ssd_state", "ssd_conv"))
    routed, held = int(m["n_routed"]), int(m["held_count"])
    latent, width = int(m["moe_latent"]), int(m["expert_width"])
    shared, vocab = int(m["shared_width"]), int(config["data"]["vocab"])
    key, data_key = jax.random.split(key)
    keys = iter(jax.random.split(key, 12 * int(m["layers"]) + 4))

    def w(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(a, b):
        return {"kernel": w(a, b)}

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    zero = jnp.zeros((), jnp.float32)
    params, stats = {"embed": w(vocab, d)}, {}
    for i, (mixer, mlp) in enumerate(zip(m["mixers"], m["mlps"])):
        layer, name = {}, f"layer_{i}"
        if mixer != "none":
            layer["attn_norm"] = ones(d)
        if mixer == "ssd":
            inner, bound = sh * sp, kc ** -0.5
            dt = jnp.exp(uniform((sh,), jnp.log(0.001), jnp.log(0.1)))
            layer["ssd"] = {
                "in_proj": lin(d, 2 * inner + 2 * sn + sh),
                "conv_kernel": uniform((kc, inner + 2 * sn), -bound, bound),
                "conv_bias": uniform((inner + 2 * sn,), -bound, bound),
                "A_log": jnp.log(uniform((sh,), 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((sh,), jnp.float32),
                "norm": ones(inner), "out_proj": lin(inner, d)}
            stats[name] = {"ssd": {"decay": zero, "steps": zero}}
        elif mixer == "full":
            layer["attn"] = {"q_proj": lin(d, h * hd), "k_proj": lin(d, g * hd),
                             "v_proj": lin(d, g * hd), "o_proj": lin(h * hd, d)}
        elif mixer != "none":
            raise ValueError(f"this reference has no mixer {mixer!r}")
        if mlp == "sparse":
            layer["mlp_norm"] = ones(d)
            layer["mlp"] = {
                "shared": {"up": lin(d, shared), "down": lin(shared, d)},
                "latent_in": lin(d, latent), "latent_out": lin(latent, d),
                "router": w(d, routed),
                "e_score_correction_bias": jnp.zeros((routed,), jnp.float32),
                "up": w(held, latent, width), "down": w(held, width, latent)}
            stats[name] = {"mlp": {
                "expert_rows": jnp.zeros((held,), jnp.float32),
                "live_units": zero, "steps": zero}}
        elif mlp != "none":
            raise ValueError(f"this reference has no MLP {mlp!r}")
        params[name] = layer
    params["final_norm"] = ones(d)
    params["lm_head"] = lin(d, vocab)
    return {"params": _balanced(config, params, data_key), "counters": stats}


def _calibration_ids(key, config: dict):
    """``_BALANCE_SEQS`` sequences by the traffic's law, each a client's of
    its own: Zipf over a permutation of the slice."""
    data = config["data"]
    vocab, t = int(data["vocab"]), int(data["seq_len"])
    law = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        data.get("zipf_exponent", 1.0))
    cdf = jnp.asarray(np.cumsum(law / law.sum()), jnp.float32)

    def one(k):
        k_perm, k_draw = jax.random.split(k)
        ranks = jnp.searchsorted(cdf, jax.random.uniform(k_draw, (t,)))
        return jax.random.permutation(k_perm, vocab)[
            jnp.minimum(ranks, vocab - 1)]

    return jnp.stack([one(k) for k in jax.random.split(key, _BALANCE_SEQS)])


def balanced_bias(s, top_k: int):
    """``s [N, E]`` (the scores) -> the bias ``[E]`` (mean zero) under which
    the ``top_k`` largest of ``s + bias`` give every expert about ``N top_k
    / E`` tokens: an expert's bias falls by its excess load (held to one
    even share a step), in steps that start at the scores' own spread and
    shrink: the published balancing rule run to rest on one batch."""
    e = s.shape[-1]
    spread = jnp.std(s)

    def step(i, bias):
        biased = s + bias
        kth = lax.top_k(biased, top_k)[0][:, -1:]
        load = jnp.mean((biased >= kth).astype(jnp.float32), axis=0)
        excess = jnp.clip(load * (e / top_k) - 1.0, -1.0, 1.0)
        return bias - spread * 0.98 ** i * excess

    bias = lax.fori_loop(0, _BALANCE_STEPS, step, jnp.zeros((e,), jnp.float32))
    return bias - jnp.mean(bias)


def _balanced(config: dict, params: dict, key) -> dict:
    """``params`` with each sparse layer's ``e_score_correction_bias``
    balanced on one seeded batch, layer after layer (a layer's input follows
    from the choices of the layers before it); float32 at the highest matmul
    precision. A function of the key alone; the bias then stays as seeded
    (no update rate is published)."""
    forward = _forward(config, "reference")
    with jax.default_matmul_precision("highest"):
        return forward.balance(dict(params), _calibration_ids(key, config))


def ops_of(variant: str):
    """(activation dtype, matmul, the operands' rounding) of one variant."""
    if variant == "reference":
        return (jnp.float32, lambda a, b: jnp.matmul(
            a, b, precision=lax.Precision.HIGHEST), lambda a: a)

    def operand(a):
        if variant == "act_fp8_scaled":
            a = a.astype(jnp.float32)
            scale = 128.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
            a = a + lax.stop_gradient(_round_to(a * scale, 4, 3) / scale - a)
        return a.astype(jnp.bfloat16)

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b),
                          preferred_element_type=jnp.float32)

    return jnp.bfloat16, mm, operand


def _rms(x, scale, eps, act, groups: int = 1):
    """RMSNorm over the last axis, or over each of its ``groups`` equal
    parts by itself; statistics in float32."""
    xf = x.astype(jnp.float32)
    xg = xf.reshape(xf.shape[:-1] + (groups, xf.shape[-1] // groups))
    y = xg * lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return (y.reshape(xf.shape) * scale).astype(act)


def recurrence(x, dt, a_log, b, c, d, operand=lambda a: a, cut: int = 0,
               block: int = 256):
    """The recurrence, one position at a time: ``x [B, T, H, P]``, ``dt [B,
    T, H]`` (after its softplus), ``a_log, d [H]``, ``b, c [B, T, G, N]``
    (head ``h`` reads group ``h // (H / G)``) -> ``y [B, T, H, P]`` float32.
    ``operand`` rounds what the configuration's precision computes in the
    module's dtype (the factors of the write and of the read); the scan runs
    in rematerialised blocks of ``block`` positions, and ``cut`` starts
    every block of ``cut`` positions from a zero state (a control)."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    blk = min(cut or block, t)
    rate = -jnp.exp(a_log.astype(f32))
    b, c = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (b, c))

    def low(a):
        return operand(a).astype(f32)

    def position(s, inp):
        xt, dtt, bt, ct = inp
        write = low(dtt[..., None] * xt)[..., None] * low(bt)[:, :, None, :]
        s = s * jnp.exp(dtt * rate)[..., None, None] + write
        return s, jnp.sum(low(s) * low(ct)[:, :, None, :], axis=-1)

    @jax.checkpoint
    def one(s, xs):
        return lax.scan(position, jnp.zeros_like(s) if cut else s, xs)

    def blocks(a):       # [B, T, ...] -> [T/blk, blk, B, ...]
        a = jnp.moveaxis(a.astype(f32), 1, 0)
        return a.reshape((t // blk, blk) + a.shape[1:])

    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    _, y = lax.scan(one, s0, tuple(blocks(a) for a in (x, dt, b, c)))
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)
    return y + d.astype(f32)[:, None] * x.astype(f32)


def mamba(x, p, ops, *, heads: int, head_dim: int, state: int, eps: float,
          groups: int = 1, norm_groups: int = None, cut: int = 0,
          block: int = 256):
    """A Mamba-2 mixer of ``heads`` heads in ``groups`` groups on ``x [B, T,
    D]`` -> ``(out [B, T, D], mean decay)``; ``norm_groups``: the parts the
    gated norm takes its statistics over (None: one a group, as published)."""
    act, mm, operand = ops
    bsz, t, _ = x.shape
    inner, gn = heads * head_dim, groups * state
    zxbcdt = mm(x, p["in_proj"]["kernel"]).astype(act)
    z = zxbcdt[..., :inner].astype(jnp.float32)
    w, bias = p["conv_kernel"], p["conv_bias"]
    kc = w.shape[0]
    xbc = jnp.pad(zxbcdt[..., inner:2 * inner + 2 * gn].astype(jnp.float32),
                  ((0, 0), (kc - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xbc[:, i:i + t] * w[i] for i in range(kc))
                      + bias).astype(act)
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * gn:].astype(jnp.float32)
                         + p["dt_bias"])
    y = recurrence(
        xbc[..., :inner].reshape(bsz, t, heads, head_dim), dt, p["A_log"],
        xbc[..., inner:inner + gn].reshape(bsz, t, groups, state),
        xbc[..., inner + gn:].reshape(bsz, t, groups, state), p["D"],
        operand, cut, block)
    y = _rms(y.reshape(bsz, t, inner) * jax.nn.silu(z), p["norm"]["scale"],
             eps, act, norm_groups or groups)
    decay = jnp.mean(jnp.exp(-dt * jnp.exp(p["A_log"])))
    return mm(y, p["out_proj"]["kernel"]).astype(act), decay


def mamba_share(p, *, heads: int, head_dim: int, state: int, groups: int,
                group: int) -> dict:
    """One group's share of a whole mixer's weights: its ``heads / groups``
    heads' columns of ``W_in`` (``z``, ``x``, ``dt``) and its own ``B``,
    ``C``, their convolution channels, its heads' ``A_log`` / ``D`` /
    ``dt_bias``, its channels of the gated norm and its rows of ``W_out``:
    a one-group mixer's tree."""
    inner, hg = heads * head_dim, heads // groups
    ch = slice(group * hg * head_dim, (group + 1) * hg * head_dim)
    hs = slice(group * hg, (group + 1) * hg)
    gs = slice(group * state, (group + 1) * state)
    w_in, gn = p["in_proj"]["kernel"], groups * state

    def cols(a):          # [..., z | x | B | C] channels of the group
        x, b, c = a[..., :inner], a[..., inner:inner + gn], a[..., inner + gn:]
        return jnp.concatenate([x[..., ch], b[..., gs], c[..., gs]], axis=-1)

    return {
        "in_proj": {"kernel": jnp.concatenate(
            [w_in[:, :inner][:, ch], cols(w_in[:, inner:2 * inner + 2 * gn]),
             w_in[:, 2 * inner + 2 * gn:][:, hs]], axis=1)},
        "conv_kernel": cols(p["conv_kernel"]), "conv_bias": cols(p["conv_bias"]),
        "A_log": p["A_log"][hs], "dt_bias": p["dt_bias"][hs], "D": p["D"][hs],
        "norm": {"scale": p["norm"]["scale"][ch]},
        "out_proj": {"kernel": p["out_proj"]["kernel"][ch]}}


def attention(x, p, ops, *, heads: int, kv_heads: int, head_dim: int):
    """Grouped-query attention without positions on ``x [B, T, D]``: every
    head's full score matrix, a block of queries at a time; softmax in
    float32."""
    act, mm, _ = ops
    bsz, t, _ = x.shape
    bq, scale = min(_Q_BLOCK, t), head_dim ** -0.5

    def split(a, n):
        return mm(x, a["kernel"]).astype(act).reshape(
            bsz, t, n, head_dim).transpose(0, 2, 1, 3)

    q = split(p["q_proj"], heads)
    k, v = (jnp.repeat(split(p[n], kv_heads), heads // kv_heads, axis=1)
            for n in ("k_proj", "v_proj"))

    @jax.checkpoint
    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, bq, axis=2)
        s = mm(qb, jnp.swapaxes(k, -1, -2)).astype(jnp.float32) * scale
        seen = (start + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(pr.astype(act), v).astype(act)

    o = lax.map(block, jnp.arange(0, t, bq))             # [T/bq, B, H, bq, d]
    o = jnp.moveaxis(o, 0, 2).reshape(bsz, heads, t, head_dim)
    return mm(o.transpose(0, 2, 1, 3).reshape(bsz, t, heads * head_dim),
              p["o_proj"]["kernel"]).astype(act)


def attention_share(p, *, heads: int, kv_heads: int, head_dim: int,
                    kv: slice) -> dict:
    """The key-value heads ``kv`` of a whole layer with the query heads
    they serve: their columns of ``W_q``, ``W_k``, ``W_v`` and their rows of
    ``W_o``."""
    per = heads // kv_heads
    first, last = kv.start, kv.stop
    qs = slice(first * per * head_dim, last * per * head_dim)
    ks = slice(first * head_dim, last * head_dim)
    return {"q_proj": {"kernel": p["q_proj"]["kernel"][:, qs]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, ks]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, ks]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][qs]}}


def sparse_parts(x, p, ops, *, top_k: int, first: int, scaling: float,
                 square: bool = True):
    """The parts of a sparse MLP on ``x [N, D]`` for the experts held here
    (``p["up"]`` holds experts ``first .. first + held - 1``): ->
    ``(routed [N, L] float32, the weighted sum of the held experts' outputs
    IN THE LATENT; shared [N, D], the shared MLP; rows [held]; idx [N, k];
    live: the chosen rows' and the shared MLP's hidden units that are
    positive, over their count)``."""
    act, mm, _ = ops
    n_routed, held = p["router"].shape[-1], p["up"].shape[0]

    def relu2(u):
        r = jax.nn.relu(u)
        return r * r if square else r

    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p["router"],
                                  precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(lax.stop_gradient(s + p["e_score_correction_bias"]),
                       top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = chosen / jnp.sum(chosen, -1, keepdims=True) * scaling
    # weight of every expert on every token, zero where not chosen
    full = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=jnp.float32)
                   * weights[..., None], axis=1)                      # [N, E]
    took = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=jnp.float32), axis=1)
    mine, took = (a[:, first:first + held] for a in (full, took))
    rows = jnp.sum(took, axis=0)
    xl = mm(x, p["latent_in"]["kernel"]).astype(act)

    @jax.checkpoint
    def one(carry, e):
        acc, live = carry
        w_e, t_e = (lax.dynamic_index_in_dim(a, e, axis=1, keepdims=False)
                    for a in (mine, took))
        u = mm(xl, p["up"][e]).astype(act)
        y = mm(relu2(u), p["down"][e]).astype(act)
        return (acc + w_e[:, None] * y.astype(jnp.float32),
                live + jnp.sum((u > 0) * t_e[:, None])), None

    (routed, live), _ = lax.scan(
        one, (jnp.zeros(xl.shape, jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(held))
    su = mm(x, p["shared"]["up"]["kernel"]).astype(act)
    shared = mm(relu2(su), p["shared"]["down"]["kernel"]).astype(act)
    units = jnp.sum(rows) * p["up"].shape[-1] + su.shape[0] * su.shape[1]
    live = lax.stop_gradient((live + jnp.sum(su > 0)) / jnp.maximum(units, 1.0))
    return routed, shared, rows, idx, live


def sparse_mlp(x, p, ops, **kw):
    """``W_2 (the held experts' weighted sum in the latent) + the shared
    MLP`` on ``x [B, T, D]`` -> ``(out, rows, idx, live)``."""
    act, mm, _ = ops
    bsz, t, d = x.shape
    routed, shared, rows, idx, live = sparse_parts(x.reshape(bsz * t, d), p,
                                                   ops, **kw)
    out = mm(routed.astype(act), p["latent_out"]["kernel"]).astype(act) + shared
    return out.reshape(bsz, t, d), rows, idx, live


def experts_share(p, first: int, count: int) -> dict:
    """Experts ``first .. first + count - 1`` of a layer that holds more,
    with the router, both projections and the shared MLP whole."""
    return {**p, "up": p["up"][first:first + count],
            "down": p["down"][first:first + count]}


def _forward(config: dict, variant: str):
    m = config["model"]
    eps = float(m["eps"])
    ops = ops_of(variant)
    act, mm, _ = ops
    mixer_kw = dict(heads=int(m["ssd_heads"]), head_dim=int(m["ssd_head_dim"]),
                    state=int(m["ssd_state"]), eps=eps,
                    block=int(m["ssd_chunk"]),
                    cut=int(m["ssd_chunk"]) if variant == "state_cut" else 0)
    attn_kw = dict(heads=int(m["heads"]), kv_heads=int(m["kv_heads"]),
                   head_dim=int(m["v_dim"]))
    moe_kw = dict(top_k=int(m["top_k"]), first=int(m["held_first"]),
                  scaling=(1.0 if variant == "scale_plain"
                           else float(m["routed_scaling"])),
                  square=variant != "relu_plain")

    def one_layer(x, p, mixer, mlp, balance=False):
        """-> (x, seen, p): with ``balance`` the layer's correction bias is
        first set from the scores of its own input (:func:`balanced_bias`)."""
        seen = {}
        if mixer != "none":
            y = _rms(x, p["attn_norm"]["scale"], eps, act)
            if mixer == "ssd":
                y, seen["decay"] = mamba(y, p["ssd"], ops, **mixer_kw)
            else:
                y = attention(y, p["attn"], ops, **attn_kw)
            x = x + y
        if mlp != "none":
            y = _rms(x, p["mlp_norm"]["scale"], eps, act)
            if balance:
                s = jax.nn.sigmoid(jnp.matmul(
                    y.reshape(-1, y.shape[-1]).astype(jnp.float32),
                    p["mlp"]["router"], precision=lax.Precision.HIGHEST))
                p = {**p, "mlp": {**p["mlp"], "e_score_correction_bias":
                                  balanced_bias(s, moe_kw["top_k"])}}
            y, seen["rows"], _, seen["live"] = sparse_mlp(
                y, p["mlp"], ops, **moe_kw)
            x = x + y
        return x, seen, p

    def balance(params, ids):
        x = params["embed"][ids].astype(act)
        for i, (mixer, mlp) in enumerate(zip(m["mixers"], m["mlps"])):
            x, _, params[f"layer_{i}"] = one_layer(
                x, params[f"layer_{i}"], mixer, mlp, balance=True)
        return params

    def forward(params, stats, ids):
        x = params["embed"][ids].astype(act)
        new_stats = {}
        for i, (mixer, mlp) in enumerate(zip(m["mixers"], m["mlps"])):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, p, mixer=mixer, mlp=mlp):
                return one_layer(x, p, mixer, mlp)[:2]

            x, seen = layer(x, params[name])
            if "decay" in seen:
                old = stats[name]["ssd"]
                new_stats[name] = {"ssd": {"decay": old["decay"] + seen["decay"],
                                           "steps": old["steps"] + 1.0}}
            if "rows" in seen:
                old = stats[name]["mlp"]
                new_stats[name] = {"mlp": {
                    "expert_rows": old["expert_rows"] + seen["rows"],
                    "live_units": old["live_units"] + seen["live"],
                    "steps": old["steps"] + 1.0}}
        x = _rms(x, params["final_norm"]["scale"], eps, act)
        return (mm(x, params["lm_head"]["kernel"]).astype(jnp.float32),
                new_stats)

    forward.balance = balance
    return forward


def _make(config: dict, variant: str):
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    forward = _forward(config, variant)
    lr = float(config["recipe"]["lr"])
    if float(config["recipe"]["momentum"]):
        raise ValueError("this reference is plain SGD: momentum must be 0")
    store = _bf16_values if variant in _STORE_BF16 else (lambda t: t)

    def loss_fn(params, stats, bx, by, bm):
        logits, new_stats = forward(params, stats, bx)
        logz = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(logz, by[..., None], axis=-1)[..., 0]
        w = jnp.broadcast_to(bm[:, None], per.shape)
        return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0), new_stats

    def step(carry, batch):
        params, stats = carry
        bx, by, bm, live = batch
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, stats, bx, by, bm)
        new_params = store(jax.tree.map(lambda p, g: p - lr * g, params, grads))
        keep = lambda n, o: jax.tree.map(
            lambda a, b: jnp.where(live, a, b), n, o)
        return ((keep(new_params, params), keep(new_stats, stats)),
                jnp.where(live, loss, 0.0))

    def local_train(params, stats, xs, ys, ms, steps_real):
        live = jnp.arange(xs.shape[1]) < steps_real

        def epoch(carry, ep):
            carry, losses = lax.scan(step, carry, (*ep, live))
            return carry, jnp.sum(losses) / jnp.maximum(steps_real, 1)

        (params, stats), ep_losses = lax.scan(
            epoch, (store(params), stats), (xs, ys, ms))
        return params, stats, ep_losses[-1]

    if variant == "reference":
        def local_train_highest(*args):
            with jax.default_matmul_precision("highest"):
                return local_train(*args)
        return jax.jit(local_train_highest)
    return jax.jit(local_train)


_built: dict = {}


def _free_host_memory():
    """Before the first client: the comparison that follows holds the
    seeded, the program's and the reference's trees and a float64 copy of
    each (25 GB at 701 M parameters) on a machine of 40 GiB, so whatever the
    process no longer needs goes first: every compiled program and trace
    cache (the timed program's among them: its API is closed by now), the
    garbage the collector was told to skip, and the heap pages the allocator
    keeps for reuse."""
    import ctypes
    import gc

    jax.clear_caches()
    gc.unfreeze()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def local_train(config: dict, variables: dict, xs, ys, ms, steps_real,
                variant: str = "reference"):
    """One client's local training from ``variables``; -> (variables, loss),
    the variables as HOST arrays (the module's note on memory)."""
    key = (config["name"], variant)
    if key not in _built:
        _free_host_memory()
        _built[key] = _make(config, variant)
    params, stats, loss = _built[key](
        variables["params"], variables["counters"], jnp.asarray(xs),
        jnp.asarray(ys), jnp.asarray(ms), jnp.int32(steps_real))
    return jax.device_get({"params": params, "counters": stats}), loss
