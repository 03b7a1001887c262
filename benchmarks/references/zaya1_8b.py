"""Plain reference for ``zaya1_8b``: one of two expert-parallel chips' share
of the first layers of Zyphra/ZAYA1-8B (``model_type`` ``zaya``), written out
in ``jax.numpy``. Every size is read from the configuration's ``model``
block; the equations are ISSUE 39's, from the published config, its
``described_as`` and the two papers it rests on (compressed convolutional
attention, arXiv:2510.04476; the ZAYA1 router and residual scaling,
arXiv:2511.17127), with what the config's keys leave open listed under
``assumed`` in the configuration's file. ``d`` the model's width, ``H``
query heads over ``G`` key-value heads of ``e`` channels, ``r = H / G``:

- a layer is two sub-layers, each followed by the scaled residual ``h <-
  (a_r * h + b_r) + (a_b * branch + b_b)``, four learned vectors of ``d``
  (scales 1 and biases 0 at the seed);
- the CCA sub-layer, ``a = RMSNorm(h)``: ``q~ = a W_q`` as ``[T, H, e]``,
  ``k~ = a W_k`` as ``[T, G, e]``; the means, BEFORE any mixing: ``m_q[t, i]
  = (q~[t, i] + k~[t, i // r]) / 2``, ``m_k[t, g]`` the mean of ``m_q[t, i]``
  over the ``r`` query heads of group ``g``; ``z = [q~ ; k~]``, ``H + G``
  heads; depthwise over ``K0`` positions ``u[t, c] = sum_i w0[i, c] z[t - K0
  + 1 + i, c] + b0[c]`` (the LAST tap is the position's own); head-wise over
  ``K1`` positions ``y[t, j, :] = sum_i u[t - K1 + 1 + i, j, :] B[i, j] +
  b1[j, :]`` with ``B[i, j]`` an ``e x e`` matrix; positions before a
  sequence's first are zeros for both (written as shifted sums, a position
  at a time); ``q = y_q + m_q``, ``k = y_k + m_k``; each head to length
  ``sqrt(e)``, a key head times its temperature ``tau[g]``, in float32;
  rotary over the first ``rope`` channels of every head at ``rope_theta``;
  ``v = a W_v`` as ``[T, G, e]`` whose heads ``G // 2 ..`` are read one
  position earlier (``a[-1] = 0``); causal softmax of ``q . k / sqrt(e)``,
  query head ``i`` on key-value head ``i // r`` (the heads repeated by
  index, every head's whole score row a block of queries at a time);
  ``W_o`` from ``H e`` back to ``d``;
- the sparse sub-layer, ``m = RMSNorm(h)``, layer ``l``: ``s_l = m W_d +
  b_d`` in ``router_hidden`` channels and, for ``l > 0``, ``s_l += gamma_l
  s_{l-1}`` (what the router of the layer before ended this step with, its
  own carry in it); ``r = RMSNorm(s_l)``; two layers ``r <- GELU(r W + b)``
  (erf); ``logits = r W_3`` over the ``n_routed`` experts and one choice
  that is no expert; ``p = softmax(logits)``; the token's ONE choice ``c =
  argmax(p + beta)``, ``beta`` a balancing bias that no gradient of the
  loss reaches and the LOAD moves (below); ``y = p[c] Expert_c(m)``, ``Expert(m) = (silu(m W_gate) * (m W_up)) W_down``;
  for the last choice nothing is added. All of the router in float32. Every
  HELD expert computes every token, with the weight zero where it was not
  chosen: a loop over the held experts, no sort, no grouped product;
- a final RMSNorm and the tied head ``logits = h E^T``; the loss is the
  softmax cross-entropy of the next id over the tokens of a batch's real
  sequences; plain SGD, no momentum;
- the balancing bias between steps: with ``f`` the share of a step's tokens
  (all of the batch's, a padded sequence's too) that chose each of the 17,
  ``beta <- beta - lr * rate * std(p) * clip(17 f - 1, -1, 1)`` in the step
  that moves every other leaf (``rate`` the model's ``balance_rate``,
  ``std(p)`` over the step's ``[N, 17]`` probabilities): written out here as
  the bias's entry of the gradient tree, where the loss's own gradient is
  zero. The aggregate is the clients' weighted mean of it, as of any leaf.

**The router's seed.** Its four matrices and their biases start as
``torch.nn.Linear``'s default does (uniform over ``+- fan_in^-0.5``), so the
logits' spread (about 0.07, a token's 17 probabilities about 0.004 apart)
does not depend on the widths.

**The balancing bias and the key temperature's seed** (read on the CPU at
the published widths, 2 x 1,024 tokens of four clients, ``PERF.md`` section
6, PR 39). With ``tau`` seeded at 1 the seeded scores are N(0, 1): attention
over a prefix is a running mean, every token of a sequence reads nearly the
same router input, and ONE choice takes most of a layer's tokens, another
for every seed, layer AND client (4% to 94% of a layer's tokens to the held
half; up to 79% to one expert, where the compiler's grouped kernels fall off
a cliff: 39 ms for 8,192 rows in one group against 5 ms for 8,192 rows in
eight). Seeded at 2 (scores N(0, 4), tens of keys a query) the share a
choice takes no longer follows the client, only the seeded router, whose
uneven shares are what the published model's balancing bias exists to undo.
That bias's trained values are not in the config, so :func:`init` does what
the balancing does: it draws one batch of ids by the traffic's law from the
key, follows it through the seeded layers and sets each layer's ``beta`` so
that the 17 choices take equal shares of THAT batch
(:func:`_balanced_bias`). The seeded bias is a function of the key alone
and is handed to the program with the other weights; from there each client's
steps move it against the load they see (above), which is the published
model's mechanism in its plainest form (a bias moved against the load between
steps; the report describes a controller on the load error, whose exact form
and gains the config does not carry: ``assumed`` in the configuration's file).

Departures from the published model, each also in the configuration's file:
(1) depth; (2) the share: experts ``held_first .. held_first + held_count -
1`` are held here, the router keeps all its outputs and its one choice, and
what the absent experts would have added is left out; (3) the vocabulary is
this chip's slice of the tied table; (4) the final norm and the head sit on
this stage; (5) rotary turns interleaved pairs ``(2i, 2i+1)`` where the
public code may turn halves: one fixed permutation of the columns of ``W_q``
and ``W_k``'s rotary channels (and of the head-wise convolution's outputs),
which are seeded; (6) ``W_v1`` and ``W_v2`` are the two column halves of one
``v_proj``; (7) each layer, each block of queries and each expert is
recomputed in the backward pass (``jax.checkpoint``): memory, not values;
(8) ``expert_rows`` / ``steps`` / ``skipped`` in the ``counters`` collection
count as the program's variable tree does.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves. ``local_train`` returns HOST trees:
``harness/check.py`` keeps the state, the new tree, its weighted part and
the sum at once, and as numpy arrays all but the state stay on the host.

The configuration states: a bfloat16 module (matmul operands and activations
bf16, float32 accumulation), the router, the softmax, the means, the
normalisation and rotary in float32, float32 parameters and aggregation.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision; it has to pass wherever a control fails. The controls have to
#: fail. Three are the nearest precision below one the configuration states:
#: ``act_fp8_scaled`` scales each operand of every module matmul so that its
#: largest magnitude is 128, rounds it to e4m3 and lets gradients pass the
#: rounding unrounded, so what it adds is e4m3's rounding noise alone;
#: ``params_bf16`` keeps the parameters and the aggregate in bf16;
#: ``local_bf16`` keeps the parameters in bf16 through local training and
#: aggregates in float32. Two are not a precision but the mechanisms the
#: configuration exists for, at the stated precision: ``mix_plain`` takes
#: ``q = q~``, ``k = k~`` and both value heads unshifted, which is what a
#: program reads that runs CCA as grouped-query attention at a narrower
#: width; ``router_alone`` takes ``gamma = 0``, every router reading its own
#: layer only, which is what a program reads that loses the carry between
#: layers. All rounding is by ``lax.reduce_precision``, which XLA keeps (a
#: cast there and back is removed on the TPU).
VARIANTS = ("reference", "stated", "params_bf16", "local_bf16",
            "act_fp8_scaled", "mix_plain", "router_alone")
CONTROLS = ("params_bf16", "local_bf16", "act_fp8_scaled", "mix_plain",
            "router_alone")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix
_Q_BLOCK = 256
#: the router's carry at the seed (``gamma``), away from zero so that the
#: check sees it (the configuration's ``assumed``)
_GAMMA = 0.5
#: the key temperature at the seed (the module's note)
_TAU = 2.0
#: steps of :func:`_balanced_bias`
_BALANCE_STEPS = 300

def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def _sizes(config: dict) -> dict:
    m = config["model"]
    out = {k: int(m[k]) for k in (
        "dim", "heads", "kv_heads", "v_dim", "rope", "layers", "n_routed",
        "held_first", "held_count", "expert_width", "router_hidden")}
    out["conv"] = tuple(int(k) for k in m["cca_conv"])
    out["n_out"] = out["n_routed"] + 1       # and the choice that is no expert
    out["balance_rate"] = float(m.get("balance_rate", 0.0))
    return out


def _uniform(key, shape, fan_in: int):
    """``torch.nn.Conv1d``'s and ``torch.nn.Linear``'s default, weights and
    biases alike: uniform over ``+- fan_in^-0.5``."""
    bound = float(fan_in) ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix normal(0, 0.02),
    norm and residual scales 1, the residual biases 0, the key temperatures
    2, ``gamma`` 0.5, the convolutions and the router's layers uniform as
    ``torch.nn``'s defaults, counters 0; then each layer's balancing bias
    from the same key (the module's note)."""
    z = _sizes(config)
    d, h, g, e = z["dim"], z["heads"], z["kv_heads"], z["v_dim"]
    k0, k1 = z["conv"]
    rh, held, f = z["router_hidden"], z["held_count"], z["expert_width"]
    vocab = int(config["data"]["vocab"])
    key, data_key = jax.random.split(key)
    keys = iter(jax.random.split(key, 24 * z["layers"] + 4))

    def w(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def u(shape, fan_in):
        return _uniform(next(keys), shape, fan_in)

    def lin(a, b):
        return {"kernel": w(a, b)}

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def merge():
        return {"res_scale": jnp.ones((d,), jnp.float32),
                "res_bias": jnp.zeros((d,), jnp.float32),
                "branch_scale": jnp.ones((d,), jnp.float32),
                "branch_bias": jnp.zeros((d,), jnp.float32)}

    params, stats = {"embed": w(vocab, d)}, {}
    for i in range(z["layers"]):
        router = {
            "down_kernel": u((d, rh), d), "down_bias": u((rh,), d),
            "norm": ones(rh),
            "fc1_kernel": u((rh, rh), rh), "fc1_bias": u((rh,), rh),
            "fc2_kernel": u((rh, rh), rh), "fc2_bias": u((rh,), rh),
            "out_kernel": u((rh, z["n_out"]), rh),
            "bias": jnp.zeros((z["n_out"],), jnp.float32)}
        if i:
            router["gamma"] = jnp.asarray(_GAMMA, jnp.float32)
        params[f"layer_{i}"] = {
            "attn_norm": ones(d), "mlp_norm": ones(d),
            "attn_merge": merge(), "mlp_merge": merge(),
            "attn": {
                "q_proj": lin(d, h * e), "k_proj": lin(d, g * e),
                "v_proj": lin(d, g * e), "o_proj": lin(h * e, d),
                "conv0_kernel": u((k0, (h + g) * e), k0),
                "conv0_bias": u(((h + g) * e,), k0),
                "conv1_kernel": u((k1, h + g, e, e), k1 * e),
                "conv1_bias": u((h + g, e), k1 * e),
                "k_temp": jnp.full((g,), _TAU, jnp.float32)},
            "mlp": {"router": router, "gate": w(held, d, f),
                    "up": w(held, d, f), "down": w(held, f, d)}}
        stats[f"layer_{i}"] = {"mlp": {
            "expert_rows": jnp.zeros((held,), jnp.float32),
            "steps": jnp.zeros((), jnp.float32),
            "skipped": jnp.zeros((), jnp.float32)}}
    params["final_norm"] = ones(d)
    return {"params": _balanced(config, params, data_key), "counters": stats}


def _calibration_ids(key, config: dict):
    """One batch of ids by the traffic's law: Zipf over a permutation of the
    slice, ``batch_size`` sequences (at least one) of ``seq_len``."""
    data = config["data"]
    vocab = int(data["vocab"])
    t = int(data.get("seq_len", config["model"]["seq_len"]))
    batch = max(int(config["recipe"].get("batch_size", 1)), 1)
    law = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        data.get("zipf_exponent", 1.0))
    cdf = jnp.asarray(np.cumsum(law / law.sum()), jnp.float32)
    k_perm, k_draw = jax.random.split(key)
    ranks = jnp.searchsorted(cdf, jax.random.uniform(k_draw, (batch, t)))
    return jax.random.permutation(k_perm, vocab)[jnp.minimum(ranks, vocab - 1)]


def _balanced_bias(p):
    """``p [N, E]`` -> the bias ``[E]`` (mean zero) under which ``argmax(p +
    bias)`` gives every choice about ``N / E`` tokens: the bias of a choice
    falls by its excess load, in steps that start at the scores' own spread
    and shrink."""
    n_out = p.shape[-1]
    spread = jnp.std(p)

    def step(i, bias):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(p + bias, axis=-1), n_out,
                                       dtype=jnp.float32), axis=0)
        return bias - spread * 0.98 ** i * (load * n_out - 1.0)

    bias = lax.fori_loop(0, _BALANCE_STEPS, step,
                         jnp.zeros((n_out,), jnp.float32))
    return bias - jnp.mean(bias)


def _balanced(config: dict, params: dict, key) -> dict:
    """``params`` with each layer's ``router/bias`` balanced on one seeded
    batch, layer after layer (a layer's input follows from the choices of
    the layers before it); float32 at the highest matmul precision."""
    parts = _parts(config, "reference")
    params = dict(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][_calibration_ids(key, config)]
        carry = None
        for i in range(_sizes(config)["layers"]):
            name = f"layer_{i}"
            layer = dict(params[name])
            x, m = parts.attend(x, layer)
            p, _ = parts.scores(m.reshape(-1, m.shape[-1]),
                                layer["mlp"]["router"], carry)
            router = {**layer["mlp"]["router"], "bias": _balanced_bias(p)}
            layer["mlp"] = {**layer["mlp"], "router": router}
            x, carry, _ = parts.sparse(x, m, layer, carry)
            params[name] = layer
    return params


def _ops(variant: str):
    """(activation dtype, matmul) of one variant."""
    if variant == "reference":
        return jnp.float32, lambda a, b: jnp.matmul(
            a, b, precision=lax.Precision.HIGHEST)

    def operand(a):
        if variant == "act_fp8_scaled":
            a = a.astype(jnp.float32)
            scale = 128.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
            a = a + lax.stop_gradient(_round_to(a * scale, 4, 3) / scale - a)
        return a.astype(jnp.bfloat16)

    def mm(a, b):
        return jnp.matmul(operand(a), operand(b),
                          preferred_element_type=jnp.float32)

    return jnp.bfloat16, mm


def _rms(x, scale, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(act)


def _rotary(x, theta: float):
    """Interleaved pairs (2i, 2i+1) of ``x [..., T, R]`` turn by ``pos *
    theta^(-2i/R)``, in float32."""
    t, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _earlier(x, n: int):
    """``x [B, T, ...]`` read ``n`` positions earlier, zeros before the
    sequence's first."""
    if not n:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :n]), x[:, :-n]], axis=1)


def _parts(config: dict, variant: str):
    """The layer's pieces of one variant, as functions of a layer's
    parameters: a namespace of ``attend``, ``scores``, ``sparse`` (and the
    ``mixer`` alone, the activation dtype and the matmul, for the tests)."""
    z = _sizes(config)
    m_ = config["model"]
    h, g, e, rope = z["heads"], z["kv_heads"], z["v_dim"], z["rope"]
    eps, theta = float(m_["eps"]), float(m_["rope_theta"])
    first, held = z["held_first"], z["held_count"]
    n_routed, n_out, rate = z["n_routed"], z["n_out"], z["balance_rate"]
    act, mm = _ops(variant)
    prec = lax.Precision.HIGHEST
    f32 = jnp.float32

    def lin(x, p):
        return mm(x, p["kernel"]).astype(act)

    def merged(x, branch, p):
        return ((x.astype(f32) * p["res_scale"] + p["res_bias"])
                + (branch.astype(f32) * p["branch_scale"] + p["branch_bias"])
                ).astype(act)

    def attention(q, k, v):
        """``q, k, v [B, H, T, e]`` (heads already repeated): every head's
        whole causal score row, a block of queries at a time; softmax in
        float32."""
        b, hh, t, _ = q.shape
        bq = min(_Q_BLOCK, t)
        scale = 1.0 / float(e) ** 0.5

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(q, start, bq, axis=2)
            s = mm(qb, jnp.swapaxes(k, -1, -2)).astype(f32) * scale
            seen = (start + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return mm(p.astype(act), v).astype(act)

        out = lax.map(block, jnp.arange(0, t, bq))       # [T/bq,B,H,bq,e]
        return jnp.moveaxis(out, 0, 2).reshape(b, hh, t, e)

    def mixed(q, k, p):
        """Steps 2 - 4: ``q~ [B,T,H,e]``, ``k~ [B,T,G,e]`` -> the mixed ``q``
        and ``k``, float32."""
        qf, kf = q.astype(f32), k.astype(f32)
        of = np.arange(h) // (h // g)
        m_q = (qf + kf[:, :, of]) / 2
        m_k = jnp.stack([jnp.mean(m_q[:, :, of == j], axis=2)
                         for j in range(g)], axis=2)
        zc = jnp.concatenate([qf, kf], axis=2)                 # [B,T,H+G,e]
        w0 = p["conv0_kernel"].reshape((-1, h + g, e))
        k0, k1 = w0.shape[0], p["conv1_kernel"].shape[0]
        u = p["conv0_bias"].reshape(h + g, e) + sum(
            w0[i] * _earlier(zc, k0 - 1 - i) for i in range(k0))
        y = p["conv1_bias"] + sum(
            jnp.stack([mm(_earlier(u, k1 - 1 - i)[:, :, j],
                          p["conv1_kernel"][i, j]).astype(f32)
                       for j in range(h + g)], axis=2)
            for i in range(k1))
        return y[:, :, :h] + m_q, y[:, :, h:] + m_k

    def mixer(x, p):
        b, t, _ = x.shape
        q = lin(x, p["q_proj"]).reshape(b, t, h, e)
        k = lin(x, p["k_proj"]).reshape(b, t, g, e)
        v = lin(x, p["v_proj"]).reshape(b, t, g, e)
        if variant == "mix_plain":
            q, k = q.astype(f32), k.astype(f32)
        else:
            q, k = mixed(q, k, p)
            v = jnp.concatenate([v[:, :, :g // 2],
                                 _earlier(v[:, :, g // 2:], 1)], axis=2)

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-12) \
                * float(e) ** 0.5

        q, k = unit(q), unit(k) * p["k_temp"][:, None]

        def turned(a):                                   # [B,T,n,e] -> [B,n,T,e]
            a = a.transpose(0, 2, 1, 3)
            return jnp.concatenate([_rotary(a[..., :rope], theta),
                                    a[..., rope:]], axis=-1).astype(act)

        of = np.arange(h) // (h // g)
        o = attention(turned(q), turned(k)[:, of],
                      v.transpose(0, 2, 1, 3)[:, of])          # [B,H,T,e]
        return lin(o.transpose(0, 2, 1, 3).reshape(b, t, h * e), p["o_proj"])

    def attend(x, layer):
        """The CCA sub-layer and its residual; -> (h, ``m = RMSNorm(h)``)."""
        x = merged(x, mixer(_rms(x, layer["attn_norm"]["scale"], eps, act),
                            layer["attn"]), layer["attn_merge"])
        return x, _rms(x, layer["mlp_norm"]["scale"], eps, act)

    def scores(xf, r, carry):
        """The router, layer by layer: -> (``p [N, n_out]``, ``s [N, hidden]``)."""
        def dense(a, name, bias=True):
            y = jnp.matmul(a, r[f"{name}_kernel"], precision=prec)
            return y + r[f"{name}_bias"] if bias else y

        s = dense(xf.astype(f32), "down")
        if carry is not None:
            gamma = 0.0 if variant == "router_alone" else r["gamma"]
            s = s + gamma * carry
        y = _rms(s, r["norm"]["scale"], eps, f32)
        y = jax.nn.gelu(dense(y, "fc1"), approximate=False)
        y = jax.nn.gelu(dense(y, "fc2"), approximate=False)
        return jax.nn.softmax(dense(y, "out", bias=False), axis=-1), s

    def sparse(x, m, layer, carry):
        """The sparse sub-layer and its residual on ``m = RMSNorm(x)``; ->
        (h, the router's state, (rows [held], skipped, choices [N], what
        the balancing bias is moved by [n_out]))."""
        b, t, d = m.shape
        xf, p = m.reshape(b * t, d), layer["mlp"]
        probs, s = scores(xf, p["router"], carry)
        idx = jnp.argmax(lax.stop_gradient(probs) + p["router"]["bias"], -1)
        weight = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
        local = idx - first
        rows = jnp.sum(jax.nn.one_hot(local, held, dtype=f32), axis=0)
        skipped = jnp.sum((idx == n_routed).astype(f32))
        load = jnp.mean(jax.nn.one_hot(idx, n_out, dtype=f32), axis=0)
        pull = lax.stop_gradient(rate * jnp.std(probs) * jnp.minimum(
            jnp.maximum(load * n_out - 1.0, -1.0), 1.0))

        @jax.checkpoint
        def one(acc, j):
            w_j = jnp.where(local == j, weight, 0.0)
            y = mm(jax.nn.silu(mm(xf, p["gate"][j]).astype(act))
                   * mm(xf, p["up"][j]).astype(act), p["down"][j]).astype(act)
            return acc + w_j[:, None] * y.astype(f32), None

        routed, _ = lax.scan(one, jnp.zeros((b * t, d), f32), jnp.arange(held))
        out = merged(x, routed.astype(act).reshape(b, t, d), layer["mlp_merge"])
        return out, s, (rows, skipped, idx, pull)

    return types.SimpleNamespace(attend=attend, scores=scores, sparse=sparse,
                                 mixer=mixer, act=act, mm=mm)


def _forward(config: dict, variant: str):
    z = _sizes(config)
    parts = _parts(config, variant)
    eps = float(config["model"]["eps"])

    def forward(params, stats, ids):
        x = params["embed"][ids].astype(parts.act)
        new_stats, picks, pulls, carry = {}, {}, {}, None
        for i in range(z["layers"]):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, carry, p):
                x, m = parts.attend(x, p)
                return parts.sparse(x, m, p, carry)

            x, carry, (rows, skipped, idx, pull) = layer(
                x, carry, params[name])
            picks[name], pulls[name] = idx[:, None], pull
            old = stats[name]["mlp"]
            new_stats[name] = {"mlp": {
                "expert_rows": old["expert_rows"] + rows,
                "steps": old["steps"] + 1.0,
                "skipped": old["skipped"] + skipped}}
        x = _rms(x, params["final_norm"]["scale"], eps, parts.act)
        logits = parts.mm(x, params["embed"].T).astype(jnp.float32)
        return logits, new_stats, picks, pulls

    forward.parts = parts        # the sub-layers alone, for the tests
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
        logits, new_stats, _, pulls = forward(params, stats, bx)
        logz = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(logz, by[..., None], axis=-1)[..., 0]
        w = jnp.broadcast_to(bm[:, None], per.shape)
        return (jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0),
                (new_stats, pulls))

    def step(carry, batch):
        params, stats = carry
        bx, by, bm, live = batch
        (loss, (new_stats, pulls)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, stats, bx, by, bm)
        # the loss has no gradient for a balancing bias (zeros): the step
        # moves it by what the load asks
        grads = dict(grads)
        for name, pull in pulls.items():
            mlp = dict(grads[name]["mlp"])
            mlp["router"] = {**mlp["router"], "bias": pull}
            grads[name] = {**grads[name], "mlp": mlp}
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
    each (26 GB at 709 M parameters) on a machine of 40 GiB, so whatever the
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


def choices(config: dict, variables: dict, ids, variant: str = "stated"):
    """Each sparse layer's chosen expert for one batch of ids, ``{layer:
    [N, 1]}`` (``benchmarks/routing_agreement.py``)."""
    forward = _forward(config, variant)
    return jax.jit(lambda v, x: forward(v["params"], v["counters"], x)[2])(
        variables, jnp.asarray(ids))
