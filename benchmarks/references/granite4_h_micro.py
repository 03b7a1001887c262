"""Plain reference for ``granite4_h_micro``: the first pipeline stage of
ibm-granite/granite-4.0-h-micro (``model_type granitemoehybrid``), written
out in ``jax.numpy``. Every size is read from the configuration's ``model``
block; the equations are the published config's, with what its keys leave
open listed under ``assumed`` in the configuration's file:

- input ``h_0 = embed_scale * E[id]``; block: ``h = h + r Mixer(RMSNorm(h))``,
  ``h = h + r Mlp(RMSNorm(h))`` with ``r`` the residual multiplier; ``Mlp(x)
  = W_down (silu(W_gate x) * W_up x)``; a final RMSNorm; the head is the
  embedding's table, ``logits = (h E^T) / logit_scale``; eps
  ``rms_norm_eps``; no learned or rotary positions; no bias but the
  convolution's. ``mixers`` names each layer's mixer;
- ``ssd`` (Mamba-2, arXiv:2405.21060, one group): ``[z | xBC | dt] = W_in
  u`` of widths ``H P``, ``H P + 2 N``, ``H``; ``xBC = silu(conv(xBC) +
  b_conv)``, the convolution causal and depthwise over ``ssd_conv``
  positions; ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; then, a head, with the state ``S [P, N]`` zero before
  position 0, **token by token**::

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
      y_t = S_t C_t + D x_t

  a ``lax.scan`` over positions inside a rematerialised scan over blocks of
  ``_SCAN_BLOCK``, so that the backward pass keeps a state a block and not
  a position; ``y = RMSNorm(y * silu(z)) * w``, the gate BEFORE the norm
  and one norm over all ``H P`` channels; ``out = W_out y``;
- ``full``: grouped-query attention, ``heads`` query heads over ``kv_heads``
  key-value heads of ``v_dim``, no rotary, causal softmax of ``q . k *
  attn_scale``: the full score matrix of every head, a block of queries at a
  time;
- loss: softmax cross-entropy of the next id, averaged over the tokens of
  the real sequences of a batch; plain SGD, no momentum.

Departures from the published model, each also in the configuration's file:
(1) depth: the first period of ``layer_types``; (2) the vocabulary is this
chip's slice of the tied table; (3) the final norm and the head sit on this
stage; (4) the MLP's ``input_linear`` is two matrices; (5) each block, each
block of queries and each block of positions of the scan is recomputed in
the backward pass (``jax.checkpoint``): memory, not values; (6) ``decay`` /
``steps`` in the ``counters`` collection count as the program's variable
tree does.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves. ``local_train`` returns HOST trees
(``harness/check.py`` keeps the state, the new tree, its weighted part and
the sum at once: four copies of 3.1 GB beside a client's training do not fit
the chip).

The configuration states: a bfloat16 module (matmul operands and activations
bf16, float32 accumulation), softmax and the scan's state in float32, norm
statistics, ``dt`` and the decays in float32, float32 parameters and
aggregation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision (the recurrence's products take bf16 operands, its state stays
#: float32). The controls have to fail. Three are the nearest precision below
#: one the configuration states: ``act_fp8_scaled`` rounds the operands of
#: every module matmul to e4m3 after scaling the largest magnitude to 128,
#: gradients passing unrounded; ``params_bf16`` keeps parameters and
#: aggregate in bf16, ``local_bf16`` the parameters through local training
#: (the fault ``lowp_share`` is there to catch). Two are not precisions but
#: what the configuration exists for: ``state_cut`` is ``stated`` with the recurrence's state set
#: to zero every ``_SCAN_BLOCK`` positions (what a program reads that loses
#: the carry between its chunks); ``scale_plain`` is ``stated`` with scores
#: times ``v_dim^-0.5`` and a residual multiplier of 1 (what a program reads
#: that ignores the multipliers). All rounding is by
#: ``lax.reduce_precision``, which XLA keeps.
VARIANTS = ("reference", "stated", "act_fp8_scaled", "params_bf16",
            "local_bf16", "state_cut", "scale_plain")
CONTROLS = ("act_fp8_scaled", "params_bf16", "local_bf16", "state_cut",
            "scale_plain")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix; positions per block of the scan
#: (the published kernel's chunk: where ``state_cut`` drops the state)
_Q_BLOCK = 512
_SCAN_BLOCK = 256


def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix and the table
    normal(0, 0.02), norm scales 1; the recurrence as Mamba-2's public code
    starts it: ``dt`` log-uniform over [0.001, 0.1] with ``dt_bias`` its
    inverse softplus, ``A_log = log(U[1, 16])``, ``D`` 1, the convolution's
    weights and bias uniform over ``+- ssd_conv^-0.5`` (``torch.nn.Conv1d``'s
    default at a depthwise fan-in of ``ssd_conv``); counters 0."""
    m = config["model"]
    d, h, g, hd = (int(m[k]) for k in ("dim", "heads", "kv_heads", "v_dim"))
    sh, sp, sn, kc = (int(m[k]) for k in ("ssd_heads", "ssd_head_dim",
                                          "ssd_state", "ssd_conv"))
    width, vocab = int(m["dense_width"]), int(config["data"]["vocab"])
    keys = iter(jax.random.split(key, 12 * int(m["layers"]) + 2))

    def lin(a, b):
        return {"kernel": 0.02 * jax.random.normal(next(keys), (a, b),
                                                   jnp.float32)}

    def uniform(shape, low, high):
        return jax.random.uniform(next(keys), shape, jnp.float32, low, high)

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params = {"embed": lin(vocab, d)["kernel"]}
    stats = {}
    for i, mixer in enumerate(m["mixers"]):
        layer = {"attn_norm": ones(d), "mlp_norm": ones(d),
                 "mlp": {"gate": lin(d, width), "up": lin(d, width),
                         "down": lin(width, d)}}
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
            stats[f"layer_{i}"] = {"ssd": {
                "decay": jnp.zeros((), jnp.float32),
                "steps": jnp.zeros((), jnp.float32)}}
        else:
            layer["attn"] = {"q_proj": lin(d, h * hd), "k_proj": lin(d, g * hd),
                             "v_proj": lin(d, g * hd), "o_proj": lin(h * hd, d)}
        params[f"layer_{i}"] = layer
    params["final_norm"] = ones(d)
    return {"params": params, "counters": stats}


def _ops(variant: str):
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


def _rms(x, scale, eps, act):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(act)


def recurrence(x, dt, a_log, b, c, d, operand=lambda a: a, state_cut=False):
    """The recurrence, one position at a time: ``x [B, T, H, P]``, ``dt [B,
    T, H]`` (after its softplus), ``a_log, d [H]``, ``b, c [B, T, N]`` ->
    ``y [B, T, H, P]`` float32. ``operand`` rounds what the configuration's
    precision computes in the module's dtype (the factors of the write and
    of the read); ``state_cut`` starts every block of ``_SCAN_BLOCK``
    positions from a zero state (a control)."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    blk = min(_SCAN_BLOCK, t)
    rate = -jnp.exp(a_log.astype(f32))

    def low(a):
        return operand(a).astype(f32)

    def position(s, inp):
        xt, dtt, bt, ct = inp
        write = low(dtt[..., None] * xt)[..., None] * low(bt)[:, None, None, :]
        s = s * jnp.exp(dtt * rate)[..., None, None] + write
        return s, jnp.sum(low(s) * low(ct)[:, None, None, :], axis=-1)

    @jax.checkpoint
    def block(s, xs):
        return lax.scan(position, jnp.zeros_like(s) if state_cut else s, xs)

    def blocks(a):       # [B, T, ...] -> [T/blk, blk, B, ...]
        a = jnp.moveaxis(a.astype(f32), 1, 0)
        return a.reshape((t // blk, blk) + a.shape[1:])

    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    _, y = lax.scan(block, s0, tuple(blocks(a) for a in (x, dt, b, c)))
    y = jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)
    return y + d.astype(f32)[:, None] * x.astype(f32)


def _forward(config: dict, variant: str):
    m = config["model"]
    h, g, hd = (int(m[k]) for k in ("heads", "kv_heads", "v_dim"))
    sh, sp, sn = (int(m[k]) for k in ("ssd_heads", "ssd_head_dim", "ssd_state"))
    eps, inner = float(m["eps"]), sh * sp
    plain = variant == "scale_plain"
    residual = 1.0 if plain else float(m["residual_scale"])
    score_scale = hd ** -0.5 if plain else float(m["attn_scale"])
    act, mm, operand = _ops(variant)

    def lin(x, p):
        return mm(x, p["kernel"]).astype(act)

    def mlp(x, p):
        return lin(jax.nn.silu(lin(x, p["gate"])) * lin(x, p["up"]), p["down"])

    def attention(q, k, v):
        """q [B, H, T, d], k, v [B, G, T, d]: every head's full score matrix,
        a block of queries at a time; softmax in float32."""
        b, _, t, _ = q.shape
        bq = min(_Q_BLOCK, t)
        k, v = (jnp.repeat(a, h // g, axis=1) for a in (k, v))

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(q, start, bq, axis=2)
            s = mm(qb, jnp.swapaxes(k, -1, -2)).astype(jnp.float32) * score_scale
            seen = (start + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return mm(p.astype(act), v).astype(act)

        out = lax.map(block, jnp.arange(0, t, bq))       # [T/bq, B, H, bq, d]
        return jnp.moveaxis(out, 0, 2).reshape(b, h, t, hd)

    def full(x, p):
        b, t, _ = x.shape

        def heads(a, n):
            return a.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

        o = attention(heads(lin(x, p["q_proj"]), h),
                      heads(lin(x, p["k_proj"]), g),
                      heads(lin(x, p["v_proj"]), g))
        return lin(o.transpose(0, 2, 1, 3).reshape(b, t, h * hd), p["o_proj"])

    def conv(a, w, bias):
        """Causal, depthwise: y_t = sum_i w[i] a_{t-K+1+i} + bias; a [B, T, C]."""
        kc, t = w.shape[0], a.shape[1]
        ap = jnp.pad(a.astype(jnp.float32), ((0, 0), (kc - 1, 0), (0, 0)))
        return sum(ap[:, i:i + t] * w[i] for i in range(kc)) + bias

    def ssd(x, p):
        b, t, _ = x.shape
        zxbcdt = lin(x, p["in_proj"])
        z = zxbcdt[..., :inner].astype(jnp.float32)
        xbc = jax.nn.silu(conv(zxbcdt[..., inner:2 * inner + 2 * sn],
                               p["conv_kernel"], p["conv_bias"])).astype(act)
        dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * sn:].astype(jnp.float32)
                             + p["dt_bias"])
        y = recurrence(xbc[..., :inner].reshape(b, t, sh, sp), dt, p["A_log"],
                       xbc[..., inner:inner + sn], xbc[..., inner + sn:],
                       p["D"], operand, variant == "state_cut")
        y = _rms(y.reshape(b, t, inner) * jax.nn.silu(z), p["norm"]["scale"],
                 eps, act)
        decay = jnp.mean(jnp.exp(-dt * jnp.exp(p["A_log"])))
        return lin(y, p["out_proj"]), decay

    def forward(params, stats, ids):
        table = params["embed"]
        x = (table[ids] * float(m["embed_scale"])).astype(act)
        new_stats = {}
        for i, mixer in enumerate(m["mixers"]):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, p, mixer=mixer):
                y = _rms(x, p["attn_norm"]["scale"], eps, act)
                y, decay = (ssd(y, p["ssd"]) if mixer == "ssd"
                            else (full(y, p["attn"]), None))
                x = x + y * jnp.asarray(residual, act)
                y = mlp(_rms(x, p["mlp_norm"]["scale"], eps, act), p["mlp"])
                return x + y * jnp.asarray(residual, act), decay

            x, decay = layer(x, params[name])
            if decay is not None:
                old = stats[name]["ssd"]
                new_stats[name] = {"ssd": {"decay": old["decay"] + decay,
                                           "steps": old["steps"] + 1.0}}
        x = _rms(x, params["final_norm"]["scale"], eps, act)
        logits = mm(x, table.T).astype(jnp.float32)
        return logits / float(m["logit_scale"]), new_stats

    forward.ssd = ssd          # one mixer alone, for the tests
    forward.full = full
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
    each (28 GB at 772 M parameters) on a machine of 40 GiB, so whatever the
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
