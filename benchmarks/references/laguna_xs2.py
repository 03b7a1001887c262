"""Plain reference for ``laguna_xs2``: one chip's share of the decoder of
poolside/Laguna-XS.2 (``model_type`` ``laguna``), written out in
``jax.numpy``. Every size is read from the configuration's ``model`` block;
the equations are the published config's, with what its keys leave open
listed under ``assumed`` in the configuration's file:

- block: ``h = h + Mixer(RMSNorm(h))``, ``h = h + Mlp(RMSNorm(h))``, eps
  ``rms_norm_eps``, no biases; a final RMSNorm and an untied head; no learned
  positions. ``mixers`` names each layer's mixer, ``full`` or ``window``;
- mixer with ``H`` query heads (``heads`` in a full layer, ``window_heads``
  in a window layer): ``q = W_q x`` as ``H`` heads of ``v_dim``, ``k`` and
  ``v`` as ``kv_heads`` heads; query head ``i`` reads key-value head ``i //
  (H / kv_heads)`` (the heads REPEATED by index, no kernel). Full layer: the
  first ``rope`` channels of every head of ``q`` and ``k`` turn, the others
  pass; the pairs' frequencies are YaRN's, written out below
  (:func:`_yarn_frequencies`), and cosine and sine are multiplied by
  ``yarn_attention_factor``. Window layer: the whole head turns at
  ``window_rope_theta``, no scale. Scores ``q . k / sqrt(v_dim)``; softmax in
  float32 over the keys ``j`` with ``i - window < j <= i`` (all ``j <= i`` in
  a full layer): the whole score row of every head, masked, a block of
  queries at a time. Each head's output times ``sigmoid(x W_g)`` (one gate a
  head, no norm), then ``W_o``;
- layer 0: SwiGLU of ``dense_width``. After: ``s = softmax(W_r x)`` in
  float32 over all ``n_routed`` experts; the ``top_k`` largest; weights ``s_i
  / sum of the chosen s`` times ``routed_scaling``; output = the shared
  expert (one SwiGLU of ``n_shared * expert_width`` on every token, ungated)
  + the weighted sum of the chosen experts' SwiGLUs. Every HELD expert
  computes every token, with the weight zero where it was not chosen: no
  sort, no kernel;
- loss: softmax cross-entropy of the next id, averaged over the tokens of
  the real sequences of a batch; plain SGD, no momentum.

Departures from the published model, each also in the configuration's file:
(1) depth; (2) the share: experts ``held_first .. held_first + held_count -
1`` are held here, the router keeps its width, choices and normalisation,
and what the absent experts would have added is left out; (3) the
vocabulary is this chip's slice; (4) the final norm and the head sit on this
stage; (5) rotary turns interleaved pairs ``(2i, 2i+1)`` where the public
code may turn halves: one fixed permutation of the columns of ``W_q`` and
``W_k``, which are seeded; (6) each block and each block of queries is
recomputed in the backward pass (``jax.checkpoint``): memory, not values;
(7) ``expert_rows`` / ``steps`` in the ``counters`` collection count as the
program's variable tree does.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves. ``local_train`` returns HOST trees:
``harness/check.py`` keeps the state, the new tree, its weighted part and
the sum at once, and as numpy arrays all but the state stay on the host.

The configuration states: a bfloat16 module (matmul operands and activations
bf16, float32 accumulation), router and softmax in float32, norm statistics
and rotary in float32, float32 parameters and aggregation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision; it has to pass wherever a control fails. The controls have to
#: fail. Four are the nearest precision below one the configuration states:
#: ``act_fp8`` rounds both operands of every module matmul to e4m3 as they
#: are; ``act_fp8_scaled`` first scales each operand so that its largest
#: magnitude is 128 and lets gradients pass the rounding unrounded, so what
#: it adds is e4m3's rounding noise alone; ``params_bf16`` keeps the
#: parameters and the aggregate in bf16; ``local_bf16`` keeps the parameters
#: in bf16 through local training and aggregates in float32. Two are not a
#: precision but the mechanisms the configuration exists for, at the stated
#: precision: ``window_full`` lets the window layers attend to the whole
#: prefix, which is what a program reads that ignores the window;
#: ``rope_plain`` turns the full layers' rotary channels at the plain
#: ``rope_theta`` frequencies with no scale, which is what a program reads
#: that leaves out YaRN's blend and attention factor. All rounding is by
#: ``lax.reduce_precision``, which XLA keeps (a cast there and back is
#: removed on the TPU).
VARIANTS = ("reference", "stated", "act_fp8", "params_bf16", "local_bf16",
            "act_fp8_scaled", "window_full", "rope_plain")
CONTROLS = ("act_fp8", "params_bf16", "local_bf16", "act_fp8_scaled",
            "window_full", "rope_plain")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix
_Q_BLOCK = 256


def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def _heads_of(m: dict, mixer: str) -> int:
    return int(m["heads"] if mixer == "full" else m["window_heads"])


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix normal(0, 0.02),
    norm scales 1, counters 0."""
    m = config["model"]
    d, g, hd = int(m["dim"]), int(m["kv_heads"]), int(m["v_dim"])
    vocab = int(config["data"]["vocab"])
    keys = iter(jax.random.split(key, 16 * int(m["layers"]) + 4))

    def w(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(a, b):
        return {"kernel": w(a, b)}

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(width):
        return {"gate": lin(d, width), "up": lin(d, width), "down": lin(width, d)}

    params, stats = {"embed": w(vocab, d)}, {}
    for i, mixer in enumerate(m["mixers"]):
        h = _heads_of(m, mixer)
        layer = {
            "attn_norm": ones(d), "mlp_norm": ones(d),
            "attn": {"q_proj": lin(d, h * hd), "k_proj": lin(d, g * hd),
                     "v_proj": lin(d, g * hd), "o_proj": lin(h * hd, d),
                     "out_gate": {"proj": lin(d, h)}}}
        if i < int(m["first_dense"]):
            layer["mlp"] = swiglu(int(m["dense_width"]))
        else:
            e, f = int(m["held_count"]), int(m["expert_width"])
            layer["mlp"] = {
                "shared": swiglu(int(m["n_shared"]) * f),
                "router": w(d, int(m["n_routed"])),
                "gate": w(e, d, f), "up": w(e, d, f), "down": w(e, f, d)}
            stats[f"layer_{i}"] = {"mlp": {
                "expert_rows": jnp.zeros((e,), jnp.float32),
                "steps": jnp.zeros((), jnp.float32)}}
        params[f"layer_{i}"] = layer
    params["final_norm"] = ones(d)
    params["lm_head"] = lin(d, vocab)
    return {"params": params, "counters": stats}


def _ops(variant: str):
    """(activation dtype, matmul) of one variant."""
    if variant == "reference":
        return jnp.float32, lambda a, b: jnp.matmul(
            a, b, precision=lax.Precision.HIGHEST)

    def operand(a):
        if variant == "act_fp8":
            a = _round_to(a.astype(jnp.float32), 4, 3)
        elif variant == "act_fp8_scaled":
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


def _yarn_frequencies(r: int, theta: float, factor: float, original: int,
                      beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's pair frequencies over a rotary width ``r``, written out as
    ``transformers``' ``_compute_yarn_parameters`` computes them. Pair ``i``
    of ``r / 2`` has the plain frequency ``f_i = theta^(-2i/r)``. The pair
    that makes ``n`` turns over the ``original`` positions is ``corr(n) = r
    ln(original / (2 pi n)) / (2 ln theta)``; ``low = floor(corr(beta_fast))``
    and ``high = ceil(corr(beta_slow))``; ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``; the pair turns at ``f_i (1 - ramp_i) + (f_i / factor)
    ramp_i``. For the published keys (theta 500,000, factor 64, original
    4,096, beta_fast 64, beta_slow 1, ``r`` 64): low 5, high 16; pairs 0 - 5
    as they are, 16 - 31 divided by 64; pair 6 reads 0.0777550, pair 16
    2.2097085e-5, pair 31 4.7091532e-8."""
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)

    def corr(turns):
        return r * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def _rotary(x, inv_freq, scale=1.0):
    """Interleaved pairs (2i, 2i+1) of ``x [..., T, R]`` turn by ``pos *
    inv_freq[i]``; cosine and sine times ``scale``."""
    t = x.shape[-2]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _plain_frequencies(r: int, theta: float) -> np.ndarray:
    return theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)


def _forward(config: dict, variant: str):
    m = config["model"]
    g, hd, r = int(m["kv_heads"]), int(m["v_dim"]), int(m["rope"])
    eps, window = float(m["eps"]), int(m["window"])
    top_k, n_routed = int(m["top_k"]), int(m["n_routed"])
    first, held = int(m["held_first"]), int(m["held_count"])
    scaling = float(m["routed_scaling"])
    act, mm = _ops(variant)
    prec = lax.Precision.HIGHEST
    if variant == "rope_plain":
        full_freq, full_scale = _plain_frequencies(r, float(m["rope_theta"])), 1.0
    else:
        full_freq = _yarn_frequencies(
            r, float(m["rope_theta"]), float(m["yarn_factor"]),
            int(m["yarn_original"]), float(m["yarn_beta_fast"]),
            float(m["yarn_beta_slow"]))
        full_scale = float(m["yarn_attention_factor"])
    window_freq = _plain_frequencies(hd, float(m["window_rope_theta"]))

    def lin(x, p):
        return mm(x, p["kernel"]).astype(act)

    def swiglu(x, p):
        return lin(jax.nn.silu(lin(x, p["gate"])) * lin(x, p["up"]), p["down"])

    def attention(q, k, v, span):
        """``q [B,H,T,hd]``, ``k, v [B,H,T,hd]`` (heads already repeated):
        every head's whole score row, a block of queries at a time, masked
        to the ``span`` keys up to the query's own (None: all of them);
        softmax in float32."""
        b, h, t, _ = q.shape
        bq = min(_Q_BLOCK, t)
        scale = 1.0 / float(hd) ** 0.5

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(q, start, bq, axis=2)
            s = mm(qb, jnp.swapaxes(k, -1, -2)).astype(jnp.float32) * scale
            behind = (start + jnp.arange(bq))[:, None] - jnp.arange(t)[None, :]
            seen = behind >= 0
            if span is not None:
                seen = seen & (behind < span)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return mm(p.astype(act), v).astype(act)

        out = lax.map(block, jnp.arange(0, t, bq))       # [T/bq,B,H,bq,hd]
        return jnp.moveaxis(out, 0, 2).reshape(b, h, t, hd)

    def mixer(x, p, kind):
        b, t, _ = x.shape
        h = _heads_of(m, kind)

        def heads(a, n):
            return a.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

        q, k = heads(lin(x, p["q_proj"]), h), heads(lin(x, p["k_proj"]), g)
        v = heads(lin(x, p["v_proj"]), g)
        if kind == "full":
            def turned(a):
                return jnp.concatenate(
                    [_rotary(a[..., :r], full_freq, full_scale), a[..., r:]], -1)
            span = None
        else:
            def turned(a):
                return _rotary(a, window_freq)
            span = None if variant == "window_full" else window
        q, k = turned(q), turned(k)
        # query head i reads key-value head i // (h / g)
        of = jnp.arange(h) // (h // g)
        o = attention(q, k[:, of], v[:, of], span)            # [B,H,T,hd]
        gate = jax.nn.sigmoid(
            mm(x, p["out_gate"]["proj"]["kernel"]).astype(jnp.float32))
        o = (o.transpose(0, 2, 1, 3).astype(jnp.float32)
             * gate[..., None]).astype(act)
        return lin(o.reshape(b, t, h * hd), p["o_proj"])

    def choose(x, p):
        """-> (idx [N,k], weights [N,k]) over all the experts."""
        s = jax.nn.softmax(jnp.matmul(x.astype(jnp.float32), p["router"],
                                      precision=prec), axis=-1)
        _, idx = lax.top_k(lax.stop_gradient(s), top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return idx, chosen / jnp.sum(chosen, -1, keepdims=True) * scaling

    def moe(x, p):
        b, t, d = x.shape
        xf = x.reshape(b * t, d)
        idx, weights = choose(xf, p)
        # weight of every expert on every token, zero where not chosen
        full = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=jnp.float32)
                       * weights[..., None], axis=1)              # [N, E]
        mine = full[:, first:first + held]
        rows = jnp.sum(((idx >= first) & (idx < first + held))[..., None]
                       * jax.nn.one_hot(idx - first, held, dtype=jnp.float32),
                       axis=(0, 1))

        @jax.checkpoint
        def one(carry, e):
            w_e = lax.dynamic_index_in_dim(mine, e, axis=1, keepdims=False)
            y = mm(jax.nn.silu(mm(xf, p["gate"][e]).astype(act))
                   * mm(xf, p["up"][e]).astype(act), p["down"][e]).astype(act)
            return carry + w_e[:, None] * y.astype(jnp.float32), None

        routed, _ = lax.scan(one, jnp.zeros((b * t, d), jnp.float32),
                             jnp.arange(held))
        out = swiglu(xf, p["shared"]) + routed.astype(act)
        return out.reshape(b, t, d), rows, idx

    def forward(params, stats, ids):
        x = params["embed"][ids].astype(act)
        new_stats, picks = {}, {}
        for i, kind in enumerate(m["mixers"]):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, p, sparse=i >= int(m["first_dense"]), kind=kind):
                x = x + mixer(_rms(x, p["attn_norm"]["scale"], eps, act),
                              p["attn"], kind)
                y = _rms(x, p["mlp_norm"]["scale"], eps, act)
                if sparse:
                    y, rows, idx = moe(y, p["mlp"])
                    return x + y, rows, idx
                return x + swiglu(y, p["mlp"]), None, None

            x, rows, idx = layer(x, params[name])
            if rows is not None:
                picks[name] = idx
                old = stats[name]["mlp"]
                new_stats[name] = {"mlp": {
                    "expert_rows": old["expert_rows"] + rows,
                    "steps": old["steps"] + 1.0}}
        x = _rms(x, params["final_norm"]["scale"], eps, act)
        return (mm(x, params["lm_head"]["kernel"]).astype(jnp.float32),
                new_stats, picks)

    forward.moe = moe        # one sparse layer alone, for the share's test
    forward.choose = choose
    forward.mixer = mixer
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
        logits, new_stats, _ = forward(params, stats, bx)
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
    each (25 GB at 692 M parameters) on a machine of 40 GiB, so whatever the
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
    """Each sparse layer's chosen experts for one batch of ids, ``{layer:
    [N, top_k]}`` (``benchmarks/routing_agreement.py``)."""
    forward = _forward(config, variant)
    return jax.jit(lambda v, x: forward(v["params"], v["counters"], x)[2])(
        variables, jnp.asarray(ids))
