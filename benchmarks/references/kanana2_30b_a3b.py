"""Plain reference for ``kanana2_30b_a3b``: one chip's share of the decoder
of kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type`` ``deepseek_v3``),
written out in ``jax.numpy``. Every size is read from the configuration's
``model`` block; the equations are the published config's:

- block: ``h = h + Attn(RMSNorm(h))``, ``h = h + Mlp(RMSNorm(h))``, eps
  ``rms_norm_eps``, no biases; a final RMSNorm and an untied head; no learned
  positions;
- latent attention (``q_lora_rank`` null): ``q = W_q x`` as heads of
  ``nope + rope``; ``[c, k_r] = W_kva x``, ``c`` ``kv_rank`` wide, ``k_r``
  one rotary key for all heads; ``c <- RMSNorm(c)``; ``[k_nope, v] = W_kvb
  c`` per head; rotary embedding (``rope_theta``, no scaling) on ``q_rope``
  and ``k_r``, interleaved pairs ``(2i, 2i+1)``; ``k = [k_nope, k_r]``;
  causal softmax of ``q . k / sqrt(nope + rope)``; output through ``W_o``.
  The full score matrix of every head, computed in blocks of queries;
- layer 0: SwiGLU of ``dense_width``. Layers 1 and up: ``s = sigmoid(W_r
  x)`` in float32 over all ``n_routed`` experts; the ``top_k`` experts with
  the largest ``s + b`` (``b`` the correction bias: no gradient, ``n_group``
  = ``topk_group`` = 1 so no group limit); weights ``s_i / sum of the chosen
  s`` times ``routed_scaling``; output = the shared experts (one SwiGLU of
  ``n_shared * expert_width`` on every token) + the weighted sum of the
  chosen experts' SwiGLUs. Every HELD expert computes every token, with the
  weight zero where it was not chosen: no sort, no kernel;
- loss: softmax cross-entropy of the next id, averaged over the tokens of
  the real sequences of a batch; plain SGD, no momentum.

Departures from the published model, each also in the configuration's file:
(1) depth: ``layers`` blocks, not 48; (2) the share: experts ``held_first ..
held_first + held_count - 1`` of the ``n_routed`` are held here, the router
keeps its width, its choices and its normalisation over all the chosen, and
what the absent experts would have added is left out; (3) the vocabulary is
this chip's slice; (4) the final norm and the head sit on this stage, so
that a loss exists; (5) each block and each block of queries is recomputed
in the backward pass (``jax.checkpoint``): memory, not values; (6)
``expert_rows`` / ``steps`` in the ``counters`` collection count the rows
each held expert was chosen for, as the program's variable tree does (the
program's round sums them over its clients; ``check.reference_rounds``
averages every leaf, so only a single client's counts compare).

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves, the format the program takes weights
in.

The configuration states: a bfloat16 module (matmul operands and
activations bf16, float32 accumulation), router and softmax in float32,
norm statistics and rotary in float32, float32 parameters and aggregation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision; it has to pass wherever a control fails. The controls, each
#: the nearest precision below one the configuration states, have to fail:
#: ``act_fp8`` rounds both operands of every module matmul to e4m3 as they
#: are (no scaling: weights 0.02 wide and their cotangents fall under
#: e4m3's smallest normal, so it fails as an unscaled deployment would);
#: ``act_fp8_scaled`` first scales each operand so that its largest
#: magnitude is 128 (per tensor, per call) and lets gradients pass the
#: rounding unrounded, so what it adds is e4m3's rounding noise alone;
#: ``params_bf16`` keeps the parameters and the aggregate in bf16;
#: ``local_bf16`` keeps the parameters in bf16 through local training and
#: aggregates in float32. All rounded by ``lax.reduce_precision``, which XLA
#: keeps (a cast there and back is removed on the TPU).
VARIANTS = ("reference", "stated", "act_fp8", "params_bf16", "local_bf16",
            "act_fp8_scaled")
CONTROLS = ("act_fp8", "params_bf16", "local_bf16", "act_fp8_scaled")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix
_Q_BLOCK = 512


def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix normal(0, 0.02),
    norm scales 1, the correction bias normal(0, 0.01), counters 0."""
    m = config["model"]
    d, h = int(m["dim"]), int(m["heads"])
    dn, dr, dv, r = int(m["nope"]), int(m["rope"]), int(m["v_dim"]), int(m["kv_rank"])
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
    for i in range(int(m["layers"])):
        layer = {
            "attn_norm": ones(d), "mlp_norm": ones(d),
            "attn": {"q_proj": lin(d, h * (dn + dr)), "kv_a": lin(d, r + dr),
                     "kv_norm": ones(r), "kv_b": lin(r, h * (dn + dv)),
                     "o_proj": lin(h * dv, d)}}
        if i < int(m["first_dense"]):
            layer["mlp"] = swiglu(int(m["dense_width"]))
        else:
            e, f = int(m["held_count"]), int(m["expert_width"])
            layer["mlp"] = {
                "shared": swiglu(int(m["n_shared"]) * f),
                "router": w(d, int(m["n_routed"])),
                "e_score_correction_bias": w(int(m["n_routed"]), std=0.01),
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


def _rotary(x, theta):
    """Interleaved pairs (2i, 2i+1) turn by pos * theta^(-2i/R); x [..., T, R]."""
    t, r = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _forward(config: dict, variant: str):
    m = config["model"]
    h, dn, dr, dv = (int(m[k]) for k in ("heads", "nope", "rope", "v_dim"))
    r, eps, theta = int(m["kv_rank"]), float(m["eps"]), float(m["rope_theta"])
    top_k, n_routed = int(m["top_k"]), int(m["n_routed"])
    first, held = int(m["held_first"]), int(m["held_count"])
    scaling = float(m["routed_scaling"])
    act, mm = _ops(variant)
    prec = lax.Precision.HIGHEST

    def lin(x, p):
        return mm(x, p["kernel"]).astype(act)

    def swiglu(x, p):
        return lin(jax.nn.silu(lin(x, p["gate"])) * lin(x, p["up"]), p["down"])

    def attention(q, k, v):
        """[B,H,T,*]: every head's full score matrix, a block of queries at
        a time; softmax in float32."""
        b, _, t, _ = q.shape
        bq = min(_Q_BLOCK, t)
        scale = 1.0 / float(dn + dr) ** 0.5

        @jax.checkpoint
        def block(start):
            qb = lax.dynamic_slice_in_dim(q, start, bq, axis=2)
            s = mm(qb, jnp.swapaxes(k, -1, -2)).astype(jnp.float32) * scale
            seen = (start + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return mm(p.astype(act), v).astype(act)

        out = lax.map(block, jnp.arange(0, t, bq))       # [T/bq,B,H,bq,dv]
        return jnp.moveaxis(out, 0, 2).reshape(b, h, t, dv)

    def attn(x, p):
        b, t, _ = x.shape
        q = lin(x, p["q_proj"]).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
        ckr = lin(x, p["kv_a"])
        c = _rms(ckr[..., :r], p["kv_norm"]["scale"], eps, act)
        kv = lin(c, p["kv_b"]).reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
        k_r = _rotary(ckr[:, None, :, r:], theta)          # one key, all heads
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (b, h, t, dr))], -1)
        o = attention(q, k, kv[..., dn:])
        return lin(o.transpose(0, 2, 1, 3).reshape(b, t, h * dv), p["o_proj"])

    def choose(x, p):
        """-> (idx [N,k], weights [N,k]) over all the experts."""
        s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p["router"],
                                      precision=prec))
        _, idx = lax.top_k(lax.stop_gradient(
            s + p["e_score_correction_bias"]), top_k)
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
        for i in range(int(m["layers"])):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, p, sparse=i >= int(m["first_dense"])):
                x = x + attn(_rms(x, p["attn_norm"]["scale"], eps, act), p["attn"])
                y = _rms(x, p["mlp_norm"]["scale"], eps, act)
                if sparse:
                    y, rows, idx = moe(y, p["mlp"])
                    return x + y, rows, idx
                return x + swiglu(y, p["mlp"]), None, None

            x, rows, picks[name] = layer(x, params[name])
            if rows is None:
                del picks[name]
            else:
                old = stats[name]["mlp"]
                new_stats[name] = {"mlp": {
                    "expert_rows": old["expert_rows"] + rows,
                    "steps": old["steps"] + 1.0}}
        x = _rms(x, params["final_norm"]["scale"], eps, act)
        return (mm(x, params["lm_head"]["kernel"]).astype(jnp.float32),
                new_stats, picks)

    forward.moe = moe        # one sparse layer alone, for the share's test
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


def local_train(config: dict, variables: dict, xs, ys, ms, steps_real,
                variant: str = "reference"):
    """One client's local training from ``variables``; -> (variables, loss)."""
    key = (config["name"], variant)
    if key not in _built:
        _built[key] = _make(config, variant)
    params, stats, loss = _built[key](
        variables["params"], variables["counters"], jnp.asarray(xs),
        jnp.asarray(ys), jnp.asarray(ms), jnp.int32(steps_real))
    return {"params": params, "counters": stats}, loss


def choices(config: dict, variables: dict, ids, variant: str = "stated"):
    """Each sparse layer's chosen experts for one batch of ids, ``{layer:
    [N, top_k]}``: what ``benchmarks/routing_agreement.py`` holds against
    the program's own choices."""
    forward = _forward(config, variant)
    return jax.jit(lambda v, x: forward(v["params"], v["counters"], x)[2])(
        variables, jnp.asarray(ids))
