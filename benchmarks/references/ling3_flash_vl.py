"""Plain reference for ``ling3_flash_vl``: one chip's share of the text
decoder of inclusionAI/Ling-3.0-flash-VL, written out in ``jax.numpy``.
Every size is read from the configuration's ``model`` block; the equations
are the published config's, with what its keys leave open listed under
``assumed`` in the configuration's file:

- block: ``h = h + Mixer(RMSNorm(h))``, ``h = h + Mlp(RMSNorm(h))``, eps
  ``rms_norm_eps``, no biases; a final RMSNorm and an untied head; no learned
  positions. ``mixers`` names each layer's mixer;
- ``delta`` (Kimi Delta Attention, arXiv:2510.26692): ``q, k, v = SiLU(conv4(
  W x))``, the convolution causal and depthwise; heads of ``delta_head_dim``;
  ``q`` and ``k`` L2-normalised a head, ``q`` times ``head_dim^-0.5``;
  ``g = lower_bound * sigmoid(exp(A_log) * (W_f x + dt_bias))``, ``alpha =
  exp(g)`` a channel; ``beta = sigmoid(W_b x)`` a head; then, a head, with
  the state ``S [dk, dv]`` zero before position 0, **token by token**::

      S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  a ``lax.scan`` over positions inside a rematerialised scan over blocks of
  64, so that the backward pass keeps a state a block and not a position;
- ``latent`` (``q_lora_rank`` null): ``q = W_q x`` as heads of ``nope +
  rope``; ``[c, k_r] = W_kva x``; ``c <- RMSNorm(c)``; ``[k_nope, v] = W_kvb
  c`` a head; ``k = [k_nope, k_r]`` with the one ``k_r`` for all heads;
  ``use_qk_norm``: an RMSNorm over each head's whole query and key; THEN
  rotary (``rope_theta``, interleaved pairs) on the last ``rope`` channels of
  both; causal softmax of ``q . k / sqrt(nope + rope)``, the full score
  matrix of every head, a block of queries at a time;
- both mixers: each head's output RMS-normalised (one scale over its
  channels) times ONE gate a head, ``sigmoid(W_g x)``; then ``W_o``;
- layer 0: SwiGLU of ``dense_width``. After: ``s = sigmoid(W_r x)`` in
  float32 over all ``n_routed`` experts; ``b`` the expert bias (no
  gradient); a group's score = the sum of its two largest ``s + b``; the
  ``topk_group`` best of the ``n_group`` groups stand, the others' experts
  are out; the ``top_k`` largest ``s + b`` among those that stand; weights
  ``s_i / sum of the chosen s`` times ``routed_scaling``; output = the
  shared expert on every token + the weighted sum of the chosen experts'
  SwiGLUs. Every HELD expert computes every token, with the weight zero
  where it was not chosen: no sort, no kernel;
- loss: softmax cross-entropy of the next id, averaged over the tokens of
  the real sequences of a batch; plain SGD, no momentum.

Departures from the published model, each also in the configuration's file:
(1) depth; (2) the share: experts ``held_first .. held_first + held_count -
1`` are held here, the router keeps its width, groups, choices and
normalisation, and what the absent experts would have added is left out;
(3) the vocabulary is this chip's slice; (4) the final norm and the head sit
on this stage; (5) no vision tower, no multi-token-prediction head; (6) each
block, each block of queries and each block of 64 positions of the scan is
recomputed in the backward pass (``jax.checkpoint``): memory, not values;
(7) ``expert_rows`` / ``steps`` / ``group_tokens`` in the ``counters``
collection count as the program's variable tree does.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves. ``local_train`` returns HOST trees:
``harness/check.py`` keeps the state, the new tree, its weighted part and
the sum at once, and five copies of 3.3 GB do not fit the chip beside a
client's training; as numpy arrays all but the state (and the last sum) stay
on the host.

The configuration states: a bfloat16 module (matmul operands and activations
bf16, float32 accumulation), router, softmax and the scan's state in
float32, norm statistics and rotary in float32, float32 parameters and
aggregation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: ``reference``: float32 under ``jax.default_matmul_precision("highest")``,
#: the yardstick. ``stated``: the reference at the configuration's own
#: precision (the scan's products take bf16 operands, its state stays
#: float32). The controls have to fail. Four are the nearest precision below
#: one the configuration states: ``act_fp8`` / ``act_fp8_scaled`` round the
#: operands of every module matmul to e4m3 (as they are / after scaling the
#: largest magnitude to 128, gradients passing unrounded), ``params_bf16``
#: keeps parameters and aggregate in bf16, ``local_bf16`` the parameters
#: through local training. One is not a precision but the mechanism the
#: configuration exists for: ``state_cut`` is ``stated`` with the scan's
#: state dropped every ``_SCAN_BLOCK`` positions, which is what a program
#: reads that loses the carry between its chunks. ``state_bf16`` rounds the
#: scan's carried state to bf16 after every position; it is a variant and
#: NOT a control: on the chip (PERF.md section 2, PR 30) it reads 1.65 times
#: the program's ``update_l2`` and ``update_leaf_l2`` (0.046 / 0.072 against
#: 0.028 / 0.043), which no limit with room for fresh seeds on both sides
#: can tell apart. All rounding is by ``lax.reduce_precision``, which XLA
#: keeps.
VARIANTS = ("reference", "stated", "act_fp8", "params_bf16", "local_bf16",
            "act_fp8_scaled", "state_bf16", "state_cut")
CONTROLS = ("act_fp8", "params_bf16", "local_bf16", "act_fp8_scaled",
            "state_cut")
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_BF16 = ("params_bf16", "local_bf16")

#: queries per block of the score matrix; positions per block of the scan
_Q_BLOCK = 512
_SCAN_BLOCK = 64


def _round_to(a, exponent_bits: int, mantissa_bits: int):
    return lax.reduce_precision(a, exponent_bits, mantissa_bits)


def _bf16_values(tree):
    return jax.tree.map(lambda a: _round_to(a, 8, 7), tree)


def _slow_decay_bias(key, shape, bound: float):
    """The decay gate's bias at the start: a channel's ``-g`` at a zero
    pre-activation log-uniform over [0.001, 0.1] (``alpha`` 0.999 .. 0.905,
    the ``dt`` range of the public KDA init at a rate ``exp(A_log)`` of 1),
    as its logit under the bound: ``g = bound * sigmoid(dt_bias)`` there.
    ``W_f x`` has a deviation near 1 at the start, which spreads ``alpha``
    to about 0.7 .. 0.9997 (median 0.99): a state lives for tens to
    thousands of positions, as a trained model's does."""
    rate = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    share = rate / -bound
    return jnp.log(share) - jnp.log1p(-share)


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: every matrix and convolution
    normal(0, 0.02), norm scales 1, ``A_log`` 0, ``dt_bias`` where a channel
    decays slowly (``_slow_decay_bias``), the expert bias normal(0, 0.01),
    counters 0."""
    m = config["model"]
    d, h = int(m["dim"]), int(m["heads"])
    dn, dr, dv, r = (int(m[k]) for k in ("nope", "rope", "v_dim", "kv_rank"))
    hd, kc = int(m["delta_head_dim"]), int(m["delta_conv"])
    bound = float(m["delta_lower_bound"])
    vocab = int(config["data"]["vocab"])
    keys = iter(jax.random.split(key, 24 * int(m["layers"]) + 4))

    def w(*shape, std=0.02):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(a, b):
        return {"kernel": w(a, b)}

    def ones(n):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(width):
        return {"gate": lin(d, width), "up": lin(d, width), "down": lin(width, d)}

    def gate(width):
        return {"norm": ones(width), "proj": lin(d, h)}

    params, stats = {"embed": w(vocab, d)}, {}
    for i, mixer in enumerate(m["mixers"]):
        layer = {"attn_norm": ones(d), "mlp_norm": ones(d)}
        if mixer == "latent":
            layer["attn"] = {
                "q_proj": lin(d, h * (dn + dr)), "kv_a": lin(d, r + dr),
                "kv_norm": ones(r), "kv_b": lin(r, h * (dn + dv)),
                "q_norm": ones(dn + dr), "k_norm": ones(dn + dr),
                "out_gate": gate(dv), "o_proj": lin(h * dv, d)}
        else:
            layer["delta"] = {
                "q_proj": lin(d, h * hd), "k_proj": lin(d, h * hd),
                "v_proj": lin(d, h * hd), "f_proj": lin(d, h * hd),
                "b_proj": lin(d, h), "q_conv": w(kc, h * hd),
                "k_conv": w(kc, h * hd), "v_conv": w(kc, h * hd),
                "A_log": jnp.zeros((h,), jnp.float32),
                "dt_bias": _slow_decay_bias(next(keys), (h * hd,), bound),
                "out_gate": gate(hd), "o_proj": lin(h * hd, d)}
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
                "steps": jnp.zeros((), jnp.float32),
                "group_tokens": jnp.zeros((), jnp.float32)}}
        params[f"layer_{i}"] = layer
    params["final_norm"] = ones(d)
    params["lm_head"] = lin(d, vocab)
    return {"params": params, "counters": stats}


def _ops(variant: str):
    """(activation dtype, matmul, the operands' rounding) of one variant."""
    if variant == "reference":
        return (jnp.float32, lambda a, b: jnp.matmul(
            a, b, precision=lax.Precision.HIGHEST), lambda a: a)

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

    return jnp.bfloat16, mm, operand


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


def delta_rule(q, k, v, g, beta, operand=lambda a: a, state_bf16=False,
               state_cut=False):
    """The recurrence, one position at a time: ``q, k, g [B, H, T, dk]``,
    ``v [B, H, T, dv]``, ``beta [B, H, T]`` -> ``o [B, H, T, dv]`` float32.
    ``operand`` rounds what the configuration's precision computes in the
    module's dtype (the factors of the two contractions with the state);
    ``state_bf16`` rounds the carried state to bf16 after every position;
    ``state_cut`` starts every block of ``_SCAN_BLOCK`` positions from a
    zero state (controls, both)."""
    f32 = jnp.float32
    b, h, t, dk = q.shape
    blk = min(_SCAN_BLOCK, t)

    def low(a):
        return operand(a).astype(f32)

    def position(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.sum(low(s) * low(kt)[..., None], axis=-2))
        s = s + low(kt)[..., None] * low(u)[..., None, :]
        if state_bf16:
            s = _round_to(s, 8, 7)
        return s, jnp.sum(low(s) * low(qt)[..., None], axis=-2)

    @jax.checkpoint
    def block(s, xs):
        return lax.scan(position, jnp.zeros_like(s) if state_cut else s, xs)

    def blocks(a):       # [B, H, T, ...] -> [T/blk, blk, B, H, ...]
        a = jnp.moveaxis(a.astype(f32), 2, 0)
        return a.reshape((t // blk, blk) + a.shape[1:])

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, o = lax.scan(block, s0, tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 2)


def chosen_groups(biased, n_group: int, topk_group: int):
    """``biased [N, E]`` -> ``[N, n_group]`` bool, the naive way: a group's
    score is the sum of its two largest entries (by a sort), and a group
    stands if fewer than ``topk_group`` groups score higher (ties to the
    lower index, as a stable sort gives them)."""
    n, e = biased.shape
    per = jnp.sort(biased.reshape(n, n_group, e // n_group), axis=-1)
    score = per[..., -1] + per[..., -2]
    order = jnp.argsort(-score, axis=-1, stable=True)[:, :topk_group]
    return jnp.any(order[..., None] == jnp.arange(n_group), axis=1)


def _forward(config: dict, variant: str):
    m = config["model"]
    h, dn, dr, dv = (int(m[k]) for k in ("heads", "nope", "rope", "v_dim"))
    r, eps, theta = int(m["kv_rank"]), float(m["eps"]), float(m["rope_theta"])
    top_k, n_routed = int(m["top_k"]), int(m["n_routed"])
    n_group, topk_group = int(m["n_group"]), int(m["topk_group"])
    first, held = int(m["held_first"]), int(m["held_count"])
    scaling = float(m["routed_scaling"])
    hd, bound = int(m["delta_head_dim"]), float(m["delta_lower_bound"])
    act, mm, operand = _ops(variant)
    prec = lax.Precision.HIGHEST

    def lin(x, p):
        return mm(x, p["kernel"]).astype(act)

    def swiglu(x, p):
        return lin(jax.nn.silu(lin(x, p["gate"])) * lin(x, p["up"]), p["down"])

    def head_gate(o, x, p):
        """o [B, T, H, dv]: a head's RMS norm, times one gate a head."""
        gate = mm(x, p["proj"]["kernel"]).astype(jnp.float32)
        o = _rms(o, p["norm"]["scale"], eps, jnp.float32)
        return (o * jax.nn.sigmoid(gate)[..., None]).astype(act)

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

    def latent(x, p):
        b, t, _ = x.shape
        q = lin(x, p["q_proj"]).reshape(b, t, h, dn + dr).transpose(0, 2, 1, 3)
        ckr = lin(x, p["kv_a"])
        c = _rms(ckr[..., :r], p["kv_norm"]["scale"], eps, act)
        kv = lin(c, p["kv_b"]).reshape(b, t, h, dn + dv).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(ckr[:, None, :, r:], (b, h, t, dr))],
            -1)

        def normed_turned(a, scale):
            a = _rms(a, scale, eps, act)
            return jnp.concatenate([a[..., :dn], _rotary(a[..., dn:], theta)], -1)

        o = attention(normed_turned(q, p["q_norm"]["scale"]),
                      normed_turned(k, p["k_norm"]["scale"]), kv[..., dn:])
        o = head_gate(o.transpose(0, 2, 1, 3), x, p["out_gate"])
        return lin(o.reshape(b, t, h * dv), p["o_proj"])

    def conv(a, w):
        """Causal, depthwise: y_t = sum_i w[i] a_{t-K+1+i}; a [B, T, C]."""
        kc, t = w.shape[0], a.shape[1]
        ap = jnp.pad(a.astype(jnp.float32), ((0, 0), (kc - 1, 0), (0, 0)))
        return sum(ap[:, i:i + t] * w[i] for i in range(kc))

    def delta(x, p):
        b, t, _ = x.shape

        def heads(a):
            return a.reshape(b, t, h, hd).transpose(0, 2, 1, 3)

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)

        q, k, v = (heads(jax.nn.silu(conv(lin(x, p[n + "_proj"]), p[n + "_conv"])))
                   for n in ("q", "k", "v"))
        q, k = unit(q) * hd ** -0.5, unit(k)
        f = lin(x, p["f_proj"]).astype(jnp.float32) + p["dt_bias"]
        g = bound * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None, None] * heads(f))
        beta = jax.nn.sigmoid(
            mm(x, p["b_proj"]["kernel"]).astype(jnp.float32)).transpose(0, 2, 1)
        o = delta_rule(q.astype(act), k.astype(act), v.astype(act), g, beta,
                       operand, variant == "state_bf16",
                       variant == "state_cut")
        o = head_gate(o.transpose(0, 2, 1, 3), x, p["out_gate"])
        return lin(o.reshape(b, t, h * hd), p["o_proj"])

    def choose(x, p):
        """-> (idx [N,k], weights [N,k], groups [N,G]) over all the experts."""
        s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p["router"],
                                      precision=prec))
        biased = lax.stop_gradient(s + p["e_score_correction_bias"])
        groups = chosen_groups(biased, n_group, topk_group)
        stands = jnp.repeat(groups, n_routed // n_group, axis=1)
        _, idx = lax.top_k(jnp.where(stands, biased, -jnp.inf), top_k)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        return (idx, chosen / jnp.sum(chosen, -1, keepdims=True) * scaling,
                groups)

    def moe(x, p):
        b, t, d = x.shape
        xf = x.reshape(b * t, d)
        idx, weights, groups = choose(xf, p)
        # weight of every expert on every token, zero where not chosen
        full = jnp.sum(jax.nn.one_hot(idx, n_routed, dtype=jnp.float32)
                       * weights[..., None], axis=1)              # [N, E]
        mine = full[:, first:first + held]
        rows = jnp.sum(((idx >= first) & (idx < first + held))[..., None]
                       * jax.nn.one_hot(idx - first, held, dtype=jnp.float32),
                       axis=(0, 1))
        size = n_routed // n_group
        reached = jnp.sum(jnp.any(
            groups[:, first // size:(first + held - 1) // size + 1], axis=1))

        @jax.checkpoint
        def one(carry, e):
            w_e = lax.dynamic_index_in_dim(mine, e, axis=1, keepdims=False)
            y = mm(jax.nn.silu(mm(xf, p["gate"][e]).astype(act))
                   * mm(xf, p["up"][e]).astype(act), p["down"][e]).astype(act)
            return carry + w_e[:, None] * y.astype(jnp.float32), None

        routed, _ = lax.scan(one, jnp.zeros((b * t, d), jnp.float32),
                             jnp.arange(held))
        out = swiglu(xf, p["shared"]) + routed.astype(act)
        return out.reshape(b, t, d), (rows, reached.astype(jnp.float32)), idx

    def forward(params, stats, ids):
        x = params["embed"][ids].astype(act)
        new_stats, picks = {}, {}
        for i, mixer in enumerate(m["mixers"]):
            name = f"layer_{i}"

            @jax.checkpoint
            def layer(x, p, sparse=i >= int(m["first_dense"]), mixer=mixer):
                y = _rms(x, p["attn_norm"]["scale"], eps, act)
                x = x + (latent(y, p["attn"]) if mixer == "latent"
                         else delta(y, p["delta"]))
                y = _rms(x, p["mlp_norm"]["scale"], eps, act)
                if sparse:
                    y, counts, idx = moe(y, p["mlp"])
                    return x + y, counts, idx
                return x + swiglu(y, p["mlp"]), None, None

            x, counts, idx = layer(x, params[name])
            if counts is not None:
                picks[name] = idx
                old = stats[name]["mlp"]
                new_stats[name] = {"mlp": {
                    "expert_rows": old["expert_rows"] + counts[0],
                    "steps": old["steps"] + 1.0,
                    "group_tokens": old["group_tokens"] + counts[1]}}
        x = _rms(x, params["final_norm"]["scale"], eps, act)
        return (mm(x, params["lm_head"]["kernel"]).astype(jnp.float32),
                new_stats, picks)

    forward.moe = moe        # one sparse layer alone, for the share's test
    forward.choose = choose
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
    each (30 GB at 822 M parameters) on a machine of 40 GiB, so whatever the
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
