"""Plain reference for ``resnet56_cifar10``: the CIFAR ResNet of He et al.
(arXiv:1512.03385, section 4.2), written out in ``jax.numpy`` and
``lax.conv_general_dilated``.

3 stages of ``blocks_per_stage`` basic blocks at the configuration's widths;
every conv 3x3, no bias, followed by batch normalisation (statistics over
batch and space, momentum 0.9, eps 1e-5); the first block of stages 2 and 3
halves the resolution and projects its shortcut by a strided 1x1 conv +
batch normalisation; global average pool; one dense layer. Softmax
cross-entropy averaged over the real rows of a batch; SGD with momentum.
float32 throughout at ``highest`` matmul precision. Departure from the
paper, taken from the program: the projection shortcut (the paper's option
B) where the paper's CIFAR nets pad with zeros (option A). A batch's padded
rows (zeros, masked out of the loss) take part in its batch statistics, as
they do in the program.

Independent of ``fedml_tpu``: the only thing shared with the program is the
naming of the variable tree's leaves (flax's ``Conv_i`` / ``BatchNorm_i`` /
``BasicBlock_i`` / ``Dense_0``), the format the program takes weights in.

The configuration states: a bf16 module (conv and normalisation outputs in
bf16, statistics and the dense head in float32), float32 parameters and
momentum, float32 aggregation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.references._sgd import make_local_train

#: ``reference`` is the yardstick: float32 at ``highest`` precision.
#: ``stated`` is the reference at the configuration's own precision (bf16
#: module, float32 parameters) and has to pass wherever a control fails.
#: The controls, each the nearest precision below one the configuration
#: states, have to fail (``tests/benchmark/test_references.py`` at a tiny
#: size; PERF.md section 2 has the chip's readings at the cell's own):
#: ``params_bf16`` keeps parameters, momentum and the aggregate in bf16;
#: ``local_bf16`` keeps parameters and momentum in bf16 through local
#: training and aggregates in float32; ``act_fp8`` rounds both operands of
#: every convolution to fp8 (e4m3).
VARIANTS = ("reference", "stated", "params_bf16", "local_bf16", "act_fp8")
CONTROLS = ("params_bf16", "local_bf16", "act_fp8")
#: variants whose aggregate is kept in a type of its own (``check.py``
#: rounds the weighted mean of the clients' results to it)
AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}
_STORE_DTYPE = {"params_bf16": jnp.bfloat16, "local_bf16": jnp.bfloat16}

_EPS = 1e-5
_BN_MOMENTUM = 0.9


def _blocks(config: dict):
    """(name, filters, stride, projects) of every basic block, in order."""
    m = config["model"]
    out, i, prev = [], 0, m["widths"][0]
    for stage, f in enumerate(m["widths"]):
        for b in range(int(m["blocks_per_stage"])):
            s = 2 if stage > 0 and b == 0 else 1
            out.append((f"BasicBlock_{i}", f, s, s != 1 or f != prev))
            prev, i = f, i + 1
    return out


def init(key: jax.Array, config: dict) -> dict:
    """Seeded weights in the program's tree: He-normal conv kernels,
    LeCun-normal dense kernel, unit scales, zero biases and means, unit
    variances."""
    m, d = config["model"], config["data"]
    keys = iter(jax.random.split(key, 4 * len(_blocks(config)) + 4))

    def conv(k, cin, cout):
        std = (2.0 / (k * k * cin)) ** 0.5
        return {"kernel": std * jax.random.normal(
            next(keys), (k, k, cin, cout), jnp.float32)}

    def bn(c, scale=1.0):
        return ({"scale": jnp.full((c,), scale, jnp.float32),
                 "bias": jnp.zeros((c,), jnp.float32)},
                {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)})

    # Goyal et al., arXiv:1706.02677 section 5.1: a block's last scale may
    # start at zero, so that every block starts as the identity
    last_scale = 0.0 if m.get("zero_init_residual") else 1.0
    cin = int(d["input_shape"][-1])
    w0 = int(m["widths"][0])
    params, stats = {"Conv_0": conv(3, cin, w0)}, {}
    params["BatchNorm_0"], stats["BatchNorm_0"] = bn(w0)
    prev = w0
    for name, f, _s, proj in _blocks(config):
        p, s = {}, {}
        p["Conv_0"] = conv(3, prev, f)
        p["BatchNorm_0"], s["BatchNorm_0"] = bn(f)
        p["Conv_1"] = conv(3, f, f)
        p["BatchNorm_1"], s["BatchNorm_1"] = bn(f, last_scale)
        if proj:
            p["Conv_2"] = conv(1, prev, f)
            p["BatchNorm_2"], s["BatchNorm_2"] = bn(f)
        params[name], stats[name] = p, s
        prev = f
    c = int(d["classes"])
    params["Dense_0"] = {
        "kernel": jax.random.normal(next(keys), (prev, c), jnp.float32)
        / jnp.sqrt(float(prev)),
        "bias": jnp.zeros((c,), jnp.float32)}
    return {"params": params, "batch_stats": stats}


def _forward(config: dict, variant: str):
    low = variant != "reference"
    act = jnp.bfloat16 if low else jnp.float32
    prec = None if low else lax.Precision.HIGHEST

    def operand(a):
        if variant == "act_fp8":
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(act)

    def conv(x, p, stride):
        return lax.conv_general_dilated(
            operand(x), operand(p["kernel"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)

    def bn(x, p, s):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.maximum(jnp.mean(xf * xf, axis=(0, 1, 2)) - mean * mean, 0.0)
        y = (xf - mean) * lax.rsqrt(var + _EPS) * p["scale"] + p["bias"]
        new = {"mean": _BN_MOMENTUM * s["mean"] + (1 - _BN_MOMENTUM) * mean,
               "var": _BN_MOMENTUM * s["var"] + (1 - _BN_MOMENTUM) * var}
        return y.astype(act), new

    blocks = _blocks(config)

    def forward(params, stats, x):
        new = {}
        y = conv(x, params["Conv_0"], 1)
        y, new["BatchNorm_0"] = bn(y, params["BatchNorm_0"], stats["BatchNorm_0"])
        y = jax.nn.relu(y)
        for name, _f, stride, proj in blocks:
            p, s, ns = params[name], stats[name], {}
            z = conv(y, p["Conv_0"], stride)
            z, ns["BatchNorm_0"] = bn(z, p["BatchNorm_0"], s["BatchNorm_0"])
            z = conv(jax.nn.relu(z), p["Conv_1"], 1)
            z, ns["BatchNorm_1"] = bn(z, p["BatchNorm_1"], s["BatchNorm_1"])
            if proj:
                y = conv(y, p["Conv_2"], stride)
                y, ns["BatchNorm_2"] = bn(y, p["BatchNorm_2"], s["BatchNorm_2"])
            y = jax.nn.relu(z + y)
            new[name] = ns
        pooled = jnp.mean(y.astype(jnp.float32), axis=(1, 2))
        d = params["Dense_0"]
        logits = jnp.dot(pooled, d["kernel"], precision=prec) + d["bias"]
        return logits, new

    return forward


def _make(config: dict, variant: str):
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    forward = _forward(config, variant)

    def loss_fn(params, stats, bx, by, bm):
        logits, new_stats = forward(params, stats, bx)
        logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        per = -jnp.take_along_axis(logz, by[:, None], axis=-1)[:, 0]
        return jnp.sum(per * bm) / jnp.maximum(jnp.sum(bm), 1.0), new_stats

    r = config["recipe"]
    return make_local_train(
        loss_fn, lr=float(r["lr"]), momentum=float(r["momentum"]),
        store_dtype=_STORE_DTYPE.get(variant))


_built: dict = {}


def local_train(config: dict, variables: dict, xs, ys, ms, steps_real,
                variant: str = "reference"):
    """One client's local training from ``variables``; -> (variables, loss)."""
    key = (config["name"], variant)
    if key not in _built:
        _built[key] = _make(config, variant)
    params, stats, loss = _built[key](
        variables["params"], variables["batch_stats"], jnp.asarray(xs),
        jnp.asarray(ys), jnp.asarray(ms), jnp.int32(steps_real))
    return {"params": params, "batch_stats": stats}, loss
