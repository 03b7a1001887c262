#!/usr/bin/env python3
"""cca_sweep: what compressed convolutional attention adds between its
projections and its scores, alone on the chip at the cell's shapes (``q~ [2,
4096, 8 x 128]``, ``k~`` and ``v [2, 4096, 2 x 128]`` in bfloat16: two
sequences, ten heads of 128 through both convolutions).

    python tools/cca_sweep.py

``models/transformer.cca_mix``, the module's own function, jitted alone (the
means, the depthwise convolution, the head-wise one as one batched ``[..,
128] x [128, 128]`` product a tap, the normalisation with the key
temperature, the value shift): forward and forward + backward wall-clock ms;
the largest error of ``q``, ``k`` against the same shifted sums in float32
at the highest matmul precision, and each gradient's error (``q~``, ``k~``,
``v``, both kernels, both biases, the temperature) as a share of that
gradient's norm. ``PERF.md`` section 6 (PR 39) has the table that chose the
head-wise form: ONE ``[.., 256] x [256, 128]`` product a head read 0.574 /
1.536 ms and a product a head and tap 0.630 / 2.196 beside this form's 0.576
/ 1.486, so neither stayed. The wide experts' grouped products at a capacity
and the rows in it: ``tools/rows_sweep.py --width 2048 --choices 1 --routed
16 --held 8``.

Fails at once without a TPU. Writes ``chiprun_out/cca_sweep.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

B, T, H, G, E = 2, 4096, 8, 2, 128
#: the module's precision (a rehearsal on the CPU, whose dot has no bfloat16
#: operands, sets float32)
DTYPE = "bfloat16"
NAMES = ("q", "k", "v", "w0", "b0", "w1", "b1", "temp")


def _ms(fn, args, iters: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def mixing(dtype):
    """``(q~, k~, v, w0, b0, w1, b1, temp) -> (q, k, v)``: the module's own
    ``cca_mix`` at the cell's heads, operands in ``dtype``."""
    import functools

    from fedml_tpu.models.transformer import cca_mix

    return functools.partial(cca_mix, heads=H, kv_heads=G, dtype=dtype)


def sweep_mixing() -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import fan_in_uniform

    ks = jax.random.split(jax.random.key(39), 11)
    dtype = jnp.dtype(DTYPE)
    q = (0.9 * jax.random.normal(ks[0], (B, T, H * E))).astype(dtype)
    k = (0.9 * jax.random.normal(ks[1], (B, T, G * E))).astype(dtype)
    v = (0.9 * jax.random.normal(ks[2], (B, T, G * E))).astype(dtype)
    w0 = fan_in_uniform(2)(ks[3], (2, (H + G) * E), jnp.float32)
    b0 = fan_in_uniform(2)(ks[4], ((H + G) * E,), jnp.float32)
    w1 = fan_in_uniform(2 * E)(ks[5], (2, H + G, E, E), jnp.float32)
    b1 = fan_in_uniform(2 * E)(ks[6], (H + G, E), jnp.float32)
    temp = jnp.ones((G,), jnp.float32)
    args = (q, k, v, w0, b0, w1, b1, temp)
    cts = (jax.random.normal(ks[7], (B, T, H, E)),
           jax.random.normal(ks[8], (B, T, G, E)),
           jax.random.normal(ks[9], (B, T, G * E)))

    def loss(fn):
        return lambda *a: sum(jnp.sum(o.astype(jnp.float32) * c)
                              for o, c in zip(fn(*a), cts))

    def exact(*a):
        with jax.default_matmul_precision("highest"):
            return mixing(jnp.float32)(*a)

    want = jax.jit(exact)(*args)
    want_g = jax.jit(jax.grad(loss(exact), argnums=tuple(range(8))))(*args)
    fn = mixing(dtype)
    fwd = jax.jit(fn)
    both = jax.jit(jax.grad(loss(fn), argnums=tuple(range(8))))
    got, got_g = fwd(*args), both(*args)
    row = {"fwd_ms": _ms(fwd, args), "fwd_bwd_ms": _ms(both, args),
           "err": {n: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                   for n, a, b in zip("qk", got, want)},
           "grad_err": {n: float(
               jnp.linalg.norm((a.astype(jnp.float32)
                                - b.astype(jnp.float32)).ravel())
               / jnp.linalg.norm(b.astype(jnp.float32).ravel()))
               for n, a, b in zip(NAMES, got_g, want_g)}}
    print(json.dumps(row), flush=True)
    return row


def main(argv=None) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("cca_sweep: needs a TPU", file=sys.stderr)
        return 3
    doc = {"shape": [B, T, H, G, E], "device": jax.devices()[0].device_kind,
           "mixing": sweep_mixing()}
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "cca_sweep.json"), "w") as f:
        json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
