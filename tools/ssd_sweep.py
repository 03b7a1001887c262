#!/usr/bin/env python3
"""ssd_sweep: the state-space recurrence's chunked form alone on the chip, at
the state-space LM cell's shapes (``x [1, 4096, 64, 64]``, ``B``, ``C [1,
4096, 128]``: one sequence, 64 heads of 64 over a state of 128), at each
chunk size named.

    python tools/ssd_sweep.py [--heads H] [chunk ...]

``--heads 16`` is a group's share of heads (one of 64 chips' share of
Nemotron-3-Super's mixer: ``PERF.md``, PR 44).

For each chunk: wall-clock ms of the forward and of forward + backward
(``ops/ssd.ssd_chunked``, jitted alone, the module's bfloat16 operands), the
largest error of the output against the token-by-token recurrence
(``ssd_recurrent``, float32) beside that of the same chunk with float32
operands (what the chunked form costs in exactness and what bfloat16 does),
and the error of each gradient (``x``, ``dt``, ``A_log``, ``B``, ``C``,
``D``) against the recurrence's own, as a share of that gradient's norm, on
the first 8 heads (the recurrence's backward keeps a state a position: 2 MB
a position at 64 heads). Inputs as the mixer makes them at its
initialisation (``models/transformer.Mamba2Mixer``): ``dt = softplus(N(0,
0.9) + dt_bias)`` with ``dt_bias`` log-uniform over [0.001, 0.1], ``A``
uniform over [1, 16], ``D`` 1, ``x``, ``B``, ``C`` the SiLU of N(0, 0.6).
Fails at once without a TPU. Writes ``chiprun_out/ssd_sweep.json``;
``PERF.md`` (PR 37) has the readings that chose ``SSD_CHUNK``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

B, T, P, N = 1, 4096, 64, 128
GRAD_HEADS = 8
NAMES = ("x", "dt", "A_log", "B", "C", "D")


def _ms(fn, args, iters: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import log_uniform_steps
    from fedml_tpu.ops import ssd

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("chunks", type=int, nargs="*", default=[64, 128, 256])
    opts = ap.parse_args(argv)
    H, chunks = opts.heads, opts.chunks
    if jax.devices()[0].platform != "tpu":
        print("ssd_sweep: needs a TPU", file=sys.stderr)
        return 3
    ks = jax.random.split(jax.random.key(37), 7)
    act = lambda k, shape: jax.nn.silu(0.6 * jax.random.normal(k, shape))
    x = act(ks[0], (B, T, H, P)).astype(jnp.bfloat16)
    b, c = (act(k, (B, T, N)).astype(jnp.bfloat16) for k in ks[1:3])
    dt = jax.nn.softplus(0.9 * jax.random.normal(ks[3], (B, T, H))
                         + log_uniform_steps(ks[4], (H,)))
    a_log = jnp.log(jax.random.uniform(ks[5], (H,), jnp.float32, 1.0, 16.0))
    d = jnp.ones((H,), jnp.float32)
    ct = jax.random.normal(ks[6], (B, T, H, P))
    args = (x, dt, a_log, b, c, d)
    exact = jax.jit(ssd.ssd_recurrent)(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    print(json.dumps({"decay_mean": float(jnp.mean(jnp.exp(-dt * jnp.exp(a_log)))),
                      "out_max": scale}), flush=True)

    g = GRAD_HEADS
    few = (x[:, :, :g], dt[:, :, :g], a_log[:g], b, c, d[:g])

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * ct[:, :, :g]),
                                argnums=tuple(range(6))))(*few)

    want = grads(ssd.ssd_recurrent)
    rows = []
    for q in chunks:
        def fwd(*a, dtype=jnp.bfloat16):
            return ssd.ssd_chunked(*a, chunk=q, dtype=dtype)

        def both(*a):
            return jax.grad(lambda *a: jnp.sum(fwd(*a) * ct),
                            argnums=tuple(range(6)))(*a)

        o16 = jax.jit(fwd)(*args)
        o32 = jax.jit(lambda *a: fwd(*a, dtype=jnp.float32))(*args)
        row = {"chunk": q, "fwd_ms": _ms(jax.jit(fwd), args),
               "fwd_bwd_ms": _ms(jax.jit(both), args),
               "err_bf16": float(jnp.max(jnp.abs(o16 - exact))) / scale,
               "err_f32": float(jnp.max(jnp.abs(o32 - exact))) / scale,
               "grad_err": {n: float(jnp.linalg.norm(
                   (got - ref).astype(jnp.float32).ravel())
                   / jnp.linalg.norm(ref.astype(jnp.float32).ravel()))
                   for n, got, ref in zip(NAMES, grads(fwd), want)}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "ssd_sweep.json"), "w") as f:
        json.dump({"shape": [B, T, H, P, N],
                   "device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
