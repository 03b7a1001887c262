#!/usr/bin/env python3
"""kda_sweep: the delta rule's chunked scan alone on the chip, at the hybrid
LM cell's shapes (``[1, 32, 4096, 128]``: one sequence, 32 heads of 128), at
each ``chunk:keep`` named (default 32:1 32:4 64:1 128:1; sub-blocks of 16;
``keep`` chunk steps between two states the backward pass keeps, the
module's own where none is given).

    python tools/kda_sweep.py [chunk[:keep] ...]

For each chunk: wall-clock ms of the forward and of forward + backward
(``ops/kda.kda_chunked``, jitted alone, the module's bfloat16 operands), and
the largest error of the output against the token-by-token recurrence
(``kda_recurrent``, float32) beside that of the same chunk with float32
operands: what the chunked form costs in exactness and what bfloat16 does.
Inputs as the module makes them at its initialisation: unit keys, queries
of norm ``128^-0.5``, log-decays ``-5 sigmoid(N(0, 1))``. Fails at once
without a TPU. Writes ``chiprun_out/kda_sweep.json``; ``PERF.md`` (PR 30)
has the readings that chose ``KDA_CHUNK``.
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

B, H, T, D = 1, 32, 4096, 128


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

    from fedml_tpu.ops.kda import KDA_KEEP, KDA_SUB, kda_chunked, kda_recurrent

    named = (argv if argv is not None else sys.argv[1:]) or [
        "32:1", "32:4", "64:1", "128:1"]
    forms = [(int(c), int(k or KDA_KEEP)) for c, _, k in
             (a.partition(":") for a in named)]
    if jax.devices()[0].platform != "tpu":
        print("kda_sweep: needs a TPU", file=sys.stderr)
        return 3
    ks = jax.random.split(jax.random.key(30), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(ks[0], (B, H, T, D))) * D ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (B, H, T, D))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, T, D)).astype(jnp.bfloat16)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, T, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, T)))
    ct = jax.random.normal(ks[5], (B, H, T, D))
    args = (q, k, v, g, beta)
    exact = jax.jit(kda_recurrent)(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    rows = []
    for c, keep in forms:
        def fwd(*a, dtype=jnp.bfloat16):
            return kda_chunked(*a, chunk=c, sub=KDA_SUB, keep=keep, dtype=dtype)

        def both(*a):
            return jax.grad(lambda *a: jnp.sum(fwd(*a) * ct),
                            argnums=(0, 1, 2, 3, 4))(*a)

        o16 = jax.jit(fwd)(*args)
        o32 = jax.jit(lambda *a: fwd(*a, dtype=jnp.float32))(*args)
        row = {"chunk": c, "sub": KDA_SUB, "keep": keep,
               "fwd_ms": _ms(jax.jit(fwd), args),
               "fwd_bwd_ms": _ms(jax.jit(both), args),
               "err_bf16": float(jnp.max(jnp.abs(o16 - exact))) / scale,
               "err_f32": float(jnp.max(jnp.abs(o32 - exact))) / scale}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "kda_sweep.json"), "w") as f:
        json.dump({"shape": [B, H, T, D], "device": jax.devices()[0].device_kind,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
