#!/usr/bin/env python3
"""kda_sweep: the delta rule's chunked scan alone on the chip, at the hybrid
LM cell's shapes (``[1, 32, 4096, 128]``: one sequence, 32 heads of 128), at
each form named ``impl:chunk[:keep[:heads]]`` (sub-blocks of 16; ``keep``
chunk steps between two states the backward pass keeps and ``heads`` a grid
step of the kernel pair, the module's own where none is given; ``impl``
``xla`` the ``jax.numpy`` scan, ``pallas`` the kernel pair).

    python tools/kda_sweep.py [impl:chunk[:keep[:heads]] ...]

For each form: wall-clock ms of the forward and of forward + backward
(``ops/kda.kda_chunked``, jitted alone, the module's bfloat16 operands), and
the largest error of the output against the token-by-token recurrence
(``kda_recurrent``, float32) beside that of the same form with float32
operands: what the chunked form costs in exactness and what bfloat16 does.
For a kernel form also each kernel alone (``fwd_kernel_ms``,
``fwd_kernel_states_ms`` the same writing the kept states, ``bwd_kernel_ms``),
and for every chunk once the ``jax.numpy`` intra-chunk part alone
(``intra_fwd_ms``, ``intra_fwd_bwd_ms``: the decayed products, the masks, the
triangular solve, all chunks at once; the kernels make a chunk's operands
themselves and do not run it).
Inputs as the module makes them at its initialisation: unit keys, queries
of norm ``128^-0.5``, log-decays ``-5 sigmoid(N(0, 1))``. Fails at once
without a TPU. Writes ``chiprun_out/kda_sweep.json``; ``PERF.md`` (PR 30,
PR 31) has the readings that chose ``KDA_CHUNK``, ``KDA_KEEP`` and
``KDA_HEADS``. It sets the module's ``KDA_HEADS`` for a row; the program has
no option for it.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

B, H, T, D = 1, 32, 4096, 128

DEFAULT = ["xla:32:4", "pallas:32:1:4", "pallas:32:2:4", "pallas:32:2:2",
           "pallas:32:4:2", "pallas:64:1:2", "pallas:64:1:4", "pallas:64:2:2",
           "pallas:64:4:1", "pallas:128:1:2", "pallas:128:2:1", "pallas:16:4:4"]


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

    from fedml_tpu.ops import kda

    named = (argv if argv is not None else sys.argv[1:]) or DEFAULT
    forms = []
    for a in named:
        impl, c, *rest = a.split(":")
        forms.append((impl, int(c), int(rest[0]) if rest else kda.KDA_KEEP,
                      int(rest[1]) if rest[1:] else kda.KDA_HEADS))
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
    exact = jax.jit(kda.kda_recurrent)(*args)
    scale = float(jnp.max(jnp.abs(exact)))
    rows, intra_ms = [], {}
    for impl, c, keep, heads in forms:
        kda.KDA_HEADS = heads
        jax.clear_caches()       # ``kda_chunked``'s jit does not key the heads

        def fwd(*a, dtype=jnp.bfloat16):
            return kda.kda_chunked(*a, chunk=c, sub=kda.KDA_SUB, keep=keep,
                                   dtype=dtype, impl=impl)

        def both(*a):
            return jax.grad(lambda *a: jnp.sum(fwd(*a) * ct),
                            argnums=(0, 1, 2, 3, 4))(*a)

        def chunks(a):
            return a.reshape(a.shape[:2] + (T // c, c) + a.shape[3:])

        intra = jax.jit(functools.partial(kda._intra, sub=kda.KDA_SUB,
                                          dtype=jnp.bfloat16))
        chunked = tuple(chunks(a) for a in args)
        if c not in intra_ms:
            def intra_both(*a):
                return jax.grad(lambda *a: sum(
                    jnp.sum(p.astype(jnp.float32)) for p in intra(*a)),
                    argnums=(0, 1, 2, 3, 4))(*a)

            intra_ms[c] = (_ms(intra, chunked),
                           _ms(jax.jit(intra_both), chunked))
        o16 = jax.jit(fwd)(*args)
        o32 = jax.jit(lambda *a: fwd(*a, dtype=jnp.float32))(*args)
        row = {"impl": impl, "chunk": c, "sub": kda.KDA_SUB, "keep": keep,
               "fwd_ms": _ms(jax.jit(fwd), args),
               "fwd_bwd_ms": _ms(jax.jit(both), args),
               "intra_fwd_ms": intra_ms[c][0],
               "intra_fwd_bwd_ms": intra_ms[c][1],
               "err_bf16": float(jnp.max(jnp.abs(o16 - exact))) / scale,
               "err_f32": float(jnp.max(jnp.abs(o32 - exact))) / scale}
        if impl == "pallas":
            kp = keep if (T // c) % keep == 0 else 1     # as ``kda_chunked``
            common = dict(chunk=c, sub=kda.KDA_SUB, group=kp, heads=heads,
                          dtype=jnp.bfloat16, interpret=kda.interpret())
            flat = tuple(a.reshape(B * H, T, -1) for a in args)
            kept = kda._kda_fwd(*flat, keep_states=True, **common)[1]
            row.update(
                heads=heads,
                fwd_kernel_ms=_ms(functools.partial(
                    kda._kda_fwd, keep_states=False, **common), flat),
                fwd_kernel_states_ms=_ms(functools.partial(
                    kda._kda_fwd, keep_states=True, **common), flat),
                bwd_kernel_ms=_ms(functools.partial(kda._kda_bwd, **common),
                                  flat + (kept, ct.reshape(B * H, T, D))))
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "kda_sweep.json"), "w") as f:
        json.dump({"shape": [B, H, T, D], "device": jax.devices()[0].device_kind,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
