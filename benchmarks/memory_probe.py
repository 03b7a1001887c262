#!/usr/bin/env python3
"""What the TPU allocator's counters hold: a reading, not part of a run.

    python3 benchmarks/memory_probe.py

Runs three jitted programs of known temporary size (XLA's own
``memory_analysis``) beside a live array of known size and prints
``memory_stats()`` after each step. It shows whether a running program's
temporaries are counted in ``peak_bytes_in_use`` or in ``bytes_reserved``,
and whether the reserved region follows the largest program run so far.
PERF.md section 2 quotes its output; ``harness/device.memory_split`` rests
on it.
"""

from __future__ import annotations

import sys

KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "largest_alloc_size")


def main() -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"memory_probe: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3

    def stats(tag):
        s = dev.memory_stats()
        print(f"{tag}: " + "  ".join(f"{k} {s.get(k)}" for k in KEYS), flush=True)

    def chain(x):
        # three products that have to exist at once: temporaries of 3 x |x|
        a, b = jnp.sin(x) @ x, jnp.cos(x) @ x
        return ((a @ b) * a + b).sum()

    stats("start")
    live = jax.block_until_ready(jnp.ones((8192, 8192), jnp.float32))
    stats("after a live array of 268435456 bytes")
    for n in (4096, 16384, 4096):
        x = jax.block_until_ready(jnp.ones((n, n), jnp.bfloat16))
        compiled = jax.jit(chain).lower(x).compile()
        m = compiled.memory_analysis()
        print(f"program n={n}: argument {m.argument_size_in_bytes}  "
              f"output {m.output_size_in_bytes}  temp {m.temp_size_in_bytes}",
              flush=True)
        stats(f"  compiled n={n}, not yet run")
        jax.block_until_ready(compiled(x))
        stats(f"  after running n={n}")
        del x, compiled
    del live
    stats("after freeing every array")
    return 0


if __name__ == "__main__":
    sys.exit(main())
