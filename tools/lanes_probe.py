"""Attribution probe for the spatial-in-lanes conv kernel (H6).

A single op timed from the host measures dispatch, not the op — so each
probe is a WHOLE jitted program: a lax.scan carrying the activation
through ITERS invocations of one conv variant, timed end-to-end with a
float() barrier. The scan's carried data dependency serializes the
iterations, so (total_time / ITERS) is an honest amortized per-invocation
cost including Mosaic dispatch and patch-build work.

Variants isolate where time goes:
  xla        — lax.conv_general_dilated on the lanes layout (control)
  kernel     — the full spatial-in-lanes kernel
  patches    — kernel with the dot removed (copies P rows to the output):
               per-call + grid + patch-build cost, no MXU work
  copy       — kernel body is a single slice copy: per-call + grid floor
  wgrad      — the wgrad kernel (patch build + A*B^T dot)

Run on the TPU: python tools/lanes_probe.py
Env: PROBE_ITERS (default 200), PROBE_BATCH (64), PROBE_IMGS_PER_STEP (1).
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops import conv_lanes as cl

ITERS = int(os.environ.get("PROBE_ITERS", "200"))
BATCH = int(os.environ.get("PROBE_BATCH", "64"))


def _run_once(fn, *args):
    out = jax.jit(fn)(*args)
    float(jnp.sum(out[0] if isinstance(out, tuple) else out).astype(jnp.float32))


def _time(make_fn, *args):
    """Two-point measurement: every jit call carries a fixed dispatch +
    sync cost, so time scans of length N and 10N and report
    (T_10N - T_N) / 9N — the fixed cost cancels."""
    short, long_ = ITERS, ITERS * 10
    fs, fl = make_fn(short), make_fn(long_)
    _run_once(fs, *args)          # warm both compiles
    _run_once(fl, *args)
    t0 = time.perf_counter()
    _run_once(fs, *args)
    ts = time.perf_counter() - t0
    t0 = time.perf_counter()
    _run_once(fl, *args)
    tl = time.perf_counter() - t0
    return (tl - ts) / (long_ - short) * 1e6  # us / iter


def _scan(body, x, w):
    def make(n):
        def step(c, _):
            y = body(c, w)
            # renormalize so the carry doesn't overflow across the scan
            return (y / (jnp.max(jnp.abs(y)) + 1e-3)).astype(x.dtype), ()

        def run(x, w):
            out, _ = jax.lax.scan(step, x, None, length=n)
            return out

        return run

    return make


def _variant_kernel(mode: str):
    """Kernel factory: 'kernel' = real fwd; 'patches' = no dot; 'copy' =
    slice copy only."""

    def kern(x_ref, w2_ref, y_ref, p_scr, *, w, t, ci, groups):
        base = 0 if groups == 1 else pl.program_id(1) * t
        if mode == "copy":
            y_ref[0, :, :] = x_ref[0, :, pl.ds(base + w + 1, t)][: y_ref.shape[1], :]
            return
        masks = cl._col_masks(w, t)
        cl._build_patches(x_ref, p_scr, base, masks, w, t, ci)
        if mode == "patches":
            y_ref[0, :, :] = p_scr[0: y_ref.shape[1], :]
            return
        y = jnp.dot(w2_ref[...], p_scr[...],
                    preferred_element_type=jnp.float32)
        y_ref[0, :, :] = y.astype(y_ref.dtype)

    return kern


def _conv_variant(mode, xf, w2, h, w):
    n, ci, hw = xf.shape
    co = w2.shape[0]
    t = cl._tile(hw)
    groups = hw // t
    xp = cl._pad_rows(xf, w)
    kernel = functools.partial(_variant_kernel(mode), w=w, t=t, ci=ci,
                               groups=groups)
    return pl.pallas_call(
        kernel,
        grid=(n, groups),
        in_specs=[
            pl.BlockSpec((1, ci, xp.shape[-1]), lambda i, g: (i, 0, 0)),
            pl.BlockSpec((co, w2.shape[-1]), lambda i, g: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, co, t), lambda i, g: (i, 0, g)),
        out_shape=jax.ShapeDtypeStruct((n, co, hw), xf.dtype),
        scratch_shapes=[pltpu.VMEM((9 * ci, t), xf.dtype)],
    )(xp, w2)


def main():
    rng = np.random.RandomState(0)
    results = {}
    for (ci, co, h, w) in [(16, 16, 32, 32), (32, 32, 16, 16)]:
        tag = f"c{ci}-{co}@{h}x{w}"
        x = jnp.asarray(rng.randn(BATCH, ci, h * w), jnp.bfloat16)
        k = jnp.asarray(rng.randn(3, 3, ci, co) * 0.1, jnp.bfloat16)
        w2 = cl._w2(k)
        row = {}

        row["xla"] = _time(_scan(
            lambda a, b, h=h, w=w: cl._xla_conv_nchw(a, b, h, w), x, k), x, k)
        row["kernel"] = _time(_scan(
            lambda a, b, h=h, w=w: cl.conv3x3_lanes(a, b, h, w), x, k), x, k)
        for mode in ("patches", "copy"):
            row[mode] = _time(_scan(
                lambda a, b, h=h, w=w, m=mode: _conv_variant(m, a, b, h, w),
                x, w2), x, w2)

        # wgrad probe: scan carries dy (same shape in/out when ci==co)
        if ci == co:
            def wg(a, b, h=h, w=w, x0=x):
                dw2 = cl._conv_wgrad(x0, a, h, w)
                # nonlinear fold-back so XLA cannot DCE the wgrad
                return a + jnp.tanh(jnp.sum(dw2)).astype(a.dtype) * 1e-4
            row["wgrad"] = _time(_scan(wg, x, w2), x, w2)

            # backward attribution: grad wrt x = fwd+dgrad; wrt w = fwd+wgrad
            for name, fn in (("xla", cl._xla_conv_nchw),
                             ("ker", cl.conv3x3_lanes)):
                def gx(a, b, h=h, w=w, fn=fn):
                    g = jax.grad(
                        lambda xx: jnp.sum((fn(xx, b, h, w) ** 2)
                                           .astype(jnp.float32)))(a)
                    return (g / (jnp.max(jnp.abs(g)) + 1e-3)).astype(a.dtype)
                row[f"{name}_f+dgrad"] = _time(_scan(gx, x, k), x, k)

                def gw(a, b, h=h, w=w, fn=fn, x0=x):
                    g = jax.grad(
                        lambda ww: jnp.sum((fn(x0, ww, h, w) ** 2)
                                           .astype(jnp.float32)))(a)
                    return (a + 1e-4 * g / (jnp.max(jnp.abs(g)) + 1e-3)
                            ).astype(a.dtype)
                row[f"{name}_f+wgrad"] = _time(_scan(gw, k, k), k, k)
        results[tag] = {k2: round(v, 2) for k2, v in row.items()}
        print(tag, json.dumps(results[tag]), flush=True)
    print(json.dumps({"iters": ITERS, "batch": BATCH,
                      "device": str(jax.devices()[0]), "us_per_iter": results}))


if __name__ == "__main__":
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
