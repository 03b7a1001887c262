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

Packed mode (fedpack, docs/mfu_experiments.md H8): ``--mode packed`` (or
PROBE_MODE=packed) sweeps the client-packing factor K at the flagship's
three channel widths and times the three lane-axis conv lowerings of
ops/packed_conv.py against each other — per-lane ``vmap`` (the packed
schedule's default), ``blockdiag`` (one im2col block-diagonal GEMM,
streams K x the useful FLOPs) and ``grouped`` (one feature_group_count=K
conv). Each row prints the block GEMM's (M, K_red, N), its 128x128 MXU
tile count, us/iteration and achieved USEFUL GFLOP/s (plus streamed for
blockdiag — the number the MXU actually executes), for forward and
forward+grad programs. Same whole-jitted-scan two-point protocol as the
default mode, so the fixed per-call cost cancels.

Auto mode (fedplan, docs/mfu_experiments.md H10): ``--mode auto`` is the
silicon adjudicator for the STATIC planner (obs/plan.py). It discovers
``--model``'s real conv stages, times each stage's fwd+grad program under
all three lowerings at K=``--lanes`` (same two-point protocol), and
compares the planner's per-stage pick against the measured-best lowering.
A non-dominated stage whose pick is more than ``--tolerance`` (fractional
time, default 0.10 / PROBE_TOL) slower than the measured best is a
DISAGREEMENT and the probe exits 1 — the H4 expansion credit the planner
bets on (explicit fgc=K convs get lane-full mappings) is exactly what
this mode confirms or refutes on the chip. Dominated stages (<1% of
model conv FLOPs) are probed and reported but never gate.
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


def _scan_opt(fn, tx, xs):
    """Adaptive-optimizer packed-program probe body: each scan iteration
    is one TRAIN step — conv loss grad wrt the stacked kernels, then a
    per-LANE optax update (``vmap(tx.update)``, the same stacked-state
    form parallel/packed.py's joint program uses) — so the timed program
    carries the optimizer's [K]-stacked state exactly like the packed
    round does. The kernel renormalizes each iteration so the carry stays
    bounded across the scan (a timing probe, not a training recipe)."""
    import optax

    def make(n):
        def step(carry, _):
            w, opt = carry
            g = jax.grad(lambda ww: jnp.sum(
                (fn(xs, ww) ** 2).astype(jnp.float32)))(w)
            upd, opt = jax.vmap(tx.update)(g, opt, w)
            w = optax.apply_updates(w, upd)
            w = (w / (jnp.max(jnp.abs(w)) + 1e-3)).astype(w.dtype)
            return (w, opt), ()

        def run(ws, opt0):
            (w, _), _ = jax.lax.scan(step, (ws, opt0), None, length=n)
            return w

        return run

    return make


def packed_main(optimizer: str = "none"):
    """The H8 sweep: K x {vmap, blockdiag, grouped} at C = 16/32/64.
    With ``--optimizer`` (sgd/adam/adamw/adagrad/yogi) each row also times
    the full TRAIN step — fwd + dgrad/wgrad + a per-lane stacked optax
    update — the packed-everywhere (H9) probe for the adaptive-optimizer
    packed programs, same two-point protocol."""
    from fedml_tpu.ops import packed_conv as pc

    tx = None
    if optimizer not in ("", "none", "off"):
        from fedml_tpu.parallel.local import make_optimizer

        tx = make_optimizer(optimizer, 0.01,
                            momentum=0.9 if optimizer == "sgd" else 0.0)

    rng = np.random.RandomState(0)
    results = {}
    variants = (("vmap", pc.conv_vmap), ("blockdiag", pc.conv_blockdiag),
                ("grouped", pc.conv_grouped))
    for (ci, co, h, w) in [(16, 16, 32, 32), (32, 32, 16, 16),
                           (64, 64, 8, 8)]:
        for K in (1, 2, 4, 8):
            tag = f"c{ci}@{h}x{w}-K{K}"
            xs = jnp.asarray(rng.randn(K, BATCH, h, w, ci), jnp.bfloat16)
            ws = jnp.asarray(rng.randn(K, 3, 3, ci, co) * 0.1, jnp.bfloat16)
            m, kr, n = BATCH * h * w, K * 9 * ci, K * co
            tiles = -(-kr // 128) * (-(-n // 128))
            useful = 2.0 * K * BATCH * h * w * 9 * ci * co
            row = {"MKN": [m, kr, n], "mxu_tiles": tiles,
                   "us": {}, "useful_gflops": {}}
            for name, fn in variants:
                us = _time(_scan(lambda a, b, f=fn: f(a, b), xs, ws), xs, ws)
                row["us"][name] = round(us, 2)
                row["useful_gflops"][name] = round(useful / us * 1e-3, 1)

                def train(a, b, f=fn):
                    g = jax.grad(lambda xx: jnp.sum(
                        (f(xx, b) ** 2).astype(jnp.float32)))(a)
                    return (g / (jnp.max(jnp.abs(g)) + 1e-3)).astype(a.dtype)

                us_t = _time(_scan(train, xs, ws), xs, ws)
                row["us"][f"{name}_f+dgrad"] = round(us_t, 2)
                if tx is not None:
                    opt0 = jax.vmap(tx.init)(ws)
                    us_o = _time(_scan_opt(fn, tx, xs), ws, opt0)
                    row["us"][f"{name}_train+{optimizer}"] = round(us_o, 2)
            # streamed rate: what the MXU executes for blockdiag (K x useful)
            row["streamed_gflops_blockdiag"] = round(
                useful * K / row["us"]["blockdiag"] * 1e-3, 1)
            results[tag] = row
            print(tag, json.dumps(row), flush=True)
    print(json.dumps({"mode": "packed", "iters": ITERS, "batch": BATCH,
                      "optimizer": optimizer,
                      "device": str(jax.devices()[0]), "rows": results}))


def auto_main(model: str, lanes: int, tolerance: float) -> int:
    """The H10 probe: planner pick vs measured best, per real conv stage.

    Times the SAME program shape the planner scored — fwd + grad wrt
    (activations, kernels) of one packed conv stage — so the comparison
    is pick-vs-best on the planner's own ground. Returns a process exit
    code: 0 agreement (within tolerance on every gating stage), 1
    disagreement, 2 unplannable model."""
    import jax.numpy as jnp  # noqa: F811 (module-level alias is fine)

    from fedml_tpu.models import create_model
    from fedml_tpu.obs import plan as fedplan
    from fedml_tpu.ops import packed_conv as pc

    bundle = create_model(model, 10, dtype=jnp.bfloat16,
                          input_shape=(32, 32, 3))
    try:
        plan = fedplan.plan_lowering(bundle, lanes)
    except ValueError as e:
        print(f"fedplan cannot plan {model}: {e}", file=sys.stderr)
        return 2

    rng = np.random.RandomState(0)
    impls = {"blockdiag": pc.conv_blockdiag, "grouped": pc.conv_grouped,
             "off": pc.conv_vmap}
    rows, disagreements = {}, []
    for st in plan.stages:
        tag = (f"{st.kh}x{st.kw}-{st.ci}-{st.co}-s{st.strides}"
               f"@{st.h}x{st.w}")
        xs = jnp.asarray(
            rng.randn(lanes, BATCH, st.h, st.w, st.ci), jnp.bfloat16)
        ws = jnp.asarray(
            rng.randn(lanes, st.kh, st.kw, st.ci, st.co) * 0.1,
            jnp.bfloat16)
        us = {}
        for name, fn in impls.items():
            def train(a, b, f=fn, s=st.strides, p=st.padding):
                gx, gw = jax.grad(
                    lambda xx, ww: jnp.sum(jnp.square(
                        f(xx, ww, s, p).astype(jnp.float32))),
                    argnums=(0, 1))(a, b)
                # fold the weight grad back nonlinearly so XLA cannot
                # DCE the wgrad dot out of the timed scan
                g = gx + (jnp.tanh(jnp.sum(gw)) * 1e-4).astype(a.dtype)
                return (g / (jnp.max(jnp.abs(g)) + 1e-3)).astype(a.dtype)

            us[name] = round(_time(_scan(train, xs, ws), xs, ws), 2)
        best = min(us, key=us.get)
        slower = (us[st.impl] - us[best]) / us[best] if us[best] > 0 else 0.0
        gates = not st.dominated
        agree = st.impl == best or slower <= tolerance
        row = {"pick": st.impl, "measured_best": best, "us": us,
               "pick_slower_frac": round(slower, 4),
               "flops_frac": st.flops_frac, "dominated": st.dominated,
               "count": st.count, "gates": gates, "agree": agree}
        rows[tag] = row
        print(tag, json.dumps(row), flush=True)
        if gates and not agree:
            disagreements.append(tag)

    out = {"mode": "auto", "model": model, "lanes": lanes,
           "tolerance": tolerance, "iters": ITERS, "batch": BATCH,
           "device": str(jax.devices()[0]),
           "plan": plan.summary_str(),
           "predicted_ceiling": plan.predicted_ceiling,
           "disagreements": disagreements, "rows": rows}
    print(json.dumps(out))
    if disagreements:
        print(f"fedplan disagreement on {len(disagreements)} stage(s): "
              f"{disagreements} — the static pick leaves "
              f">{tolerance:.0%} on the table", file=sys.stderr)
        return 1
    return 0


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
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mode", choices=("lanes", "packed", "auto"),
                    default=os.environ.get("PROBE_MODE", "lanes"))
    ap.add_argument("--optimizer",
                    choices=("none", "sgd", "adam", "adamw", "adagrad",
                             "yogi"),
                    default=os.environ.get("PROBE_OPT", "none"),
                    help="packed mode: also time the full train step with "
                         "a per-lane stacked optax update (packed-"
                         "everywhere / H9 probe)")
    ap.add_argument("--model",
                    default=os.environ.get("BENCH_MODEL", "resnet56"),
                    help="auto mode: whose conv stages to adjudicate")
    ap.add_argument("--lanes", type=int,
                    default=int(os.environ.get("PROBE_LANES", "4")),
                    help="auto mode: pack-lane count K")
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get("PROBE_TOL", "0.10")),
                    help="auto mode: fractional pick-vs-best slowdown "
                         "above which a non-dominated stage fails")
    args = ap.parse_args()
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.mode == "auto":
        sys.exit(auto_main(args.model, args.lanes, args.tolerance))
    elif args.mode == "packed":
        packed_main(args.optimizer)
    else:
        main()
