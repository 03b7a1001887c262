#!/usr/bin/env python3
"""attn_sweep: ``ops/attention.py``'s kernels alone on the chip, at the LM
cell's shapes, over DMA tiles and compute sub-tiles.

The benchmark reads the kernels through a whole round (``attn_ms``, by
scope, with XLA's passes around them). This tool times each kernel by
itself: forward (``_pallas_block_partial``), the backward as the shape takes
it (``bwd``: ``_pallas_flash_bwd``, ONE call from which dq, dk and dv all
leave while a key-value head's dq fits VMEM; ``bwd_form`` says which form
``_bwd_vmem`` gave the shape) and, beside it, the two-kernel form that a
longer sequence falls back to (``dkv`` and ``dq``: ``_flash_bwd`` without a
VMEM limit, each jitted alone so that XLA drops the other), at ``[2, 32,
4096]`` with 192-wide keys and 128-wide values in bf16, causal. One row per
``tile:sub_q:sub_k``: wall-clock ms a call over ``--iters`` calls, the
device time of the heaviest operation in a traced call (the kernel without
XLA's passes around it), the share of the score area the row executes, its
MXU share on that executed work, the largest difference from the first
row's results, and ``attention`` forward and gradients against the XLA path
at float32 ``highest`` on two heads. The sub-tile is the module's constant,
set here for a row; nothing else selects it.

    python tools/attn_sweep.py 1024:1024:1024 1024:256:256 1024:128:128

``--heads`` / ``--kv-heads`` / ``--head-dim`` / ``--value-dim`` / ``--window``
give another cell's shapes (a window layer of 64 query heads over 8
key-value heads of 128 under a window of 512: ``--heads 64 --kv-heads 8
--head-dim 128 --window 512``). ``--repeat-kv`` times the other way to serve
grouped heads: ``k`` and ``v`` repeated ``heads / kv_heads`` times BEFORE
kernels that then see equal heads, and their gradients summed over the group
after, both inside the timed call. ``--pad-to 128`` times a NARROW head the
other way (a state-space hybrid's attention layer has heads of 64, half a
lane tile): ``q``, ``k``, ``v`` and the output's cotangent zero-padded to that
width before the kernels (scores unchanged; the output's extra channels are
zeros that a slice drops), against the same command without it.

Fails at once without a TPU. Writes ``chiprun_out/attn_sweep.json``.
"""

from __future__ import annotations

import argparse
import functools
import glob
import importlib
import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

#: matmul passes over the executed score area, (at the key width, at the
#: value width): forward s | pv; the one backward call s, dk, dq | dp, dv;
#: of the two-kernel form dk/dv s, dk | dp, dv and dq s, dq | dp
PASSES = {"fwd": (1, 1), "bwd": (3, 2), "dkv": (2, 2), "dq": (2, 1)}


@functools.cache
def _peak_flops() -> float:
    """The chip's published bf16 peak (``benchmarks/peaks.json``; a device
    that is not in the table is an error)."""
    import jax

    with open(os.path.join(_ROOT, "benchmarks", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]["flops_per_s"]["bfloat16"]


def _device_ms(fn, args, calls: int = 3) -> dict:
    """Device time by operation name over ``calls`` traced calls, ms a call."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    ops = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns / 1e6 / calls
    return ops


def measure(shape, d, dv, tile, sub_q, sub_k, iters, interpret=False,
            trace=True, kv_heads=None, window=None, repeat_kv=False,
            pad_to=None):
    """-> (row, results): one configuration's kernels."""
    import jax
    import jax.numpy as jnp

    att = importlib.import_module("fedml_tpu.ops.attention")
    att._SUB_Q, att._SUB_K = sub_q, sub_k
    b, h, t = shape
    g = kv_heads or h
    keys = jax.random.split(jax.random.key(27), 4)
    q, k = (jax.random.normal(keys[i], (b, n, t, d), jnp.bfloat16)
            for i, n in ((0, h), (1, g)))
    v, do = (jax.random.normal(keys[i], (b, n, t, dv), jnp.bfloat16)
             for i, n in ((2, g), (3, h)))
    scale = d ** -0.5
    if pad_to:
        q, k, v, do = (jnp.pad(a, [(0, 0)] * 3 + [(0, pad_to - a.shape[-1])])
                       for a in (q, k, v, do))
        dv = pad_to
    q, k = att._pad_qk(q, k)

    def spread(a):      # a key-value head for each of its query heads
        return jnp.repeat(a, h // g, axis=1) if repeat_kv else a

    def gathered(a):    # and their gradients' sum
        return (a.reshape(b, g, h // g, *a.shape[2:]).sum(2).astype(a.dtype)
                if repeat_kv else a)

    def partial(q, k, v):
        return att._pallas_block_partial(q, spread(k), spread(v), 0, 0, True,
                                         scale, tile, tile, interpret, window)

    def fwd(q, k, v):
        o, m, l = partial(q, k, v)
        return (o / l[..., None]).astype(q.dtype), m + jnp.log(l)

    out, lse = jax.jit(fwd)(q, k, v)

    def bwd(q, k, v, out, lse, do, two_kernels=False):
        k, v = spread(k), spread(v)
        if two_kernels:
            dq, dk, dv_ = att._flash_bwd(
                *att._bwd_operands(q, k, v, out, lse, do),
                tiling=att._tiling(True, t, t, tile, tile, window),
                sm_scale=scale, interpret=interpret, vmem_limit=None)
            dq, dk, dv_ = (x.reshape(like.shape)
                           for x, like in ((dq, q), (dk, k), (dv_, v)))
        else:
            dq, dk, dv_ = att._pallas_flash_bwd(
                q, k, v, out, lse, do, True, scale, tile, tile, interpret,
                window)
        return dq, gathered(dk), gathered(dv_)

    res = (q, k, v, out, lse, do)
    fns = {
        "fwd": (jax.jit(partial), (q, k, v)),
        "bwd": (jax.jit(bwd), res),
        "dkv": (jax.jit(lambda *a: bwd(*a, two_kernels=True)[1:]), res),
        "dq": (jax.jit(lambda *a: bwd(*a, two_kernels=True)[0]), res),
    }
    vmem = att._bwd_vmem(t, q.shape[-1], dv, 1 if repeat_kv else h // g)
    share = att.executed_score_share(t, t, tile, tile, sub_q, sub_k,
                                     window=window)
    row = {"tile": tile, "sub_q": sub_q, "sub_k": sub_k, "heads": h,
           "kv_heads": g, "window": window, "repeat_kv": repeat_kv,
           "head_dim": d, "padded_to": pad_to,
           "bwd_form": ("two kernels" if vmem is None else
                        f"one call, {vmem >> 20} MiB of VMEM"),
           "executed_score_share": share}
    results = {}
    for name, (fn, args) in fns.items():
        results[name] = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        jax.block_until_ready(r)
        row[f"{name}_ms"] = (time.perf_counter() - t0) * 1e3 / iters
        if trace:
            ops = _device_ms(fn, args)
            top = max(ops, key=ops.get)
            row[f"{name}_kernel_ms"] = ops[top]
            row[f"{name}_other_ms"] = sum(ops.values()) - ops[top]
            # executed work at the PADDED key width, which the MXU passes
            wide, narrow = PASSES[name]
            flops = 2 * b * h * t * t * share * (wide * q.shape[-1] + narrow * dv)
            row[f"{name}_mxu_pct"] = 100 * flops / _peak_flops() / (ops[top] / 1e3)
    one = h // g                # the query heads of the first key-value head
    row["err_to_xla"] = _err_to_xla(
        att, (q[:1, :2 * one, :, :d], k[:1, :2, :, :d], v[:1, :2],
              do[:1, :2 * one]), tile, window)
    return row, results


def _err_to_xla(att, qkvc, tile, window=None) -> dict:
    """Forward and the three gradients of ``attention`` on the kernels
    against the XLA path in float32 at ``highest``."""
    import jax
    import jax.numpy as jnp

    def run(impl, args):
        q, k, v, c = args

        def loss(q, k, v):
            o = att.attention(q, k, v, causal=True, impl=impl, block_q=tile,
                              block_k=tile, window=window)
            return jnp.sum(o.astype(jnp.float32) * c.astype(jnp.float32)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q, k, v)
        return o, grads

    got = jax.jit(lambda *a: run("pallas", a))(*qkvc)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: run("xla", a))(
            *(x.astype(jnp.float32) for x in qkvc))
    return {"fwd": _gap(got[0], want[0]), "bwd": _gap(got[1], want[1])}


def _gap(a, b) -> float:
    import jax
    import numpy as np

    gaps = []
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        gaps.append(float(np.max(np.abs(x - y)) / np.max(np.abs(y))))
    return max(gaps)


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("rows", nargs="*", default=[
        "1024:1024:1024", "1024:128:128", "1024:256:256", "1024:512:512"],
        help="tile:sub_q:sub_k; the first row is what the others' results "
             "are compared with")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, help="default: --heads")
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--head-dim", type=int, default=192,
                    help="queries and keys")
    ap.add_argument("--value-dim", type=int, default=128)
    ap.add_argument("--window", type=int)
    ap.add_argument("--repeat-kv", action="store_true")
    ap.add_argument("--pad-to", type=int,
                    help="zero-pad q, k, v and the cotangent to this width")
    ap.add_argument("--out", default="chiprun_out/attn_sweep.json")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("attn_sweep: no TPU", file=sys.stderr)
        return 1
    rows, first = [], None
    for spec in args.rows:
        tile, sub_q, sub_k = (int(x) for x in spec.split(":"))
        try:
            row, results = measure(
                (args.batch, args.heads, args.seq_len), args.head_dim,
                args.value_dim, tile, sub_q, sub_k, args.iters,
                kv_heads=args.kv_heads, window=args.window,
                repeat_kv=args.repeat_kv, pad_to=args.pad_to)
        except Exception as e:  # noqa: BLE001  the compiler refused the row
            print(json.dumps({"row": spec, "failed": str(e)[-600:]}), flush=True)
            continue
        first = first or results
        row["gap_to_first_row"] = {n: _gap(results[n], first[n]) for n in results}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
