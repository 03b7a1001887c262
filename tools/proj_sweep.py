#!/usr/bin/env python3
"""proj_sweep: the state-space mixer's joint projection ``[z | xBC | dt] =
W_in u`` alone on the chip, at the state-space LM cell's shape (``u [1,
4096, 2048]`` bfloat16, ``W_in [2048, 8512]`` float32 and cast inside, as
``models/transformer.Mamba2Mixer`` holds it), in each form named.

    python tools/proj_sweep.py [form ...]

A form is a list of column edges of the ONE kernel: each pair of
neighbouring edges is one product ``u @ W[:, a:b]`` (operands bfloat16,
float32 accumulation, result bfloat16, as ``Linear`` states), and the
outputs are cut where a consumer's columns end (``z`` to 4,096, ``xBC`` to
8,448, ``dt`` to 8,512) and nowhere else, each piece leaving the jitted
function by itself as the mixer's consumers take them:

    incumbent  0-8512                one product, cut at 4,096 and 8,448
    A          0-8192-8512           z | x from the first, B | C | dt the second
    B          0-4096-8192-8512      z, x, and B | C | dt
    C          0-4096-8448-8512      one product a consumer: z, xBC, dt
    D          0-8512 padded to 8704 one product over the cast kernel
                                     zero-padded at use to 68 lane tiles
    Bp         as B, the 320-wide remainder zero-padded at use to 384
    B4         0-4096-8192-8448-8512 z, x, B | C, dt: no output is cut
    only:N     ``u @ W[:, :N]`` alone (no whole projection: a width's own rate)

A suffix ``+vjp`` gives the form a backward of its own (``jax.custom_vjp``):
the kernel's gradient as ONE concatenation of the products' gradients
(each rounded to bfloat16, as JAX's own backward of ``Linear`` rounds it) and
the input's cotangent summed over the products in float32 and rounded once,
where JAX's own backward pads and adds the first and adds the second in
bfloat16.

For each form: wall-clock ms of the forward, of the backward alone (the two
products ``du = dY W^T`` and ``dW = u^T dY`` from given cotangents of the
pieces) and of both in one call; ``four_pass_ms`` = twice the forward plus
the backward, which is what a layer-step of the cell pays under
``nn.remat``; the share of the bfloat16 peak those four passes reach; the
largest device operations of a traced call of each (what XLA made of the
form); and the error of the outputs, ``du`` and ``dW`` against float32
``highest``. Fails at once without a TPU. Writes
``chiprun_out/proj_sweep.json``; ``PERF.md`` (PR 38) has the readings that
chose the mixer's form: B4, which the row ``mixer`` times through the
mixer's own function (``models/transformer.column_products``). Alone, the
forms differ by 13% at most; what the cell paid was the compiler computing
the incumbent's ONE product again for each consumer, which only the cell's
compiled program shows (``tools/round_fit.py --hlo``).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

T, DIM, INNER, STATE, HEADS = 4096, 2048, 4096, 128, 64
WIDTH = 2 * INNER + 2 * STATE + HEADS                    # 8512
CONSUMERS = (0, INNER, 2 * INNER + 2 * STATE, WIDTH)     # z | xBC | dt
PEAK_BF16 = 197e12
FORMS = {
    "incumbent": ((0, WIDTH), None),
    "A": ((0, 2 * INNER, WIDTH), None),
    "B": ((0, INNER, 2 * INNER, WIDTH), None),
    "C": ((0, INNER, 2 * INNER + 2 * STATE, WIDTH), None),
    "D": ((0, WIDTH), {0: 8704}),
    "Bp": ((0, INNER, 2 * INNER, WIDTH), {2: 384}),
    "B4": ((0, INNER, 2 * INNER, 2 * INNER + 2 * STATE, WIDTH), None),
}


def _ms(fn, args, iters: int = 20) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _device_ops(fn, args, calls: int = 3, top: int = 6) -> list:
    """The ``top`` device operations of a traced call, ``[name, ms a call]``."""
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
                ops[ev.name] = (ops.get(ev.name, 0.0)
                                + ev.duration_ns / 1e6 / calls)
    return [[k, round(v, 4)] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]]


def pieces_of(edges) -> tuple:
    """The columns ``(lo, hi)`` of each piece that leaves a form: cut at the
    products' edges and where a consumer's columns end."""
    cuts = sorted({*edges, *(c for c in CONSUMERS
                             if edges[0] <= c <= edges[-1])})
    return tuple(zip(cuts[:-1], cuts[1:]))


def _products(u, w, edges, pads):
    import jax.numpy as jnp

    outs = []
    for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
        k = w[:, a:b].astype(u.dtype)
        if pads and i in pads:
            k = jnp.pad(k, ((0, 0), (0, pads[i] - (b - a))))
        outs.append(jnp.dot(u, k, preferred_element_type=jnp.float32
                            ).astype(u.dtype))
    return outs


def _cut(outs, edges):
    """The products' outputs cut into the pieces of :func:`pieces_of`."""
    got = []
    for (a, b), y in zip(zip(edges[:-1], edges[1:]), outs):
        got += [y[..., lo - a:hi - a] for lo, hi in pieces_of(edges)
                if a <= lo and hi <= b]
    return tuple(got)


def make_form(edges, pads=None, own_vjp=False):
    """``(u, w) -> pieces`` for a form."""
    import jax
    import jax.numpy as jnp

    def plain(u, w):
        return _cut(_products(u, w, edges, pads), edges)

    if not own_vjp:
        return plain

    @jax.custom_vjp
    def form(u, w):
        return plain(u, w)

    def fwd(u, w):
        return plain(u, w), (u, w)

    def bwd(res, cts):
        u, w = res
        du, dws, cts = None, [], list(cts)
        for a, b in zip(edges[:-1], edges[1:]):
            mine = [cts.pop(0) for lo, hi in pieces_of(edges)
                    if a <= lo and hi <= b]
            dy = mine[0] if len(mine) == 1 else jnp.concatenate(mine, axis=-1)
            part = jnp.einsum("btn,dn->btd", dy, w[:, a:b].astype(u.dtype),
                              preferred_element_type=jnp.float32)
            du = part if du is None else du + part
            dws.append(jnp.einsum("btd,btn->dn", u, dy,
                                  preferred_element_type=jnp.float32
                                  ).astype(u.dtype))
        return (du.astype(u.dtype),
                jnp.concatenate(dws, axis=1).astype(w.dtype))

    form.defvjp(fwd, bwd)
    return form


def resolve(name):
    """-> (edges, pads, own_vjp, whole) of a named form or ``only:N``."""
    base, _, suffix = name.partition("+")
    if base.startswith("only:"):
        return (0, int(base[5:])), None, suffix == "vjp", False
    edges, pads = FORMS[base]
    return edges, pads, suffix == "vjp", True


def measure(name, u, w, ct, exact):
    import jax
    import jax.numpy as jnp

    if name == "mixer":
        from fedml_tpu.models import transformer

        edges, whole = FORMS["B4"][0], True
        form = functools.partial(
            transformer.column_products, dtype=jnp.bfloat16,
            widths=tuple(b - a for a, b in zip(edges[:-1], edges[1:])))
    else:
        edges, pads, own_vjp, whole = resolve(name)
        form = make_form(edges, pads, own_vjp)
    cts = tuple(ct[..., lo:hi] for lo, hi in pieces_of(edges))

    def back(u, w, cts):
        return jax.vjp(form, u, w)[1](cts)

    def both(u, w, cts):
        out, pull = jax.vjp(form, u, w)
        return out, pull(cts)

    fwd, bwd, fb = jax.jit(form), jax.jit(back), jax.jit(both)
    cols = edges[-1] - edges[0]
    flop = 2.0 * T * DIM * cols
    row = {"form": name, "edges": list(edges),
           "products": [b - a for a, b in zip(edges[:-1], edges[1:])],
           "fwd_ms": _ms(fwd, (u, w)), "bwd_ms": _ms(bwd, (u, w, cts)),
           "fwd_bwd_ms": _ms(fb, (u, w, cts))}
    row["four_pass_ms"] = 2 * row["fwd_ms"] + row["bwd_ms"]
    row["fwd_peak_pct"] = 100 * flop / (row["fwd_ms"] * 1e-3) / PEAK_BF16
    row["four_pass_peak_pct"] = (100 * 4 * flop / (row["four_pass_ms"] * 1e-3)
                                 / PEAK_BF16)
    row["fwd_ops"] = _device_ops(fwd, (u, w))
    row["bwd_ops"] = _device_ops(bwd, (u, w, cts))
    out = jnp.concatenate(fwd(u, w), axis=-1).astype(jnp.float32)
    du, dw = bwd(u, w, cts)
    y0, du0, dw0 = exact
    rel = lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
                             / jnp.linalg.norm(b.ravel()))
    row["err"] = {"out": rel(out, y0[..., :cols]),
                  "dw": rel(dw[:, :cols], dw0[:, :cols])}
    if whole:
        row["err"]["du"] = rel(du, du0)
    return row


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    names = list(argv if argv is not None else sys.argv[1:]) or [
        "incumbent", "A", "B", "C", "D"]
    if jax.devices()[0].platform != "tpu":
        print("proj_sweep: needs a TPU", file=sys.stderr)
        return 3
    ks = jax.random.split(jax.random.key(38), 3)
    u = jax.random.normal(ks[0], (1, T, DIM)).astype(jnp.bfloat16)
    w = 0.02 * jax.random.normal(ks[1], (DIM, WIDTH), jnp.float32)
    ct = jax.random.normal(ks[2], (1, T, WIDTH)).astype(jnp.bfloat16)

    @jax.jit
    def reference(u, w, ct):
        with jax.default_matmul_precision("highest"):
            f = lambda u, w: u @ w
            y, pull = jax.vjp(f, u.astype(jnp.float32), w)
            return (y,) + pull(ct.astype(jnp.float32))

    exact = reference(u, w, ct)
    rows = []
    for name in names:
        rows.append(measure(name, u, w, ct, exact))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "proj_sweep.json"), "w") as f:
        json.dump({"shape": [1, T, DIM, WIDTH], "peak_bf16": PEAK_BF16,
                   "device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
