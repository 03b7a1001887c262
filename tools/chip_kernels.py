#!/usr/bin/env python3
"""chip_kernels: compile every Pallas kernel on the chip against its XLA
reference.

CPU tests run the kernels in interpret mode (or not at all: ``impl="auto"``
takes the XLA path off-TPU), so only a chip run shows whether Mosaic
accepts a shape. One row per kernel and shape: ``compiled`` with the error
against the reference and the stated tolerance, or ``FAILED`` with the
compiler's message. Rows:

- ``ops/attention.py`` forward and backward at the registered models'
  shapes (T=80 and T=20, head_dim 32) and at long context (T=2048 and 8192,
  head_dim 64), under ``impl="auto"``; then the registered ``transformer``
  and ``transformer_nwp`` through one real federated round each, which puts
  the kernel under ``vmap`` inside the round's ``lax.scan``;
- ``ops/xent.py`` at V=10,004 and up a ladder of vocabularies (its block is
  the whole padded row, so VMEM grows with V);
- ``ops/kda.py``'s kernel pair, forward and backward, at heads of 128 and
  chunks of 32 and 64 against the ``jax.numpy`` scan, float32 operands;
- ``ops/grouped_matmul.py``'s three kernels (bf16 rows, float32 matrices,
  an empty group and rows of no group) against ``lax.ragged_dot`` on the
  matrices cast outside, at a narrow and a wide expert's shape;
- ``ops/batchnorm.py`` and ``ops/conv_lanes.py`` once each — they sit
  behind ``bn_impl``/``conv_impl`` (default ``xla``) and are queued for
  deletion, so a failure there is reported but does not fail the run — and
  ``bn_impl="pallas"`` inside the cross-silo ``shard_map`` round (the one
  caller that needs ``ops.common.sds``'s ``vma=``).

References are computed at ``jax.default_matmul_precision("highest")``; the
kernels run as shipped. The XLA path's own default-precision error against
the same reference is printed beside each row as the yardstick.

Fails at once without a TPU. Exit 1 if a kernel reachable under
``impl="auto"`` fails to compile or misses its tolerance.

    python tools/chip_kernels.py        # writes chiprun_out/chip_kernels.json
"""

from __future__ import annotations

import json
import os
import sys
import traceback
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: max |kernel - reference| / max |reference|. bf16-pass matmuls (the TPU
#: default for f32 operands) carry ~2^-8 relative error per product.
TOL = 2e-2


def _rel_err(got, ref) -> float:
    import jax
    import numpy as np

    errs = []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs.append(float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)))
    return max(errs)


def _attention_case(t: int, d: int, b: int, h: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.attention import attention

    rng = np.random.default_rng(t + d)
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
               for _ in range(3))

    def fwd_bwd(impl):
        def loss(q, k, v):
            o = attention(q, k, v, causal=True, impl=impl)
            return jnp.sum(jnp.sin(o)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q, k, v)
        return o, grads

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda: fwd_bwd("xla"))()
    xla = jax.jit(lambda: fwd_bwd("xla"))()
    got = jax.jit(lambda: fwd_bwd("auto"))()
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1]),
            "xla_default_fwd_err": _rel_err(xla[0], ref[0]),
            "xla_default_bwd_err": _rel_err(xla[1], ref[1])}


def _federated_transformer(model: str, dataset: str) -> dict:
    """One real sim round of a registered transformer: the kernel under
    vmap inside the local-training scan, forward and backward."""
    import math

    from fedml_tpu.experiments import run

    result = run.main([
        "--algorithm", "fedavg", "--model", model, "--dataset", dataset,
        "--client_num_in_total", "8", "--client_num_per_round", "4",
        "--batch_size", "4", "--epochs", "1", "--lr", "0.05",
        "--comm_round", "2", "--frequency_of_the_test", "1"])
    losses = result["Test/Loss"]
    if not losses or not all(math.isfinite(v) for v in losses):
        raise RuntimeError(f"non-finite eval loss {losses}")
    return {"test_loss": losses,
            "placement": result["placement"]["variables_on"]}


def _xent_case(v: int, n: int = 256) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.xent import masked_cross_entropy

    rng = np.random.default_rng(v)
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3.0, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(n,)), jnp.int32)

    def run(impl):
        def loss(lg):
            per = masked_cross_entropy(lg, labels, impl=impl)
            return jnp.sum(per), per

        (_, per), g = jax.value_and_grad(loss, has_aux=True)(logits)
        return per, g

    ref = jax.jit(lambda: run("xla"))()
    got = jax.jit(lambda: run("auto"))()
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1])}


def _kda_case(t: int, h: int, chunk: int) -> dict:
    """The delta rule's kernel pair (forward with the intra-chunk part
    inside, backward over the chunks in reverse) against the ``jax.numpy``
    scan, float32 operands on both paths, heads of 128."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.kda import kda_chunked

    ks = jax.random.split(jax.random.key(t + chunk), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    shape = (1, h, t, 128)
    x = (unit(jax.random.normal(ks[0], shape)) * 128 ** -0.5,
         unit(jax.random.normal(ks[1], shape)), jax.random.normal(ks[2], shape),
         -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], shape)),
         jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])))

    def run(impl):
        def loss(*a):
            o = kda_chunked(*a, chunk=chunk, dtype=jnp.float32, impl=impl)
            return jnp.sum(jnp.sin(o)), o

        (_, o), grads = jax.value_and_grad(loss, (0, 1, 2, 3, 4),
                                           has_aux=True)(*x)
        return o, grads

    ref = jax.jit(lambda: run("xla"))()
    got = jax.jit(lambda: run("auto"))()
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1])}


def _grouped_case(m: int, k: int, n: int, groups: int) -> dict:
    """The grouped matmul's kernels, forward and both gradients, against the
    compiler's grouped kernels on the matrices cast to bf16 outside."""
    import jax
    import jax.numpy as jnp

    import fedml_tpu.ops.grouped_matmul as gm

    ks = jax.random.split(jax.random.key(m + n), 4)
    share = jax.random.dirichlet(ks[0], jnp.ones((groups,))).at[1].set(0)
    sizes = jnp.floor(share * 0.8 * m).astype(jnp.int32)
    live = (jnp.arange(m) < jnp.sum(sizes))[:, None]
    x = jnp.where(live, jax.random.normal(ks[1], (m, k), jnp.bfloat16), 0)
    c = jnp.where(live, jax.random.normal(ks[2], (m, n), jnp.float32), 0)
    w = k ** -0.5 * jax.random.normal(ks[3], (groups, k, n), jnp.float32)

    def run(fn):
        return jax.value_and_grad(lambda x, w: jnp.sum(
            fn(x, w, sizes).astype(jnp.float32) * c), (0, 1))(x, w)

    assert gm._tiles(m, k, n, groups, 2, 4)
    ref = jax.jit(lambda: run(gm._plain))()
    got = jax.jit(lambda: run(gm.grouped_matmul))()
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1])}


def _batchnorm_case() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.batchnorm import _xla_bn_relu, fused_bn_relu

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32, 32, 16)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(16,)), jnp.float32)

    def loss_k(x, g, b):
        return jnp.sum(jnp.sin(fused_bn_relu(x, g, b, 1e-5, True)[0]
                               .astype(jnp.float32)))

    def loss_r(x, g, b):
        y = _xla_bn_relu(x.reshape(-1, 16), g, b, 1e-5, True)[0]
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    got = jax.jit(jax.value_and_grad(loss_k, (0, 1, 2)))(x, g, b)
    ref = jax.jit(jax.value_and_grad(loss_r, (0, 1, 2)))(x, g, b)
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1])}


def _conv_lanes_case() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.conv_lanes import _xla_conv_nchw, conv3x3_lanes

    rng = np.random.default_rng(0)
    h = w = 32
    x = jnp.asarray(rng.normal(size=(64, 16, h * w)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(3, 3, 16, 16)) * 0.1, jnp.bfloat16)

    def loss(fn):
        return lambda x, k: jnp.sum(jnp.sin(fn(x, k, h, w)
                                            .astype(jnp.float32)))

    got = jax.jit(jax.value_and_grad(loss(conv3x3_lanes), (0, 1)))(x, k)
    ref = jax.jit(jax.value_and_grad(loss(_xla_conv_nchw), (0, 1)))(x, k)
    return {"fwd_err": _rel_err(got[0], ref[0]),
            "bwd_err": _rel_err(got[1], ref[1])}


def _bn_in_shard_map() -> dict:
    """One packed-off cross-silo round of a pallas-BN ResNet-20 over every
    device: the kernel's outputs must declare their varying mesh axes."""
    import math

    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model

    n = 2 * len(jax.devices())
    ds = make_synthetic_classification(
        "bn-shard-map", (32, 32, 3), 10, n, records_per_client=64,
        partition_method="homo", batch_size=32, seed=0)
    cfg = FedConfig(model="resnet20", client_num_in_total=n,
                    client_num_per_round=n, comm_round=1, batch_size=32,
                    epochs=1, lr=0.1, dtype="bfloat16", seed=0)
    bundle = create_model("resnet20", 10, dtype=jnp.bfloat16,
                          bn_impl="pallas")
    loss = float(CrossSiloFedAvgAPI(ds, cfg, bundle).run_round(0))
    if not math.isfinite(loss):
        raise RuntimeError(f"non-finite train loss {loss}")
    return {"train_loss": loss, "devices": len(jax.devices())}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_kernels: no TPU (platform {dev.platform!r}); these "
              f"checks only mean something on the chip", file=sys.stderr)
        return 1

    from fedml_tpu.ops import common
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if common.interpret():
        raise RuntimeError("ops.common.interpret() is true on a TPU")

    # (row name, gates the exit code, thunk)
    checks = [
        (f"attention T={t} D={d} B={b} H={h}", True,
         partial(_attention_case, t, d, b, h))
        for t, d, b, h in ((80, 32, 2, 8), (20, 32, 2, 8),
                           (2048, 64, 1, 4), (8192, 64, 1, 2))
    ] + [
        ("transformer (T=80) federated round", True,
         partial(_federated_transformer, "transformer", "shakespeare")),
        ("transformer_nwp (T=20) federated round", True,
         partial(_federated_transformer, "transformer_nwp",
                 "stackoverflow_nwp")),
    ] + [
        (f"xent V={v}", v == 10_004, partial(_xent_case, v))
        for v in (10_004, 32_768, 50_304, 131_072)
    ] + [
        (f"kda T={t} H={h} chunk={c}", True, partial(_kda_case, t, h, c))
        for t, h, c in ((512, 4, 32), (1024, 2, 64))
    ] + [
        (f"grouped matmul M={m} K={k} N={n} G={g}", True,
         partial(_grouped_case, m, k, n, g))
        for m, k, n, g in ((6144, 2048, 768, 16), (4096, 2048, 2048, 8))
    ] + [
        ("batchnorm (bn_impl=pallas) 64x32x32x16 bf16", False,
         _batchnorm_case),
        ("conv_lanes (conv_impl=lanes) 64x16x32x32 bf16", False,
         _conv_lanes_case),
        ("bn_impl=pallas inside the cross-silo shard_map round", False,
         _bn_in_shard_map),
    ]

    rows, failed = [], False
    for name, gating, thunk in checks:
        row = {"kernel": name, "gating": gating}
        try:
            row.update(status="compiled", **thunk())
            errs = [v for k, v in row.items()
                    if k in ("fwd_err", "bwd_err")]
            if errs and max(errs) > TOL:
                row["status"] = f"compiled, OVER tolerance {TOL:g}"
        except Exception as e:  # the compiler's message IS the finding
            traceback.print_exc()
            msg = str(e).strip()
            row.update(status="FAILED", error=type(e).__name__,
                       message=msg[:2000])
        if gating and row["status"] != "compiled":
            failed = True
        rows.append(row)
        print(json.dumps(row), flush=True)

    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_kernels.json"), "w") as f:
        json.dump({"device_kind": dev.device_kind,
                   "device_count": len(jax.devices()),
                   "jax": jax.__version__, "tolerance": TOL,
                   "rows": rows}, f, indent=1)
    print(f"chip_kernels: {sum(r['status'] == 'compiled' for r in rows)}/"
          f"{len(rows)} rows compiled inside tolerance; "
          f"{'FAILED' if failed else 'ok'}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
