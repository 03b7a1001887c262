"""Plain reference for tag prediction by logistic regression: the second
configuration the tests drive the harness with (another model, generator
and round path than the ResNet's), and the reference of the host-path cell
``so_lr_xdev_c50`` that PR 23 measured and did not admit (PERF.md, Open
questions 0b): a later PR that steadies that cell copies this file to
``benchmarks/references/``.

One dense layer, words -> tags, a sigmoid cross-entropy summed over the
tags and averaged over the real rows of a batch, plain SGD. float32
throughout at ``highest`` matmul precision. Independent of ``fedml_tpu``:
the only thing shared with the program is the name of the parameter tree's
leaves (``params/linear/{kernel,bias}``), the format the program takes its
weights in.

The configuration states: bf16 features (rounded on the host before they
are shipped), float32 parameters, float32 aggregation; the matmul runs at
the TPU's default precision (bf16 operands, float32 accumulation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references._sgd import make_local_train

#: what the harness may ask for. ``reference`` is the yardstick; ``stated``
#: is the reference at the configuration's own precision and has to pass;
#: every name in CONTROLS is one step lower and has to fail.
VARIANTS = ("reference", "stated", "params_bf16", "features_fp8")
CONTROLS = ("params_bf16", "features_fp8")

AGGREGATE_DTYPE = {"params_bf16": jnp.bfloat16}


def init(key: jax.Array, config: dict) -> dict:
    d, c = int(config["data"]["input_dim"]), int(config["data"]["classes"])
    kernel = jax.random.normal(key, (d, c), jnp.float32) / jnp.sqrt(float(d))
    return {"params": {"linear": {"kernel": kernel,
                                  "bias": jnp.zeros((c,), jnp.float32)}}}


def _bce(logits, targets):
    return (jnp.maximum(logits, 0.0) - logits * targets
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _make(config: dict, variant: str):
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    low = variant != "reference"
    feat = {"features_fp8": jnp.float8_e4m3fn}.get(variant, jnp.bfloat16)

    def loss_fn(params, state, bx, by, bm):
        p = params["linear"]
        if low:
            # bf16 (or, in the control, fp8) features; bf16 operands into a
            # float32 accumulator, as the TPU's default precision does
            bx = bx.astype(feat).astype(jnp.bfloat16)
            logits = jnp.dot(bx, p["kernel"].astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32) + p["bias"]
        else:
            logits = jnp.dot(bx, p["kernel"],
                             precision=jax.lax.Precision.HIGHEST) + p["bias"]
        per = jnp.sum(_bce(logits, by), axis=-1)
        return jnp.sum(per * bm) / jnp.maximum(jnp.sum(bm), 1.0), state

    r = config["recipe"]
    return make_local_train(
        loss_fn, lr=float(r["lr"]), momentum=float(r["momentum"]),
        store_dtype=jnp.bfloat16 if variant == "params_bf16" else None)


_built: dict = {}


def local_train(config: dict, variables: dict, xs, ys, ms, steps_real,
                variant: str = "reference"):
    """One client's local training from ``variables``; -> (variables, loss)."""
    key = (config["name"], variant)
    if key not in _built:
        _built[key] = _make(config, variant)
    params, _state, loss = _built[key](
        variables["params"], {}, jnp.asarray(xs), jnp.asarray(ys),
        jnp.asarray(ms), jnp.int32(steps_real))
    return {"params": params}, loss
