"""Tensor (intra-layer model) parallelism for the transformer LM.

The reference predates LLM-era parallelism entirely (SURVEY.md §2.6) — TP
exists here because the TPU-native framework treats long-context/LLM
training as first-class. The scheme is the Megatron split expressed purely
through GSPMD placement: no model surgery, no manual collectives.

- ``qkv`` projection kernel ``[D, 3D]`` shards its OUTPUT dim over 'tp'
  (each device computes a head subset), ``attn.out`` kernel ``[D, D]``
  shards its INPUT dim (row-parallel) so the matmul's partial results
  all-reduce once per attention block.
- MLP up-projection ``[D, 4D]`` is column-parallel, down-projection
  ``[4D, D]`` row-parallel — one all-reduce per MLP.
- everything else (embeddings, layernorms, lm_head, biases of row-parallel
  layers) stays replicated.

XLA's sharding propagation inserts exactly the Megatron communication
pattern from these parameter placements; the step function itself is the
unmodified single-device step, so TP results equal single-device results
to float tolerance (tested).
"""

from __future__ import annotations

import re
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: Megatron TP placement: (path regex, spec builder) — first match wins;
#: default replicated.
_TP_RULES = (
    (re.compile(r"attn.*qkv.*kernel"), lambda tp: P(None, tp)),
    (re.compile(r"attn.*qkv.*bias"), lambda tp: P(tp)),
    (re.compile(r"attn.*out.*kernel"), lambda tp: P(tp, None)),
    (re.compile(r"Dense_0.*kernel"), lambda tp: P(None, tp)),   # MLP up
    (re.compile(r"Dense_0.*bias"), lambda tp: P(tp)),
    (re.compile(r"Dense_1.*kernel"), lambda tp: P(tp, None)),   # MLP down
)

#: expert-parallel placement: stacked expert weights [E, ...] shard their
#: leading (expert) axis; router + everything else replicated.
_EP_RULES = (
    (re.compile(r"moe.*w_(up|dn)"), lambda ep: P(ep)),
    (re.compile(r"moe.*b_(up|dn)"), lambda ep: P(ep)),
)


def _spec_for(rules, path: str, axis: str) -> P:
    for rx, spec in rules:
        if rx.search(path):
            return spec(axis)
    return P()


def _shard_params(variables, mesh: Mesh, rules, axis: str):
    def place(path, leaf):
        spec = _spec_for(rules, jax.tree_util.keystr(path), axis)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, variables)


def _mesh2d(n_dp: int, n_other: int, other_axis: str) -> Mesh:
    devs = jax.devices()
    need = n_dp * n_other
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:need]).reshape(n_dp, n_other),
                ("dp", other_axis))


def tp_spec(path: str, tp_axis: str = "tp") -> P:
    """Megatron PartitionSpec for one parameter path (default replicated)."""
    return _spec_for(_TP_RULES, path, tp_axis)


def shard_params_tp(variables, mesh: Mesh, tp_axis: str = "tp"):
    """device_put the variable tree with Megatron TP shardings over
    ``mesh``'s 'tp' axis. Heads and MLP hidden must divide the axis size."""
    return _shard_params(variables, mesh, _TP_RULES, tp_axis)


def tp_mesh(n_dp: int, n_tp: int) -> Mesh:
    """2-D (dp, tp) mesh: batch over dp, tensor-parallel over tp (keep tp
    ICI-adjacent — it all-reduces twice per layer)."""
    return _mesh2d(n_dp, n_tp, "tp")


def ep_spec(path: str, ep_axis: str = "ep") -> P:
    """Expert-parallel PartitionSpec for one parameter path."""
    return _spec_for(_EP_RULES, path, ep_axis)


def shard_params_ep(variables, mesh: Mesh, ep_axis: str = "ep"):
    """device_put a MoeTransformerLM variable tree with the expert axis of
    every expert weight sharded over ``mesh``'s 'ep' axis — each device
    stores (and computes) only its experts. num_experts must divide the
    axis size."""
    return _shard_params(variables, mesh, _EP_RULES, ep_axis)


def ep_mesh(n_dp: int, n_ep: int) -> Mesh:
    """2-D (dp, ep) mesh: batch over dp, experts over ep."""
    return _mesh2d(n_dp, n_ep, "ep")


def make_tp_lm_train_step(
    module, tx: optax.GradientTransformation, mesh: Mesh,
) -> Callable:
    """Build an LM train step whose parallelism comes entirely from
    placement: call ``shard_params_tp(variables, mesh)`` once (the optax
    state inherits the shardings via ``tx.init`` on the sharded params) and
    pass batches with the batch axis on 'dp'. Returns
    ``step(variables, opt_state, x, y, mask, rng)``; use
    ``attn_impl='xla'`` modules so attention stays partitionable, and the
    loss below takes the XLA cross-entropy for the same reason: GSPMD
    cannot partition a Mosaic kernel (on a four-chip v5e host the
    ``impl='auto'`` loss raised "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map." — PR 21; a CPU run
    never sees it because 'auto' is XLA off-TPU).
    """
    from fedml_tpu.ops.xent import masked_cross_entropy

    data_shard = NamedSharding(mesh, P("dp", None))

    def step(variables, opt_state, x, y, mask, rng):
        def loss_fn(params):
            vars_in = dict(variables)
            vars_in["params"] = params
            logits = module.apply(vars_in, x, train=True, rngs={"dropout": rng})
            per = masked_cross_entropy(logits, y, mask, impl="xla")
            cnt = jnp.sum(mask.astype(jnp.float32))
            return jnp.sum(per) / jnp.maximum(cnt, 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
        updates, new_opt = tx.update(grads, opt_state, variables["params"])
        new_params = optax.apply_updates(variables["params"], updates)
        out = dict(variables)
        out["params"] = new_params
        return out, new_opt, loss

    jitted = jax.jit(step, donate_argnums=(0, 1))

    def run(variables, opt_state, x, y, mask, rng):
        x = jax.device_put(x, data_shard)
        y = jax.device_put(y, data_shard)
        mask = jax.device_put(mask, data_shard)
        return jitted(variables, opt_state, x, y, mask, rng)

    run.mesh = mesh
    return run
