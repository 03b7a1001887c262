"""Sequence/context parallelism: ring attention over an 'sp' mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5.7 — its sequence
models are 80-token LSTMs). For a TPU-native framework long context is
first-class: a sequence is sharded over the mesh's 'sp' axis, every device
holds the full model and one sequence shard, and attention runs as a ring —
each device's K/V shard hops around the ring via ``ppermute`` over ICI while
queries stay put, with partial softmax results merged online
(:func:`fedml_tpu.ops.attention.merge_partials`). Compute overlaps the
collective naturally: XLA pipelines the next hop's ppermute against the
current block's flash kernel.

The same function composes with federated axes: a ('clients', 'sp') 2-D mesh
trains each client's long-sequence model with its own ring, and the weighted
psum aggregation rides the 'clients' axis (fedml_tpu/parallel/crosssilo.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.ops.attention import (
    NEG_INF,
    attention,
    attention_block_partial,
    merge_partials,
    normalize_partial,
)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    axis_name: str, axis_size: int, causal: bool = True,
    sm_scale: Optional[float] = None, impl: str = "auto",
    interpret: bool = False, window: Optional[int] = None,
) -> jax.Array:
    """Attention over a sequence sharded along ``axis_name``.

    Call INSIDE ``shard_map``; ``q/k/v`` are the local shards ``[B, H, Tl,
    D]`` of a global ``[B, H, axis_size*Tl, D]`` sequence laid out in order
    of mesh position. Runs ``axis_size`` ring steps: local K/V chunks rotate
    to the next device each step (``ppermute``), partial (o, m, l) results
    merge online, one normalization at the end. Causal masking uses global
    positions, so fully-future chunks contribute nothing (their rows stay at
    -inf / l=0).
    """
    idx = jax.lax.axis_index(axis_name)
    tl = q.shape[2]
    q_off = idx * tl
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:3], jnp.float32)

    def compute(acc, k_cur, v_cur, i):
        src = (idx - i) % axis_size          # whose shard we hold this step
        part = attention_block_partial(
            q, k_cur, v_cur, q_offset=q_off, k_offset=src * tl,
            causal=causal, sm_scale=sm_scale, impl=impl, interpret=interpret,
            window=window)      # refused there: the ring knows no window
        return merge_partials(acc, part)

    # step 0 on the resident shard, then permute-then-compute for the rest:
    # exactly axis_size-1 ppermutes (no dead final rotation on the wire).
    acc = compute((o0, m0, l0), k, v, 0)

    def step(carry, i):
        acc, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        return (compute(acc, k_cur, v_cur, i), k_cur, v_cur), None

    (acc, _, _), _ = jax.lax.scan(step, (acc, k, v),
                                  jnp.arange(1, axis_size))
    return normalize_partial(*acc, out_dtype=q.dtype)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    axis_name: str, axis_size: int, causal: bool = True,
    sm_scale: Optional[float] = None, impl: str = "auto",
    interpret: bool = False, window: Optional[int] = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Call INSIDE ``shard_map`` with the same layout as :func:`ring_attention`
    (local shards ``[B, H, Tl, D]`` of a sequence sharded along
    ``axis_name``). Two ``all_to_all`` reshards instead of a ring of
    ppermutes: heads scatter / sequence gathers, so each device runs FULL
    attention for ``H/axis_size`` heads over the whole sequence, then the
    inverse reshard restores sequence sharding. Communication volume is
    O(T·D·H/n) per device independent of step count — cheaper than the ring
    when heads are plentiful and ICI all-to-all bandwidth is good; the ring
    wins when H < axis_size or memory for the full-T K/V is tight. Both are
    exact (tests assert equality with single-device dense attention).

    Requires ``H % axis_size == 0``.
    """
    B, H, tl, D = q.shape
    if H % axis_size:
        raise ValueError(
            f"ulysses needs heads ({H}) divisible by the sp axis ({axis_size}); "
            "use ring_attention for head counts below the axis size"
        )

    def scatter_heads(x):
        # [B, H, Tl, D] -> [B, H/n, n*Tl, D]: head groups scatter, seq gathers
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = attention(qg, kg, vg, causal=causal, sm_scale=sm_scale,
                    impl=impl, interpret=interpret, window=window)
    # inverse: sequence scatters back, head groups gather
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def sequence_attention(
    q, k, v, *, axis_name: str, axis_size: int, mode: str = "ring", **kw
) -> jax.Array:
    """Dispatch between the two exact sequence-parallel attention schemes."""
    if mode == "ring":
        return ring_attention(q, k, v, axis_name=axis_name, axis_size=axis_size, **kw)
    if mode == "ulysses":
        return ulysses_attention(q, k, v, axis_name=axis_name, axis_size=axis_size, **kw)
    raise ValueError(f"unknown sequence-parallel mode {mode!r} (ring|ulysses)")


# ---------------------------------------------------------------------------
# Sequence-parallel LM training step
# ---------------------------------------------------------------------------

def sp_mesh(n_dp: int, n_sp: int) -> Mesh:
    """2-D (dp, sp) mesh: batch over dp, sequence over sp (ICI-adjacent)."""
    devs = jax.devices()
    need = n_dp * n_sp
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:need]).reshape(n_dp, n_sp), ("dp", "sp"))


def make_sp_lm_train_step(
    module, tx, mesh: Mesh, *, attn_impl: str = "auto",
    interpret: bool = False,
) -> Callable:
    """Build a jitted LM train step over a ('dp', 'sp') mesh.

    ``module`` is a TransformerLM (fedml_tpu/models/transformer.py) built
    with ``ring_axis='sp'`` and ``ring_size=mesh.shape['sp']``; ``tx`` an
    optax transformation. Returns ``step(variables, opt_state, x, y, mask,
    rng) -> (variables, opt_state, loss)`` where ``x/y [B, T]`` global
    arrays get sharded P('dp', 'sp'); params replicated; grads psum over
    both axes.
    """
    from jax import shard_map

    n_sp = mesh.shape["sp"]

    def local_step(variables, opt_state, x, y, mask, rng):
        tl = x.shape[1]                      # local seq shard length
        pos_off = jax.lax.axis_index("sp") * tl
        # global token count, computed OUTSIDE the differentiated graph: a
        # scalar psum inside loss_fn would transpose to another psum and
        # scale every cotangent by the mesh size (8x grads on an 8-device
        # mesh — exactness-tested against the single-device step).
        total = jax.lax.psum(jnp.sum(mask.astype(jnp.float32)), ("dp", "sp"))

        def loss_fn(params):
            vars_in = dict(variables)
            vars_in["params"] = params
            logits = module.apply(vars_in, x, train=True, pos_offset=pos_off,
                                  rngs={"dropout": rng})
            from fedml_tpu.ops.xent import masked_cross_entropy

            per = masked_cross_entropy(logits, y, mask, impl=attn_impl,
                                       interpret=interpret)
            return jnp.sum(per) / jnp.maximum(total, 1.0)

        local_loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
        # local_loss divides by the GLOBAL token count, so each device's
        # grad is its local contribution to the true mean — sum, not mean.
        loss = jax.lax.psum(local_loss, ("dp", "sp"))
        grads = jax.lax.psum(grads, ("dp", "sp"))
        import optax

        updates, new_opt = tx.update(grads, opt_state, variables["params"])
        new_params = optax.apply_updates(variables["params"], updates)
        out_vars = dict(variables)
        out_vars["params"] = new_params
        return out_vars, new_opt, loss

    repl = P()
    sharded = P("dp", "sp")
    step = shard_map(
        local_step, mesh=mesh,
        in_specs=(repl, repl, sharded, sharded, sharded, repl),
        out_specs=(repl, repl, repl),
        check_vma=False,
    )
    jitted = jax.jit(step, donate_argnums=(0, 1))

    def run(variables, opt_state, x, y, mask, rng):
        xs = jax.device_put(x, NamedSharding(mesh, sharded))
        ys = jax.device_put(y, NamedSharding(mesh, sharded))
        ms = jax.device_put(mask, NamedSharding(mesh, sharded))
        return jitted(variables, opt_state, xs, ys, ms, rng)

    run.mesh = mesh
    run.n_sp = n_sp
    return run
