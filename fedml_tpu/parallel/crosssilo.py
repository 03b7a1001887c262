"""Cross-silo distributed FedAvg: one (or more) clients per device over a
Mesh, aggregation by weighted psum on ICI.

This replaces the reference's entire distributed stack for in-datacenter
runs — the rank-0 Aggregator + ServerManager / rank-i Trainer + ClientManager
star protocol with pickled state dicts over MPI (SURVEY.md §3.2,
FedAvgAPI.py:20-28, FedAVGAggregator.py:58-87, com_manager.py:71-93). One
``shard_map``-ped jit program per round:

    device d: vmap(local_train) over its clients -> weighted partial sums
    all-reduce: psum(sum_i w_i * params_i) / psum(sum_i w_i)

No server rank, no message passing, no 0.3 s poll loops; the collective IS
the aggregation. Multi-host pods run the same code under jax.distributed.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from fedml_tpu.core.rng import server_key
from fedml_tpu.obs.tracer import SCOPE_AGGREGATE, SCOPE_SERVER
from fedml_tpu.parallel.local import LocalResult


def weighted_psum_tree_mean(tree, w, axis, denom):
    """The one weighted-mean-by-all-reduce used by every mesh aggregation:
    per-leaf ``psum_over(axis)(sum_i w_i * x_i) / denom`` with f32
    accumulation and a cast back to the leaf dtype. ``denom`` must already
    be the psum'd total weight (epsilon-guarded by the caller) so callers
    with different reduction scopes (global vs per-group) share this one
    numerically sensitive body."""

    def reduce_leaf(x):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
        s = jax.lax.psum(jnp.sum(x.astype(jnp.float32) * wb, axis=0), axis)
        return (s / denom).astype(x.dtype)

    return jax.tree.map(reduce_leaf, tree)


def make_crosssilo_round(
    local_train: Callable,
    mesh: Mesh,
    axis: str = "clients",
    client_transform: Callable | None = None,
    reduce_extras: Callable | None = None,
    server_update: Callable | None = None,
    lens: bool = False,
):
    """Build the jitted cross-silo round function.

    The three hooks are how the whole algorithm zoo runs on the mesh path —
    the reference deploys each algorithm as its own Aggregator subclass over
    MPI (FedOptAggregator.py:70-120, FedAvgRobustAggregator.py:14-60); here
    an algorithm is (per-client transform, extra reductions, post-collective
    server transform) around the one weighted-psum program:

      client_transform(global_vars, stacked_vars) -> stacked_vars
        per-device, applied to the locally-trained client variables BEFORE
        the psum (AGC / norm clipping of updates).
      reduce_extras(global_vars, res, w) -> pytree of f32 partial SUMS
        per-device weighted partial sums that ride the same all-reduce as
        the parameters (FedNova's normalized-update sums); psum'd leafwise.
      server_update(vars0, agg, extras, total, server_state, rng)
        -> (new_vars, new_server_state)
        applied identically on every device AFTER the psum, on replicated
        values only (FedOpt server optimizer, weak-DP noise). ``extras`` is
        the psum of reduce_extras (or None), ``total`` the psum'd weight.

    Args:
      local_train: per-client function from make_local_train_fn.
      mesh: 1-D mesh with ``axis``.

    Returns round_fn(variables, server_state, cx, cy, cm, counts, keys, rng)
    -> (variables, server_state, loss) where cx/cy/cm/counts/keys are stacked
    over sampled clients (leading axis divisible by mesh size) and variables /
    server_state / rng are replicated.
    """

    finish = _make_mesh_finish(axis, client_transform, reduce_extras,
                               server_update, lens=lens)

    def shard_fn(variables, server_state, cx, cy, cm, counts, keys, rng):
        variables0 = variables  # replicated original (all-failed fallback)
        # Mark the replicated global weights as device-varying before local
        # training. Without this, JAX's varying-manual-axes autodiff treats
        # the loss as a GLOBAL objective and auto-psums the gradient across
        # devices — every client would train on the sum of all gradients.
        variables = jax.tree.map(
            lambda x: jax.lax.pcast(x, axis_name=axis, to="varying"), variables
        )
        res: LocalResult = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0, 0))(
            variables, cx, cy, cm, counts, keys
        )
        return finish(variables0, variables, server_state, res, counts, rng)

    out_specs = ((P(), P(), P(), P(axis)) if lens else (P(), P(), P()))
    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=out_specs,
    )
    return jax.jit(mapped)


def _make_mesh_finish(axis, client_transform, reduce_extras, server_update,
                      lens: bool = False):
    """The shared post-local-training tail of a mesh round: per-client hook →
    weighted psum mean → extra reductions → loss → server hook → elastic
    all-failed rollback. One definition so the plain and grouped round
    programs cannot drift (``variables`` is the pcast device-varying copy the
    local training consumed; ``variables0`` the replicated original)."""

    def finish(variables0, variables, server_state, res: LocalResult, counts, rng):
        with jax.named_scope(SCOPE_AGGREGATE):
            stacked = res.variables
            if client_transform is not None:
                stacked = client_transform(variables, stacked)
            w = counts.astype(jnp.float32)
            total = jax.lax.psum(jnp.sum(w), axis)
            denom = jnp.maximum(total, 1e-12)
            agg = weighted_psum_tree_mean(stacked, w, axis, denom)
            extras = None
            if reduce_extras is not None:
                extras = jax.tree.map(
                    lambda x: jax.lax.psum(x, axis),
                    reduce_extras(variables, res, w),
                )
            loss = jax.lax.psum(jnp.sum(res.train_loss * w), axis) / denom
        new_vars, new_state = apply_server_and_rollback(
            variables0, agg, extras, total, server_state, rng, server_update)
        if lens:
            # fedlens on the mesh: per-shard norms/dots against the GLOBAL
            # raw weighted-mean update (its own f32 psum — the agg above is
            # post-client_transform and dtype-cast, deliberately not reused
            # so robust clipping can't hide an attacker and the alignment
            # definition matches obs/lens.stacked_lens bit-for-bit in sim).
            # Output-only: nothing below feeds new_vars/new_state, so an
            # armed program aggregates bit-identically.
            f32 = jnp.float32
            upd = jax.tree.leaves(jax.tree.map(
                lambda s, v: s.astype(f32) - v.astype(f32)[None],
                res.variables["params"], variables0["params"]))
            n = upd[0].shape[0]
            flat = [u.reshape((n, -1)) for u in upd]
            n2 = sum(jnp.sum(u * u, axis=1) for u in flat)
            wb = w.reshape((-1, 1)).astype(f32)
            mean = [jax.lax.psum(jnp.sum(u * wb, axis=0), axis) / denom
                    for u in flat]
            m2 = sum(jnp.sum(m * m) for m in mean)
            dots = sum(u @ m for u, m in zip(flat, mean))
            norm = jnp.sqrt(n2)
            ldict = {"update_norm": norm,
                     "align": dots / jnp.maximum(norm * jnp.sqrt(m2), 1e-12)}
            first = getattr(res, "first_loss", None)
            if first is not None:
                ldict["loss_delta"] = (first.astype(f32)
                                       - res.train_loss.astype(f32))
            return new_vars, new_state, loss, ldict
        return new_vars, new_state, loss

    return finish


def apply_server_and_rollback(variables0, agg, extras, total, server_state,
                              rng, server_update):
    """The ONE post-aggregation tail every non-vmap round shares — the
    mesh rounds (plain, grouped, and packed — parallel/packed.py) AND,
    since packed-everywhere, the packed SIMULATION round
    (FedAvgAPI.build_round_step_packed), which passes already-summed
    (psum-free) values: the server hook on replicated values with the
    round's server key, then the elastic all-failed rollback. Zero-count clients (failed/dropped, counts*live=0)
    contribute nothing to ``agg``; if EVERY client failed the round is a
    full no-op — weights AND server state roll back (matching the
    simulation paradigm's _finish_round guard), else the server optimizer
    would absorb the garbage zero-aggregate pseudo-gradient."""
    with jax.named_scope(SCOPE_SERVER):
        if server_update is not None:
            new_vars, new_state = server_update(
                variables0, agg, extras, total, server_state, server_key(rng)
            )
        else:
            new_vars, new_state = agg, server_state
        keep = total > 0
        new_vars = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_vars, variables0)
        new_state = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_state, server_state)
    return new_vars, new_state


def make_hierarchical_round(
    local_train: Callable,
    mesh: Mesh,
    group_rounds: int = 1,
    group_axis: str = "group",
    client_axis: str = "clients",
):
    """Two-tier aggregation on a 2-D ('group', 'clients') mesh — the
    distributed form of hierarchical FL (SURVEY.md §2.6.5, reference
    hierarchical_fl/trainer.py:43-69 runs it as nested Python loops over
    processes).

    Topology mapping: the ``clients`` axis should be ICI-adjacent (within a
    pod slice) because the group aggregation psums over it every group
    round; the ``group`` axis can ride DCN across slices because it is
    reduced ONCE per global round. Each device holds a stack of its group's
    clients; semantics match HierarchicalFedAvgAPI with grouping
    gid = mesh row (see tests).

    Returns round_fn(variables, cx, cy, cm, counts, keys) -> (vars, loss)
    where cx/cy/cm/counts are stacked [G, C/G, ...] sharded over both axes
    and keys is [group_rounds, G, C/G] per-client PRNG keys (same sharding
    on its trailing two axes), so every client's randomness is independent.
    """

    def shard_fn(variables, cx, cy, cm, counts, keys):
        # local shards arrive [1, c_local, ...] — flatten the group dim
        cx, cy, cm = (a.reshape((-1,) + a.shape[2:]) for a in (cx, cy, cm))
        counts = counts.reshape((-1,))
        keys = keys.reshape((keys.shape[0], -1))          # [rounds, c_local]
        variables0 = variables
        variables = jax.tree.map(
            lambda x: jax.lax.pcast(x, axis_name=(group_axis, client_axis),
                                    to="varying"), variables
        )
        w = counts.astype(jnp.float32)
        gmass = jax.lax.psum(jnp.sum(w), client_axis)     # this group's mass
        gden = jnp.maximum(gmass, 1e-12)

        def one_group_round(gvars, keys_local):
            res: LocalResult = jax.vmap(local_train, in_axes=(None, 0, 0, 0, 0, 0))(
                gvars, cx, cy, cm, counts, keys_local
            )
            # reduce over the client axis only: ICI within the group
            gvars = weighted_psum_tree_mean(res.variables, w, client_axis, gden)
            loss = jax.lax.psum(jnp.sum(res.train_loss * w), client_axis) / gden
            return gvars, loss

        gvars, losses = jax.lax.scan(one_group_round, variables, keys)
        # global: group models weighted by group mass — one reduce over the
        # group axis (DCN on a real pod)
        total = jax.lax.psum(gmass, group_axis)
        keep = total > 0

        def global_leaf(x):
            s = jax.lax.psum(x.astype(jnp.float32) * gmass, group_axis)
            return (s / jnp.maximum(total, 1e-12)).astype(x.dtype)

        new_vars = jax.tree.map(global_leaf, gvars)
        new_vars = jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                                new_vars, variables0)
        loss = jax.lax.psum(losses[-1] * gmass, group_axis) / jnp.maximum(total, 1e-12)
        return new_vars, loss

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(group_axis, client_axis), P(group_axis, client_axis),
                  P(group_axis, client_axis), P(group_axis, client_axis),
                  P(None, group_axis, client_axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


def place_round_inputs(mesh: Mesh, variables, cx, cy, cm, counts, keys, axis="clients"):
    """Device placement for one round: variables replicated, client-stacked
    arrays sharded along the client axis (the round's single host->device
    transfer)."""
    from fedml_tpu.parallel.mesh import global_put, replicated, shard_client_batch

    variables = global_put(variables, replicated(mesh))
    return (variables,) + shard_client_batch(mesh, (cx, cy, cm, counts, keys), axis)
