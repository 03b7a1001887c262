"""Client-packing schedule: many small clients share one scan lane.

The bucketed schedule (algorithms/fedavg.py `_round_bucket`) cuts padding
by trimming the scan to the cohort's largest client — but every client
still pads to that max (a grouped variant with one scan length per
count-sorted group, deleted in PR 28, still left 15% (sim) / 21% (mesh) of
executed slots dead). This module removes the max: the cohort is packed into a few
fixed-length lanes (LPT balancing), each lane running its clients
BACK-TO-BACK in one loop with optimizer-state reset at client
boundaries. Padding shrinks to the final partial batch of each client plus
what a lane lacks to the longest lane vmapped WITH it — one-batch
granularity instead of group-max granularity. The plan's ``T`` is a shape
(one XLA program for every round that shares it), not a step count: the
lanes that advance together walk the plan only as far as their last live
step (:func:`chunk_bounds`), which arrives as data.

Exactness: each client's trajectory REPLAYS the canonical unbucketed
program (`make_local_train_fn` at full n_pad) bit-for-bit — the same
per-epoch `jax.random.permutation(ekey, n_pad)` + real-first stable sort
and the same per-step batch keys, of which the packed lane simply executes
only the `ceil(count/bs)` real steps. The round aggregate is the same
weighted mean up to float summation order (lanes accumulate
`sum(w_i * vars_i)` locally).

The reference has no analogue: its clients are OS processes; padding is a
TPU-ism (SURVEY.md §7 hard part (a)) and packing is the TPU-native answer.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from fedml_tpu.core.pytree import map_chunks
from fedml_tpu.core.tasks import Task
from fedml_tpu.models import ModelBundle
from fedml_tpu.obs.tracer import (SCOPE_AGGREGATE, SCOPE_PROLOGUE, SCOPE_STEP,
                                  SCOPE_STEP_EMIT, SCOPE_STEP_GATHER,
                                  SCOPE_STEP_RESET)
from fedml_tpu.parallel.local import (EPOCH_KEY_SALT as _EPOCH_KEY_SALT,
                                      make_batch_sgd_step, make_optimizer)

class PackPlan(NamedTuple):
    """Static lane schedule for one cohort. Shapes (n_lanes, k_max, T) are
    the compile signature; the arrays are runtime data, so rounds with the
    same shapes share one XLA program."""

    n_lanes: int
    k_max: int
    T: int                 # scan steps per lane
    epochs: int
    # [n_lanes, T] per-step metadata
    slot: np.ndarray       # which member slot trains this step (0 on dead steps)
    epoch: np.ndarray      # epoch index
    sie: np.ndarray        # step within the epoch
    reset: np.ndarray      # 1.0 at a client's first step
    emit: np.ndarray       # 1.0 at a client's last step
    live: np.ndarray       # 0.0 on dead lane-tail steps
    # [n_lanes, k_max] per-member metadata
    member_pos: np.ndarray   # position in the sampled cohort (0-padded)
    member_valid: np.ndarray  # 1.0 for real members
    steps_real: np.ndarray   # ceil(count/bs) per member (>=1 for real members)

    @property
    def shape_key(self) -> tuple:
        return (self.n_lanes, self.k_max, self.T, self.epochs)

    def executed_slots(self, width: int, unroll: int = 1) -> int:
        """Batch slots the schedule executes when its lanes advance
        ``width`` at a time (for padded-throughput accounting): each chunk's
        lanes x the steps that chunk walks (:func:`chunk_bounds`, the bound
        the lane program itself takes) — without the batch factor."""
        return int(width * chunk_bounds(self.live, width, unroll).sum())

    def tree_pass_steps(self, width: int, unroll: int = 1) -> int:
        """Client boundaries on the steps the chunks walk: the ``reset`` and
        ``emit`` flags set under each chunk's bound (:func:`chunk_bounds`,
        as :meth:`executed_slots` takes it), counted together. In a round of
        ONE lane these are the passes over the parameter tree its program
        still makes (:func:`make_lane_train`'s ``branch``), of two a walked
        step; lanes under ``vmap`` make both on every step whatever it
        reads."""
        walked = (np.arange(self.T) < np.repeat(
            chunk_bounds(self.live, width, unroll), width)[:, None])
        return int(((self.reset > 0) & walked).sum()
                   + ((self.emit > 0) & walked).sum())


def plan_packing(counts: np.ndarray, batch_size: int, epochs: int,
                 n_lanes: int, t_quantum: int = 1) -> Optional[PackPlan]:
    """LPT-pack the cohort (client j costs ``epochs * ceil(count_j/bs)``
    consecutive steps) into ``n_lanes`` lanes; T = max lane load rounded up
    to ``t_quantum`` steps. Returns None when the cohort is empty."""
    counts = np.asarray(counts, np.float64)
    steps = np.ceil(np.maximum(counts, 0.0) / batch_size).astype(np.int64)
    members = np.nonzero(steps > 0)[0]
    if members.size == 0 or n_lanes < 1:
        return None
    n_lanes = int(min(n_lanes, members.size))
    cost = epochs * steps[members]
    order = np.argsort(-cost, kind="stable")          # LPT: biggest first
    lanes: list[list[int]] = [[] for _ in range(n_lanes)]
    loads = np.zeros(n_lanes, np.int64)
    for j in order:
        l = int(np.argmin(loads))
        lanes[l].append(int(members[j]))
        loads[l] += cost[j]
    T = int(np.ceil(loads.max() / max(t_quantum, 1)) * max(t_quantum, 1))
    k_max = max(len(l) for l in lanes)

    slot = np.zeros((n_lanes, T), np.int32)
    epoch = np.zeros((n_lanes, T), np.int32)
    sie = np.zeros((n_lanes, T), np.int32)
    reset = np.zeros((n_lanes, T), np.float32)
    emit = np.zeros((n_lanes, T), np.float32)
    live = np.zeros((n_lanes, T), np.float32)
    member_pos = np.zeros((n_lanes, k_max), np.int32)
    member_valid = np.zeros((n_lanes, k_max), np.float32)
    steps_real = np.ones((n_lanes, k_max), np.int32)

    for l, mem in enumerate(lanes):
        t = 0
        for k, pos in enumerate(mem):
            member_pos[l, k] = pos
            member_valid[l, k] = 1.0
            s = int(steps[pos])
            steps_real[l, k] = s
            reset[l, t] = 1.0
            for e in range(epochs):
                for si in range(s):
                    slot[l, t] = k
                    epoch[l, t] = e
                    sie[l, t] = si
                    live[l, t] = 1.0
                    t += 1
            emit[l, t - 1] = 1.0
        # steps t..T-1 stay dead (slot 0, live 0)

    return PackPlan(n_lanes, k_max, T, epochs, slot, epoch, sie, reset, emit,
                    live, member_pos, member_valid, steps_real)


def chunk_bounds(live, width: int, unroll: int = 1):
    """How many of a plan's ``T`` steps each chunk of ``width`` neighbouring
    lanes walks: one past the last step that is live in ANY of the chunk's
    lanes (0 for a chunk with none), rounded up to whole blocks of
    ``unroll`` steps. Not ``sum(live)``: a masked plan
    (:func:`masked_plan`) has dead spans in the middle of a lane, which
    are walked to reach the next live member. ``live``: ``[n_lanes, T]``,
    NumPy or JAX; -> ``[n_lanes // width]`` ints of the same kind.

    THE one definition: the lane programs take their loop's bound from it
    (:func:`make_lanes_train`, :func:`make_packed_lanes_train`) and the round
    driver its count of executed slots (:meth:`PackPlan.executed_slots`), so
    the count cannot drift from the program."""
    n, T = live.shape
    unroll = max(int(unroll), 1)
    last = ((live > 0) * np.arange(1, T + 1, dtype=np.int32)).max(axis=1)
    bound = last.reshape(n // width, width).max(axis=1)
    return -(-bound // unroll) * unroll


def _walk_steps(step_fn: Callable, carry0, steps: tuple, bound, unroll: int):
    """``carry = step_fn(carry, step)`` over the first ``bound`` entries of
    the ``steps`` arrays' leading axis, in blocks of ``unroll``; ``bound``
    (a traced scalar, from :func:`chunk_bounds`) is the loop's trip count,
    so one program serves every bound. Every step from ``bound`` on must be
    one whose effects the step itself discards (``live == 0``, no emit):
    the result is then the whole scan's. Nothing differentiates through
    this loop (the gradients are inside the step), so it may be a while."""
    unroll = max(int(unroll), 1)
    pad = -steps[0].shape[0] % unroll
    if pad:
        # whole blocks: the steps added are dead ones (live 0, emit 0)
        steps = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                      for a in steps)
    blocks, n = steps[0].shape[0] // unroll, bound // unroll

    def block(loop):
        i, carry = loop
        for j in range(unroll):
            carry = step_fn(carry, tuple(
                jax.lax.dynamic_index_in_dim(a, i * unroll + j, keepdims=False)
                for a in steps))
        return i + 1, carry

    # the plan's length stays in the condition as a constant: the loop's
    # static ceiling, which the static cost model counts the body at
    # (obs/cost.py) and past which an index would re-read the last step
    return jax.lax.while_loop(
        lambda loop: (loop[0] < n) & (loop[0] < blocks),
        block, (jnp.int32(0), carry0))[1]


def _member_replay_tables(mask_rows, epochs: int, n_pad: int,
                          steps_full: int):
    """The canonical per-member replay tables — EXACTLY
    make_local_train_fn's per-epoch ``permutation`` over the global n_pad,
    real-first stable sort, and ``fold_in(ekey, EPOCH_KEY_SALT)`` batch
    keys. Returns ``member_tables(key, row) -> (orders [E,n_pad],
    bkeys [E,steps_full])``; vmap it over members (and lanes)."""

    def member_tables(key, row):
        mask_row = mask_rows[row]
        ekeys = jax.random.split(key, epochs)

        def per_epoch(ek):
            perm = jax.random.permutation(ek, n_pad)
            order = perm[jnp.argsort(-mask_row[perm], stable=True)]
            bkeys = jax.random.split(
                jax.random.fold_in(ek, _EPOCH_KEY_SALT), steps_full)
            return order, bkeys

        return jax.vmap(per_epoch)(ekeys)

    return member_tables


def _kept_transposed(shape) -> bool:
    """Whether the TPU keeps a float32 array of ``shape`` with its two minor
    axes swapped. It tiles them (8 sublanes x 128 lanes) and keeps the
    array the way round that pads less, as it comes the way round on a tie:
    a ``[2048, 8512]`` kernel lies column-major (2048 x 8,576 against
    8,512 x 2,048 padded), its transpose row-major. Held to the compiler's
    own answer shape by shape in tests/test_tpu_compile_lm.py."""
    if len(shape) < 2:
        return False

    def padded(sublanes, lanes):
        return -(-sublanes // 8) * 8 * (-(-lanes // 128) * 128)

    rows, cols = shape[-2:]
    return padded(cols, rows) < padded(rows, cols)


def _on_flag(flag, update: Callable, kept, *more):
    """``update(kept, *more)`` where ``flag`` (a scalar) is set and ``kept``
    as it came where not, under ``lax.cond``: the step that does not need
    the pass over the trees does not make it.

    Both branches say of every leaf they take and hand back that it lies
    the way round the device keeps it (:func:`_kept_transposed`). The
    compiler lays a branch out by itself, before the loop around it, and
    with nothing said it takes and returns a matrix row-major; a leaf that
    lies column-major is then copied at that door, the loop's carry turns
    row-major with it and a row-major copy of the round's variables is
    kept beside the loop: granite4h_sim_c2, whose nine ``[2048, 8512]``
    kernels lie so, held 589 MB more on the chip and lost two thirds of
    the gain (PERF.md, PR 42). tests/test_tpu_compile_lm.py compiles a loop
    for the chip with this and with a plain ``lax.cond`` and fails when the
    copy is back here, or gone there."""
    def as_kept(tree):
        def leaf(x):
            if not _kept_transposed(x.shape):
                return x
            axes = tuple(range(x.ndim))
            return with_layout_constraint(x, Layout(
                major_to_minor=axes[:-2] + (axes[-1], axes[-2])))
        return jax.tree.map(leaf, tree)

    return jax.lax.cond(
        flag > 0, lambda *trees: as_kept(update(*as_kept(trees))),
        lambda *trees: as_kept(trees[0]), kept, *more)


def make_lane_train(
    bundle: ModelBundle,
    task: Task,
    n_pad: int,
    *,
    optimizer: str = "sgd",
    lr: float = 0.01,
    momentum: float = 0.0,
    wd: float = 0.0,
    epochs: int = 1,
    batch_size: int = 32,
    grad_clip: Optional[float] = None,
    prox_mu: float = 0.0,
    compute_dtype=None,
    scan_unroll: int = 1,
    client_transform: Optional[Callable] = None,
    reduce_extras: Optional[Callable] = None,
    lens: bool = False,
) -> Callable:
    """Build the single-lane program both execution forms share: the
    simulation paradigm vmaps it over all lanes
    (:func:`make_packed_cohort_train`), the cross-silo mesh shard_maps it
    with a psum tail (:func:`make_crosssilo_packed_round`).

    ``client_transform`` / ``reduce_extras`` are the per-client halves of
    the cross-silo hook contract (crosssilo.make_crosssilo_round): both
    take STACKED client results, so the lane applies them at each client's
    emit step with a singleton leading axis — this is how the whole
    algorithm zoo (FedOpt/FedNova/AGC/robust) rides the packed schedule."""
    del compute_dtype  # callers pre-cast the stacked arrays once
    from fedml_tpu.parallel.local import LocalResult
    tx_opt = make_optimizer(optimizer, lr, momentum, wd)
    batch_step = make_batch_sgd_step(
        bundle, task, tx_opt, grad_clip=grad_clip, prox_mu=prox_mu,
        compute_dtype=None,
    )
    steps_full = n_pad // batch_size
    bs = batch_size

    def lane_train(variables0, x_flat, y_flat, m_flat, mask_rows,
                   member_row, member_keys, member_w, steps_real,
                   slot, epoch_a, sie, reset, emit, live, bound, *,
                   branch: bool = False):
        """One lane. x_flat/y_flat/m_flat: [C*n_pad, ...] flattened stacks
        (shared, unbatched); mask_rows [C, n_pad]; member_* are this lane's
        [k_max] arrays; per-step metadata [T]; ``bound``: how many of the T
        steps to walk (:func:`chunk_bounds` of the lanes vmapped together:
        a scalar, unbatched, so the loop's predicate stays one).

        ``branch``: the two passes over the parameter tree that only a
        client's first and last step need (the reset, the emit into the
        lane's sums) run under ``lax.cond`` on the step's own flag, so the
        steps between make neither. For a lane with NO lane axis only
        (:func:`make_lanes_train` says which): under ``vmap`` the flags are
        batched, a branch lowers to both sides and a select, and the lane
        would pay the trace of a branch for the selects it has. The two
        forms share the blocks' bodies, and the arithmetic as written (no
        ``a * b + c`` contracted) agrees bit for bit, but for the sign of a
        zero (``a + 0 * v`` turns ``-0.0`` into ``+0.0``); two COMPILED
        programs may differ by 1 ulp where the backend fuses ``a * b + c``
        in one and not in the other (BatchNorm's running means, on the
        CPU at its default level)."""
        with jax.named_scope(SCOPE_PROLOGUE):
            params0 = variables0["params"]
            opt_state0 = tx_opt.init(params0)

            # Exact replay of make_local_train_fn's per-epoch order and
            # batch keys, per member (shared definition — see
            # _member_replay_tables)
            member_tables = _member_replay_tables(mask_rows, epochs, n_pad,
                                                  steps_full)
            orders, bkeys = jax.vmap(member_tables)(member_keys, member_row)

        def step_fn(carry, xs):
            variables, opt_state, loss_acc = carry[:3]
            # what a client's last step adds to: the lens stacks ride along
            accs = carry[3:8] + (carry[8][:3] if lens else ())
            k, e, s, rs, em, lv = xs
            with jax.named_scope(SCOPE_STEP_RESET):
                # what a client's first step starts from: the round's
                # variables, a new optimizer, no loss (nor the lens's)
                state = (variables, opt_state, loss_acc) + (
                    (carry[8][3],) if lens else ())
                fresh = (variables0, opt_state0) + jax.tree.map(
                    jnp.zeros_like, state[2:])
                if branch:
                    state = _on_flag(rs, lambda state, fresh: fresh,
                                     state, fresh)
                else:
                    state = jax.tree.map(
                        lambda v, z: jnp.where(rs > 0, z, v), state, fresh)
                variables, opt_state, loss_acc = state[:3]

            with jax.named_scope(SCOPE_STEP_GATHER):
                row = member_row[k]
                oseg = jax.lax.dynamic_slice(
                    orders, (k, e, s * bs), (1, 1, bs)).reshape(bs)
                flat = row * n_pad + oseg
                bx = jnp.take(x_flat, flat, axis=0)
                by = jnp.take(y_flat, flat, axis=0)
                bm = jnp.take(m_flat, flat, axis=0)
                bkey = bkeys[k, e, s]

            # batch_step scopes itself (parallel/local.py): step.train with
            # step.opt inside
            new_vars, new_opt, l = batch_step(
                variables, opt_state, params0, bx, by, bm, bkey)

            with jax.named_scope(SCOPE_STEP_EMIT):
                def freeze_if_dead(new, old):
                    return jax.tree.map(
                        lambda n, o: lv * n + (1.0 - lv) * o
                        if jnp.issubdtype(n.dtype, jnp.floating)
                        else jnp.where(lv > 0, n, o),
                        new, old,
                    )

                new_opt = freeze_if_dead(new_opt, opt_state)
                out_vars = dict(freeze_if_dead(new_vars, variables))

                lastep = (e == epochs - 1).astype(jnp.float32)
                loss_acc = loss_acc + l * lv * lastep
                if lens:
                    floss_acc = state[3] + l * lv * (e == 0).astype(jnp.float32)

                def emit_block(accs, out_vars):
                    """The client's result into the lane's sums. Linear in
                    ``em``: a step that ends no client (``em`` 0) adds
                    exactly nothing, so the select form calls it on every
                    step and the branch form only where ``em`` is 1."""
                    acc_vars, acc_w, acc_loss, acc_tau, acc_extras = accs[:5]
                    w = member_w[k] * em
                    sr = jnp.maximum(steps_real[k].astype(jnp.float32), 1.0)
                    if lens:
                        # fedlens member scatter (obs/lens.py): each member
                        # emits exactly once, so .add at its slot is a masked
                        # set. RAW update (pre-client_transform): a robust
                        # clip must not hide the attacker from the lens.
                        upd_stack, l_first, l_last = accs[5:]
                        upd_stack = jax.tree.map(
                            lambda b, v, p: b.at[k].add(
                                em * (v.astype(jnp.float32)
                                      - p.astype(jnp.float32))),
                            upd_stack, out_vars["params"], params0)
                        l_first = l_first.at[k].add(em * floss_acc / sr)
                        l_last = l_last.at[k].add(em * loss_acc / sr)
                    acc_out = out_vars
                    if client_transform is not None:
                        # hook contract is stacked-clients; singleton axis
                        acc_out = jax.tree.map(
                            lambda v: v[0],
                            client_transform(
                                variables0,
                                jax.tree.map(lambda v: v[None], out_vars)))
                    acc_vars = jax.tree.map(
                        lambda a, v: a + w * v, acc_vars, acc_out)
                    acc_w = acc_w + w
                    acc_loss = acc_loss + w * loss_acc / sr
                    acc_tau = acc_tau + w * epochs * sr
                    if reduce_extras is not None:
                        res1 = LocalResult(
                            jax.tree.map(lambda v: v[None], out_vars),
                            (loss_acc / sr)[None], (epochs * sr)[None])
                        # the hook returns WEIGHTED partial sums
                        ex = reduce_extras(variables0, res1, w[None])
                        acc_extras = jax.tree.map(
                            lambda a, b: a + b, acc_extras, ex)
                    out = (acc_vars, acc_w, acc_loss, acc_tau, acc_extras)
                    if lens:
                        out += (upd_stack, l_first, l_last)
                    return out

                if branch:
                    # the barrier (an identity) keeps the compiler from
                    # moving the optimizer's update of the tree into both
                    # branches: laguna_sim_c2's round program, compiled for
                    # the chip, holds 13,892.4 MB without it and 13,513.4
                    # with it (13,879.3 in the select form; PERF.md, PR 42:
                    # section 6 has the chip's readings)
                    accs, out_vars = jax.lax.optimization_barrier(
                        (accs, out_vars))
                    accs = _on_flag(em, emit_block, accs, out_vars)
                else:
                    accs = emit_block(accs, out_vars)
                out = (out_vars, new_opt, loss_acc) + accs[:5]
                if lens:
                    out += (accs[5:] + (floss_acc,),)
            return out

        # zeros DERIVED from inputs, not constants: under shard_map the
        # inputs are device-varying, and a constant-zero carry init would
        # type-clash with the varying carry the loop body produces
        with jax.named_scope(SCOPE_PROLOGUE):
            z = jnp.sum(member_w) * 0.0
            acc0 = jax.tree.map(lambda v: v.astype(jnp.float32) * 0.0, variables0)
            if reduce_extras is not None:
                ex0 = reduce_extras(
                    variables0,
                    LocalResult(jax.tree.map(lambda v: (v * 0.0)[None], variables0),
                                z[None], z[None]),
                    z[None])
                acc_extras0 = jax.tree.map(lambda e: e * 0.0, ex0)
            else:
                acc_extras0 = {}
            carry0 = (variables0, opt_state0, z, acc0, z, z, z, acc_extras0)
            if lens:
                # zeros derived from inputs (shard_map type consistency): the
                # per-member update stack [k_max, *param] plus first/last mean
                # losses [k_max]; same memory class as the vmap fallback's
                # stacked per-client variables
                zk = member_w * 0.0
                upd0 = jax.tree.map(
                    lambda p: zk.reshape(zk.shape + (1,) * p.ndim)
                    * p.astype(jnp.float32)[None], params0)
                carry0 = carry0 + ((upd0, zk, zk, z),)
        with jax.named_scope(SCOPE_STEP):
            final = _walk_steps(
                step_fn, carry0, (slot, epoch_a, sie, reset, emit, live),
                bound, scan_unroll)
        (_, _, _, acc_vars, acc_w, acc_loss, acc_tau, acc_extras) = final[:8]
        if lens:
            return (acc_vars, acc_w, acc_loss, acc_tau, acc_extras,
                    final[8][:3])
        return acc_vars, acc_w, acc_loss, acc_tau, acc_extras

    return lane_train


#: Lanes vmapped together. The plan's lane count says how many lanes a
#: round HAS; how many advance in one grouped convolution is the chip's
#: choice: on the v5e the per-lane form executes 31,215 img/s at a vmap
#: width of 2, 30,530 at 4, 26,183 at 8, 26,555 at 1
#: (docs/mfu_experiments.md H5, H11)
LANE_VMAP_WIDTH = 2
#: a convolution kernel with fewer output channels leaves MXU columns idle
_MXU_COLUMNS = 128


def lane_vmap_width(variables, n_lanes: int) -> int:
    """How many of ``n_lanes`` lanes :func:`make_lanes_train`'s per-lane
    form vmaps together: :data:`LANE_VMAP_WIDTH` when the model has
    convolution kernels narrower than the MXU (a 4-D parameter leaf with
    fewer than 128 output channels) and the lanes split evenly into more
    than one such chunk, else all of them. Dense-only models (lr, the
    RNNs) gain from the wider batched matmul and keep the full vmap.
    ``variables``: the model's variables, arrays or shapes."""
    w = LANE_VMAP_WIDTH
    if n_lanes <= w or n_lanes % w:
        return n_lanes
    narrow = any(len(p.shape) == 4 and p.shape[-1] < _MXU_COLUMNS
                 for p in jax.tree.leaves(variables["params"]))
    return w if narrow else n_lanes


def make_lanes_train(
    bundle: ModelBundle,
    task: Task,
    n_pad: int,
    **lane_kwargs,
) -> Callable:
    """The all-lanes program both packed round builders share: ``vmap``
    of :func:`make_lane_train` over the lane axis (XLA lowers the
    batched-kernel convs to a grouped conv, docs/mfu_experiments.md H4),
    :func:`lane_vmap_width` lanes at a time, the chunks one after another
    in one ``lax.map``, each as far as its own last live step
    (:func:`chunk_bounds`), and ONE lane with no lane axis at all, which
    alone can branch at its client boundaries (``lane_train``'s ``branch``:
    chosen here, from the lanes' count)."""
    lane_train = make_lane_train(bundle, task, n_pad, **lane_kwargs)
    vmapped = jax.vmap(lane_train, in_axes=(None,) * 5 + (0,) * 10 + (None,))
    unroll = lane_kwargs.get("scan_unroll", 1)

    def lanes_train(variables0, x_flat, y_flat, m_flat, mask_rows, *per_lane):
        L = per_lane[-1].shape[0]
        shared = (variables0, x_flat, y_flat, m_flat, mask_rows)

        def bound(lanes):
            # of the lanes that advance together, from their ``live`` rows
            # (the last per-lane array) and outside their vmap
            return chunk_bounds(lanes[-1], lanes[-1].shape[0], unroll)[0]

        if L == 1:
            # one lane needs no lane axis inside its program, so its step
            # flags are scalars and it can branch on them; the results get
            # the axis back
            return jax.tree.map(
                lambda a: a[None],
                lane_train(*shared, *(a[0] for a in per_lane),
                           bound(per_lane), branch=True))
        w = lane_vmap_width(variables0, L)
        if w == L:
            return vmapped(*shared, *per_lane, bound(per_lane))
        # same lanes, same steps, same order within a lane: only how many
        # lanes one convolution groups changes. The shared arguments are
        # closed over (loop constants), each chunk runs its own loop and
        # stops at its own last live step
        with jax.named_scope(SCOPE_STEP):
            return map_chunks(
                lambda *chunk: vmapped(*shared, *chunk, bound(chunk)),
                per_lane, w)

    return lanes_train


def make_packed_cohort_train(
    bundle: ModelBundle,
    task: Task,
    n_pad: int,
    shape_key: tuple,
    *,
    compute_dtype=None,
    key_slice: Optional[tuple] = None,
    **lane_kwargs,
) -> Callable:
    """Build the packed-cohort program (simulation paradigm) for one plan
    SHAPE: vmap of the lane program over all lanes.

    ``key_slice=(cohort_total, start)`` derives per-position keys as
    ``split(rng, cohort_total)[start:start + len(rows)]`` instead of
    ``split(rng, len(rows))`` — the streamed sub-cohort chunks (fedsched)
    use it so every client consumes the SAME per-round key it would under
    the whole-cohort program, keeping the canonical-replay contract intact
    across chunk boundaries.

    Returns ``packed_train(variables, tx, ty, tm, sampled_rows, weights_pos,
    rng, plan_arrays) -> (acc_vars, acc_w, acc_loss, acc_tau, extras)``
    summed over all lanes. Aggregate = ``acc_vars / acc_w``
    (elastic-guarded by the caller); ``extras`` is the summed
    ``reduce_extras`` partial tree ({} when the hook is absent) — the sim
    paradigm's counterpart of the mesh psum tail, so the full cross-silo
    hook contract (FedOpt/FedNova/AGC/robust) rides the packed schedule in
    BOTH paradigms."""
    del shape_key  # lane count and shapes come in via the arrays
    lanes_fn = make_lanes_train(bundle, task, n_pad, **lane_kwargs)

    def packed_train(variables, tx, ty, tm, sampled_rows, weights_pos, rng,
                     plan_arrays):
        """``tx/ty/tm``: the full stacked client arrays [C_total, n_pad, ...]
        (device-resident); ``sampled_rows`` [cohort] maps cohort position ->
        stack row; ``weights_pos`` [cohort] aggregation weights (count x
        live) by position; ``rng`` the round key (per-position keys derive
        exactly as in the unpacked paths: split(rng, cohort)[position])."""
        (slot, epoch_a, sie, reset, emit, live,
         member_pos, member_valid, steps_real) = plan_arrays
        with jax.named_scope(SCOPE_PROLOGUE):
            if (compute_dtype is not None
                    and jnp.issubdtype(tx.dtype, jnp.floating)):
                tx = tx.astype(compute_dtype)
            C = tx.shape[0]
            x_flat = tx.reshape((C * n_pad,) + tx.shape[2:])
            y_flat = ty.reshape((C * n_pad,) + ty.shape[2:])
            m_flat = tm.reshape((C * n_pad,))
            if key_slice is None:
                keys_full = jax.random.split(rng, sampled_rows.shape[0])
            else:
                total, start = key_slice
                keys_full = jax.random.split(rng, total)[
                    start:start + sampled_rows.shape[0]]
            member_row = sampled_rows[member_pos]      # [n_lanes, k_max]
            member_keys = keys_full[member_pos]
            member_w = weights_pos[member_pos] * member_valid

        lanes = lanes_fn(variables, x_flat, y_flat, m_flat, tm,
                         member_row, member_keys, member_w, steps_real,
                         slot, epoch_a, sie, reset, emit, live)
        lens_out = None
        if len(lanes) == 6:                          # fedlens member stacks
            lens_out = lanes[5]
            lanes = lanes[:5]
        acc_vars, acc_w, acc_loss, acc_tau, extras = lanes
        # extras: [L] stacked; sum(axis=0) reduces them to the cohort
        # partial sums the server_update hook consumes
        with jax.named_scope(SCOPE_AGGREGATE):
            out = (jax.tree.map(lambda a: jnp.sum(a, axis=0), acc_vars),
                   jnp.sum(acc_w), jnp.sum(acc_loss), jnp.sum(acc_tau),
                   jax.tree.map(lambda e: jnp.sum(e, axis=0), extras))
        if lens_out is not None:
            # per-member stacks stay UNsummed ([L, k_max, ...], member_pos
            # order) + the matching member weights for the alignment basis
            out = out + (lens_out + (member_w,),)
        return out

    return packed_train


# --- masked lane freeze/exit (packed Silo early stopping) -------------------

def plan_arrays_tuple(plan: PackPlan) -> tuple:
    """The 9-array runtime tuple every packed round program takes, in the
    one canonical order (slot, epoch, sie, reset, emit, live, member_pos,
    member_valid, steps_real)."""
    return (plan.slot, plan.epoch, plan.sie, plan.reset, plan.emit,
            plan.live, plan.member_pos, plan.member_valid, plan.steps_real)


def masked_plan(plan: PackPlan, member_active: np.ndarray) -> PackPlan:
    """The plan masked for per-client lane EXIT (Silo early stopping):
    a member whose ``member_active[lane, k]`` is 0 becomes a STRUCTURAL
    no-op — its steps run with ``live = 0`` (params/opt/stats frozen by
    the existing dead-step masks), its ``emit``/``member_valid`` zero out
    so it contributes nothing to the weighted aggregate, and ``reset`` is
    suppressed so the lane carries frozen state through the dead span to
    the next active member's reset. Shapes are UNCHANGED — the same
    compiled program executes, no recompile, no vmap fallback. A dead span
    in the MIDDLE of a lane is still walked (the next live member lies
    behind it; a re-pack would reclaim it at one recompile per exit wave);
    one at a lane's tail is not, once every lane vmapped with it has ended
    too (:func:`chunk_bounds` reads the masked ``live``).

    ``member_active``: [n_lanes, k_max] {0,1} per plan member."""
    act_m = np.asarray(member_active, np.float32)
    # each step's activity = its owning member's activity (dead lane-tail
    # steps index slot 0 but already carry live == 0, so the product below
    # cannot resurrect or kill them incorrectly)
    step_act = np.take_along_axis(act_m, plan.slot.astype(np.int64), axis=1)
    return plan._replace(
        reset=(plan.reset * step_act).astype(plan.reset.dtype),
        emit=(plan.emit * step_act).astype(plan.emit.dtype),
        live=(plan.live * step_act).astype(plan.live.dtype),
        member_valid=(plan.member_valid * act_m).astype(
            plan.member_valid.dtype))


def mesh_member_active(plan: PackPlan, n_devices: int,
                       active_perm: np.ndarray) -> np.ndarray:
    """Per-(lane, member) activity for the MESH plan, whose ``member_pos``
    index LOCAL rows within each device's client block and whose lane axis
    is device-major [D * lanes_dev]. ``active_perm``: per-client {0,1} in
    plan (device-major perm) order."""
    ap = np.asarray(active_perm, np.float32)
    D = int(n_devices)
    rows = ap.reshape(D, -1)                       # [D, clients_per_device]
    lanes_dev = plan.n_lanes // D
    dev = np.repeat(np.arange(D), lanes_dev)       # lane -> device
    return rows[dev[:, None], plan.member_pos.astype(np.int64)]


# --- cross-silo mesh form ---------------------------------------------------

def pad_plan(plan: PackPlan, T: int, k_max: int, n_lanes: int) -> PackPlan:
    """Pad a plan to shared (n_lanes, k_max, T) so per-device plans form one
    SPMD-uniform program (extra steps/members/lanes are dead: live 0,
    member_valid 0)."""

    def pad2(a, rows, cols, fill=0):
        out = np.full((rows, cols), fill, a.dtype)
        out[: a.shape[0], : a.shape[1]] = a
        return out

    return PackPlan(
        n_lanes, k_max, T, plan.epochs,
        pad2(plan.slot, n_lanes, T), pad2(plan.epoch, n_lanes, T),
        pad2(plan.sie, n_lanes, T), pad2(plan.reset, n_lanes, T),
        pad2(plan.emit, n_lanes, T), pad2(plan.live, n_lanes, T),
        pad2(plan.member_pos, n_lanes, k_max),
        pad2(plan.member_valid, n_lanes, k_max),
        pad2(plan.steps_real, n_lanes, k_max, fill=1),
    )


def plan_packing_mesh(counts: np.ndarray, batch_size: int, epochs: int,
                      n_devices: int, lanes_per_device: int,
                      t_quantum: int = 1):
    """Mesh packing: deal clients to devices by capacity-constrained LPT
    (biggest client first to the least-loaded device with a free row — see
    the inline comment for why this beats a count-sorted strip deal
    here), pack each device's clients into its own lanes, and pad
    every per-device plan to shared shapes (SPMD: one program, all
    devices).

    Returns ``(perm, plan)`` or None: ``perm`` is the device-major client
    order for data placement (device d's block = perm[d*L:(d+1)*L]); the
    plan's lane axis is device-major [D*lanes_dev, ...] to be sharded along
    the mesh axis; ``member_pos`` index LOCAL rows within a device block.
    """
    counts = np.asarray(counts, np.float64)
    C = len(counts)
    D = int(n_devices)
    if C % D or C // D < 1:
        return None
    L = C // D
    # capacity-constrained LPT: biggest client first, to the least-loaded
    # device that still has a free row — the whale client's device gets the
    # smallest co-residents, so T (= max device load = the round's critical
    # path) approaches the whale bound instead of stacking big clients
    # together the way a count-sorted strip deal does
    cost = epochs * np.ceil(np.maximum(counts, 0.0) / batch_size)
    order = np.argsort(-cost, kind="stable")
    loads = np.zeros(D)
    dev_clients = [[] for _ in range(D)]
    for j in order:
        free = [d for d in range(D) if len(dev_clients[d]) < L]
        d = min(free, key=lambda i: loads[i])
        dev_clients[d].append(int(j))
        loads[d] += cost[j]
    dev_clients = [np.asarray(m, np.int64) for m in dev_clients]
    plans = []
    for d in range(D):
        p = plan_packing(counts[dev_clients[d]], batch_size, epochs,
                         lanes_per_device, t_quantum=t_quantum)
        if p is None:
            return None
        plans.append(p)
    T = max(p.T for p in plans)
    k_max = max(p.k_max for p in plans)
    n_lanes_dev = max(p.n_lanes for p in plans)
    plans = [pad_plan(p, T, k_max, n_lanes_dev) for p in plans]

    def cat(field):
        return np.concatenate([getattr(p, field) for p in plans], axis=0)

    plan = PackPlan(
        D * n_lanes_dev, k_max, T, epochs,
        cat("slot"), cat("epoch"), cat("sie"), cat("reset"), cat("emit"),
        cat("live"), cat("member_pos"), cat("member_valid"), cat("steps_real"),
    )
    return np.concatenate(dev_clients), plan


def make_crosssilo_packed_round(
    bundle: ModelBundle,
    task: Task,
    n_pad: int,
    mesh,
    axis: str = "clients",
    *,
    compute_dtype=None,
    client_transform: Optional[Callable] = None,
    reduce_extras: Optional[Callable] = None,
    server_update: Optional[Callable] = None,
    **lane_kwargs,
) -> Callable:
    """Mesh form of the packed schedule: each device runs its lanes (vmap of
    the SAME lane program the simulation paradigm uses), and ONE weighted
    psum tail aggregates all lanes' accumulators — the packed counterpart of
    `make_crosssilo_round`, with the cohort-max padding replaced by
    one-batch-granularity lanes.

    The three hooks are the cross-silo contract (make_crosssilo_round):
    client_transform / reduce_extras apply per client at lane emit;
    server_update runs post-psum on replicated values — so the whole
    algorithm zoo (FedOpt/FedNova/AGC/robust) rides the packed schedule.

    Returns ``round_fn(variables, server_state, tx, ty, tm, weights, perm,
    rng, plan_arrays) -> (variables, server_state, loss)`` where
    tx/ty/tm/weights are stacked in PLAN ORDER (device-major perm from
    `plan_packing_mesh`) and sharded along ``axis``, plan_arrays are the
    PackPlan arrays (lane axis sharded along ``axis``), and
    variables/server_state/rng are replicated.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from fedml_tpu.parallel.crosssilo import apply_server_and_rollback

    lanes_fn = make_lanes_train(bundle, task, n_pad,
                                client_transform=client_transform,
                                reduce_extras=reduce_extras, **lane_kwargs)

    def shard_fn(variables, server_state, tx, ty, tm, weights, keys,
                 plan_arrays, rng):
        (slot, epoch_a, sie, reset, emit, live,
         member_pos, member_valid, steps_real) = plan_arrays
        variables0 = variables
        with jax.named_scope(SCOPE_PROLOGUE):
            variables = jax.tree.map(
                lambda x: jax.lax.pcast(x, axis_name=axis, to="varying"),
                variables)
            L = tx.shape[0]
            x_flat = tx.reshape((L * n_pad,) + tx.shape[2:])
            y_flat = ty.reshape((L * n_pad,) + ty.shape[2:])
            m_flat = tm.reshape((L * n_pad,))
            member_keys = keys[member_pos]
            member_w = weights[member_pos] * member_valid

        acc_vars, acc_w, acc_loss, _tau, acc_extras = lanes_fn(
            variables, x_flat, y_flat, m_flat, tm,
            member_pos, member_keys, member_w, steps_real,
            slot, epoch_a, sie, reset, emit, live)

        # the psum tail: here fedml.aggregate holds the collectives
        with jax.named_scope(SCOPE_AGGREGATE):
            acc_vars = jax.tree.map(
                lambda a: jax.lax.psum(jnp.sum(a, axis=0), axis), acc_vars)
            total = jax.lax.psum(jnp.sum(acc_w), axis)
            loss_sum = jax.lax.psum(jnp.sum(acc_loss), axis)
            denom = jnp.maximum(total, 1e-12)
            agg = jax.tree.map(
                lambda a, v: (a / denom).astype(v.dtype), acc_vars, variables0)
            extras = None
            if reduce_extras is not None:
                extras = jax.tree.map(
                    lambda e: jax.lax.psum(jnp.sum(e, axis=0), axis),
                    acc_extras)
        new_vars, new_state = apply_server_and_rollback(
            variables0, agg, extras, total, server_state, rng, server_update)
        with jax.named_scope(SCOPE_AGGREGATE):
            return new_vars, new_state, loss_sum / denom

    p_plan = tuple(P(axis) for _ in range(9))
    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis), P(axis),
                  p_plan, P()),
        out_specs=(P(), P(), P()),
    )

    def round_fn(variables, server_state, tx, ty, tm, weights, perm, rng,
                 plan_arrays):
        """``perm``: the device-major client order from plan_packing_mesh —
        every client keeps the per-round key of its ORIGINAL index (same
        rule as the grouped mesh schedule), so the packing changes only the
        padding, never which randomness a client consumes."""
        with jax.named_scope(SCOPE_PROLOGUE):
            if (compute_dtype is not None
                    and jnp.issubdtype(tx.dtype, jnp.floating)):
                tx = tx.astype(compute_dtype)
            keys = jax.random.split(rng, weights.shape[0])[perm]
        return mapped(variables, server_state, tx, ty, tm, weights, keys,
                      plan_arrays, rng)

    return jax.jit(round_fn)
