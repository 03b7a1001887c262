"""Per-client local training as one compiled program.

The reference's innermost hot loop is Python: for epoch / for batch /
loss.backward() / optimizer.step() (my_model_trainer_classification.py:19-53),
with a host->device transfer per batch and a .cpu() state-dict copy per client
(:12-14). Here the WHOLE local training run — E epochs of S minibatch steps
with per-epoch reshuffling — is a single jitted ``lax.scan`` program, so one
dispatch trains a client, and ``vmap``/``shard_map`` of the same function
trains a whole cohort.

Supports every trainer variant the algorithms need:
- plain SGD/momentum/Adam (OptRepo counterpart is optax, fedopt/optrepo.py),
- local gradient clipping (reference clips at 1.0, my_model_trainer:40),
- FedProx proximal term mu/2 ||w - w_global||^2 — the term the reference
  advertises but never implements (SURVEY.md §2.2 FedProx WARNING),
- step counting (tau) for FedNova normalized averaging.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.core.pytree import Pytree, tree_dot, tree_sub
from fedml_tpu.core.tasks import Task
from fedml_tpu.models import ModelBundle
from fedml_tpu.obs.tracer import (SCOPE_PROLOGUE, SCOPE_STEP,
                                  SCOPE_STEP_EMIT, SCOPE_STEP_GATHER,
                                  SCOPE_STEP_OPT, SCOPE_STEP_TRAIN)

# Salt folded into each epoch key to derive the per-step batch keys. The
# packed schedule (parallel/packed.py) replays each client's trajectory
# bit-for-bit and must derive the SAME keys — it imports this constant, so
# the two paths cannot silently desynchronize (advisor r4 #1).
EPOCH_KEY_SALT = 0x5BA7


def make_optimizer(
    name: str, lr: float, momentum: float = 0.0, wd: float = 0.0
) -> optax.GradientTransformation:
    """Client optimizer factory; torch semantics (wd folded into the gradient
    before momentum/moments, like torch.optim.SGD/Adam weight_decay). The
    reference resolves optimizers by reflection over torch.optim subclasses
    (fedopt/optrepo.py:11-39); optax names fill that role."""
    chain = []
    if wd:
        chain.append(optax.add_decayed_weights(wd))
    name = name.lower()
    if name == "sgd":
        chain.append(optax.sgd(lr, momentum=momentum if momentum else None))
    elif name == "adam":
        # reference uses amsgrad=True for client Adam (my_model_trainer.py:28-29)
        chain.append(optax.amsgrad(lr))
    elif name == "adamw":
        chain.append(optax.adamw(lr))
    elif name == "adagrad":
        chain.append(optax.adagrad(lr))
    elif name == "yogi":
        chain.append(optax.yogi(lr))
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    return optax.chain(*chain)


def local_train_kwargs(config) -> dict:
    """The ONE config -> make_local_train_fn kwargs mapping. Every consumer
    of make_local_train_fn (the algorithm APIs via
    FedAvgAPI._local_train_kwargs, the edge trainers, the centralized
    baseline) goes through here so a new config knob cannot be silently
    dropped by one call site."""
    return dict(
        optimizer=config.client_optimizer, lr=config.lr,
        momentum=config.momentum, wd=config.wd,
        epochs=config.epochs, batch_size=config.batch_size,
        grad_clip=config.grad_clip,
        compute_dtype=jnp.bfloat16 if config.dtype == "bfloat16" else None,
        scan_unroll=config.scan_unroll,
    )


class LocalResult(NamedTuple):
    variables: dict       # updated model variables (params [+ batch_stats])
    train_loss: jax.Array  # mean loss over the last epoch
    tau: jax.Array         # number of optimizer steps taken (FedNova)
    #: mean loss over the FIRST local epoch (the fedlens loss-delta basis:
    #: first - last > 0 means local training still makes progress). Optional
    #: so existing positional LocalResult(...) constructions keep working;
    #: jit dead-code-eliminates it wherever the lens is off.
    first_loss: Optional[jax.Array] = None


def make_batch_sgd_step(
    bundle: ModelBundle,
    task: Task,
    tx: optax.GradientTransformation,
    *,
    grad_clip: Optional[float] = None,
    prox_mu: float = 0.0,
    compute_dtype=None,
):
    """ONE minibatch SGD step — the single definition of the per-batch
    update both execution forms share: ``make_local_train_fn`` scans it (with
    dead-step freezing around it) and the streaming paradigm
    (algorithms/streaming_fedavg.py) drives it batch-by-batch, so the two
    paths cannot drift apart numerically.

    Returns ``step(variables, opt_state, params0, bx, by, bm, bkey) ->
    (new_variables, new_opt_state, loss)``; ``params0`` anchors the FedProx
    proximal term (ignored when prox_mu == 0).
    """

    def batch_step(variables, opt_state, params0, bx, by, bm, bkey):
        with jax.named_scope(SCOPE_STEP_TRAIN):
            if compute_dtype is not None and jnp.issubdtype(bx.dtype, jnp.floating):
                bx = bx.astype(compute_dtype)

            def loss_fn(p):
                vars_in = dict(variables)
                vars_in["params"] = p
                logits, new_vars = bundle.apply_train(vars_in, bx, bkey)
                l = task.loss(logits, by, bm)
                if prox_mu:
                    d = tree_sub(p, params0)
                    l = l + 0.5 * prox_mu * tree_dot(d, d)
                return l, new_vars

            (l, new_vars), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                variables["params"]
            )
            if grad_clip:
                gnorm = optax.global_norm(grads)
                scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree.map(lambda g: g * scale, grads)
        with jax.named_scope(SCOPE_STEP_OPT):
            updates, new_opt_state = tx.update(grads, opt_state, variables["params"])
            out_vars = dict(new_vars)
            out_vars["params"] = optax.apply_updates(variables["params"], updates)
        return out_vars, new_opt_state, l

    return batch_step


def make_local_train_fn(
    bundle: ModelBundle,
    task: Task,
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
) -> Callable[[dict, jax.Array, jax.Array, jax.Array, jax.Array], LocalResult]:
    """Build ``local_train(variables, x, y, mask, count, rng) -> LocalResult``.

    ``x/y/mask`` are one client's padded arrays [n_pad, ...]; n_pad must be a
    multiple of batch_size (loaders guarantee this); ``count`` is the client's
    REAL record count. Shapes are static, so the function vmaps over a
    stacked client axis and shard_maps over a mesh.

    Faithfulness to the reference's ragged execution under static shapes:
    each epoch shuffles the REAL records to the front, and optimizer steps
    beyond ceil(count/batch_size) are masked out (params and optimizer state
    frozen), so a 10-sample client takes the same number of effective SGD
    steps it would in the reference's Python loop — this is also what makes
    the per-client tau in LocalResult honest for FedNova.
    """
    tx = make_optimizer(optimizer, lr, momentum, wd)
    # x is pre-cast once per client below, so the shared step's own cast is
    # a no-op; prox anchors at the round's incoming params (params0)
    batch_step = make_batch_sgd_step(
        bundle, task, tx, grad_clip=grad_clip, prox_mu=prox_mu,
        compute_dtype=None,
    )

    def local_train(variables: dict, x, y, mask, count, rng) -> LocalResult:
        n_pad = x.shape[0]
        steps = n_pad // batch_size
        with jax.named_scope(SCOPE_PROLOGUE):
            params0 = variables["params"]
            opt_state = tx.init(variables["params"])
            # effective steps/epoch for this client's real data (traced scalar)
            steps_real = jnp.ceil(count.astype(jnp.float32) / batch_size).astype(jnp.int32)

            if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                x_cast = x.astype(compute_dtype)
            else:
                x_cast = x

        def epoch_fn(carry, ekey):
            variables, opt_state = carry
            with jax.named_scope(SCOPE_STEP_GATHER):
                perm = jax.random.permutation(ekey, n_pad)
                # stable-sort shuffled indices so real records come first:
                # batches 0..steps_real-1 are the reference's real
                # minibatches, later batches are pure padding and their
                # steps get masked out.
                order = perm[jnp.argsort(-mask[perm], stable=True)]
                xs = x_cast[order].reshape((steps, batch_size) + x.shape[1:])
                ys = y[order].reshape((steps, batch_size) + y.shape[1:])
                ms = mask[order].reshape((steps, batch_size))
                bkeys = jax.random.split(
                    jax.random.fold_in(ekey, EPOCH_KEY_SALT), steps)

            def step_fn(carry, batch):
                variables, opt_state = carry
                bx, by, bm, bkey, step_idx = batch
                live = (step_idx < steps_real).astype(jnp.float32)
                new_vars, new_opt_state, l = batch_step(
                    variables, opt_state, params0, bx, by, bm, bkey
                )

                # freeze params/opt/stats on dead (padding-only) steps
                def freeze_if_dead(new, old):
                    return jax.tree.map(
                        lambda n, o: live * n + (1.0 - live) * o
                        if jnp.issubdtype(n.dtype, jnp.floating) else jnp.where(live > 0, n, o),
                        new, old,
                    )

                with jax.named_scope(SCOPE_STEP_EMIT):
                    new_opt_state = freeze_if_dead(new_opt_state, opt_state)
                    out_vars = dict(freeze_if_dead(new_vars, variables))
                    return (out_vars, new_opt_state), l * live

            (variables, opt_state), losses = jax.lax.scan(
                step_fn, (variables, opt_state),
                (xs, ys, ms, bkeys, jnp.arange(steps)),
                unroll=max(int(scan_unroll), 1),
            )
            with jax.named_scope(SCOPE_STEP_EMIT):
                mean_loss = jnp.sum(losses) / jnp.maximum(steps_real.astype(jnp.float32), 1.0)
            return (variables, opt_state), mean_loss

        with jax.named_scope(SCOPE_PROLOGUE):
            ekeys = jax.random.split(rng, epochs)
        with jax.named_scope(SCOPE_STEP):
            (variables, opt_state), ep_losses = jax.lax.scan(
                epoch_fn, (variables, opt_state), ekeys
            )
        with jax.named_scope(SCOPE_STEP_EMIT):
            tau = (epochs * steps_real).astype(jnp.float32)
        return LocalResult(variables, ep_losses[-1], tau, ep_losses[0])

    return local_train


def make_eval_fn(bundle: ModelBundle, task: Task, eval_batch_size: int = 256):
    """Build ``evaluate(variables, x, y, mask) -> dict of metric SUMS`` —
    a scan over fixed-size batches, jitted once. Counterpart of the
    reference's trainer.test (my_model_trainer.py:61-105) without the
    per-batch host loop."""

    @jax.jit
    def evaluate(variables, x, y, mask):
        n = x.shape[0]
        bs = min(eval_batch_size, n)
        steps = -(-n // bs)  # ceil: pad the tail rather than dropping it
        pad = steps * bs - n
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            y = jnp.concatenate([y, jnp.zeros((pad,) + y.shape[1:], y.dtype)])
            mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
        xs = x.reshape((steps, bs) + x.shape[1:])
        ys = y.reshape((steps, bs) + y.shape[1:])
        ms = mask.reshape((steps, bs))

        def body(acc, batch):
            bx, by, bm = batch
            logits = bundle.apply_eval(variables, bx)
            m = task.metrics(logits, by, bm)
            if acc is None:
                return m, None
            return jax.tree.map(jnp.add, acc, m), None

        first = jax.tree.map(
            jnp.zeros_like, task.metrics(bundle.apply_eval(variables, xs[0]), ys[0], ms[0])
        )
        acc, _ = jax.lax.scan(lambda a, b: body(a, b), first, (xs, ys, ms))
        return acc

    return evaluate


def finalize_metrics(sums: dict) -> dict:
    """Metric sums -> human metrics (acc, loss, precision/recall; for
    segmentation sums, Acc/mIoU/FWIoU via the confusion matrix)."""
    out = {}
    if "confusion" in sums:
        from fedml_tpu.core.tasks import segmentation_scores

        scores = {k: float(v) for k, v in segmentation_scores(sums["confusion"]).items()}
        scores["acc"] = scores["Acc"]
        scores["loss"] = 1.0 - scores["mIoU"]
        return scores
    count = float(sums.get("count", 1.0))
    if "correct" in sums:
        out["acc"] = float(sums["correct"]) / max(count, 1.0)
    if "loss_sum" in sums:
        out["loss"] = float(sums["loss_sum"]) / max(count, 1.0)
    if "true_pos" in sums:
        tp, fp, fn = (float(sums[k]) for k in ("true_pos", "false_pos", "false_neg"))
        out["precision"] = tp / max(tp + fp, 1.0)
        out["recall"] = tp / max(tp + fn, 1.0)
    return out
