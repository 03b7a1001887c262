"""Task families: loss + metric functions, mask-aware.

The reference couples task logic to trainers — one MyModelTrainer subclass
per family (classification / next-word-prediction / tag-prediction,
fedml_api/standalone/fedavg/my_model_trainer_*.py) plus the segmentation
Evaluator (fedseg/utils.py:62-70). Here a task is a pair of pure functions
``loss(logits, targets, mask)`` and ``metrics(logits, targets, mask)``, so
one jitted trainer serves every family.

Masks make ragged client datasets static-shaped for XLA: padded records
carry mask 0 and contribute nothing to loss or metrics.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.obs.tracer import SCOPE_LM_LOSS


class Task(NamedTuple):
    """loss returns a scalar; metrics returns a dict of SUMS plus 'count' so
    results aggregate correctly across batches and clients."""

    loss: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
    metrics: Callable[[jax.Array, jax.Array, jax.Array], dict]


def _masked_mean(values: jax.Array, mask: jax.Array) -> jax.Array:
    m = mask.astype(values.dtype)
    return jnp.sum(values * m) / jnp.maximum(jnp.sum(m), 1.0)


def int_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example softmax CE with integer labels. (Hand-rolled: optax's
    version chex-asserts on tracer dtypes, which trips under vmap+grad with
    numpy 2.)"""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logz, labels[..., None].astype(jnp.int32), axis=-1)
    return -gold[..., 0]


def binary_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Numerically stable elementwise sigmoid BCE."""
    l = logits.astype(jnp.float32)
    t = targets.astype(jnp.float32)
    return jnp.maximum(l, 0.0) - l * t + jnp.log1p(jnp.exp(-jnp.abs(l)))


# --- classification (MyModelTrainerCLS counterpart) -------------------------

def classification_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    per = int_cross_entropy(logits, targets)
    return _masked_mean(per, mask)


def classification_metrics(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> dict:
    m = mask.astype(jnp.float32)
    pred = jnp.argmax(logits, axis=-1)
    correct = jnp.sum((pred == targets).astype(jnp.float32) * m)
    per = int_cross_entropy(logits, targets)
    return {
        "correct": correct,
        "loss_sum": jnp.sum(per * m),
        "count": jnp.sum(m),
    }


classification = Task(classification_loss, classification_metrics)


# --- next-word / next-char prediction (MyModelTrainerNWP counterpart) -------
# logits [B, T, V], targets [B, T]; mask may be [B] (whole sequence) or [B, T].

def _seq_mask(mask: jax.Array, targets: jax.Array) -> jax.Array:
    if mask.ndim < targets.ndim:
        mask = jnp.broadcast_to(mask[..., None], targets.shape)
    return mask


def nwp_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """The gold logit is picked by a one-hot product, not a gather: a
    gather's transpose is a scatter of one element per token, which a TPU
    serialises (8,192 tokens a step in the LM cell)."""
    with jax.named_scope(SCOPE_LM_LOSS):
        m = _seq_mask(mask, targets)
        lf = logits.astype(jnp.float32)
        hot = targets[..., None] == jnp.arange(lf.shape[-1])
        per = (jax.nn.logsumexp(lf, axis=-1)
               - jnp.sum(jnp.where(hot, lf, 0.0), axis=-1))
        return _masked_mean(per, m)


def nwp_metrics(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> dict:
    m = _seq_mask(mask, targets).astype(jnp.float32)
    pred = jnp.argmax(logits, axis=-1)
    per = int_cross_entropy(logits, targets)
    return {
        "correct": jnp.sum((pred == targets).astype(jnp.float32) * m),
        "loss_sum": jnp.sum(per * m),
        "count": jnp.sum(m),
    }


nwp = Task(nwp_loss, nwp_metrics)


# --- multilabel tag prediction (MyModelTrainerTAG counterpart; the reference
# tracks precision/recall for stackoverflow_lr, my_model_trainer.py:61-105) --

def tag_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    per = jnp.sum(binary_cross_entropy(logits, targets), axis=-1)
    return _masked_mean(per, mask)


def tag_metrics(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> dict:
    m = mask.astype(jnp.float32)[:, None]
    pred = (jax.nn.sigmoid(logits) > 0.5).astype(jnp.float32)
    tgt = targets.astype(jnp.float32)
    tp = jnp.sum(pred * tgt * m)
    fp = jnp.sum(pred * (1 - tgt) * m)
    fn = jnp.sum((1 - pred) * tgt * m)
    per = jnp.sum(binary_cross_entropy(logits, targets), axis=-1)
    return {
        "true_pos": tp,
        "false_pos": fp,
        "false_neg": fn,
        "loss_sum": jnp.sum(per * mask.astype(jnp.float32)),
        "count": jnp.sum(mask.astype(jnp.float32)),
    }


tag_prediction = Task(tag_loss, tag_metrics)


# --- semantic segmentation (FedSeg Evaluator counterpart:
# pixel acc / mIoU / FWIoU from a confusion matrix, fedseg/utils.py) ---------

def make_segmentation_task(num_classes: int, ignore_index: int = 255) -> Task:
    def seg_loss(logits, targets, mask):
        # logits [B, H, W, C], targets [B, H, W]
        valid = (targets != ignore_index) & (mask.reshape(mask.shape + (1,) * (targets.ndim - mask.ndim)) > 0)
        tgt = jnp.where(valid, targets, 0)
        per = int_cross_entropy(logits, tgt)
        return _masked_mean(per, valid)

    def seg_metrics(logits, targets, mask):
        valid = (targets != ignore_index) & (mask.reshape(mask.shape + (1,) * (targets.ndim - mask.ndim)) > 0)
        pred = jnp.argmax(logits, axis=-1)
        tgt = jnp.where(valid, targets, 0)
        idx = tgt * num_classes + pred
        # int32 accumulation: float32 stalls at 2^24, which a single large
        # eval pool's background cell can exceed; int32 is exact to 2.1e9
        conf = jnp.bincount(
            idx.reshape(-1), weights=valid.reshape(-1).astype(jnp.int32),
            length=num_classes * num_classes,
        ).reshape(num_classes, num_classes)
        return {"confusion": conf, "count": jnp.sum(valid.astype(jnp.int32))}

    return Task(seg_loss, seg_metrics)


def segmentation_scores(confusion) -> dict:
    """Derive Acc / Acc_class / mIoU / FWIoU from an accumulated confusion
    matrix (reference Evaluator in fedseg/utils.py). Host-side finalizer:
    numpy float64, since jnp silently truncates to f32 without x64 mode."""
    import numpy as np

    conf = np.asarray(confusion, np.float64)
    total = max(conf.sum(), 1.0)
    diag = np.diag(conf)
    rows = conf.sum(axis=1)
    cols = conf.sum(axis=0)
    acc = diag.sum() / total
    with np.errstate(invalid="ignore"):
        acc_class = np.nanmean(np.where(rows > 0, diag / np.maximum(rows, 1.0), np.nan))
        union = rows + cols - diag
        iou = np.where(union > 0, diag / np.maximum(union, 1.0), np.nan)
        miou = np.nanmean(iou)
    freq = rows / total
    fwiou = np.nansum(np.where(union > 0, freq * diag / np.maximum(union, 1.0), 0.0))
    return {"Acc": acc, "Acc_class": acc_class, "mIoU": miou, "FWIoU": fwiou}


TASKS: dict[str, Task] = {
    "classification": classification,
    "nwp": nwp,
    "tag_prediction": tag_prediction,
}


def get_task(name: str, class_num: Optional[int] = None) -> Task:
    """'segmentation' is parameterized by class count (its metrics carry a
    [C, C] confusion matrix), so it is built on demand rather than looked up."""
    if name == "segmentation":
        if not class_num:
            raise ValueError("segmentation task requires class_num")
        return make_segmentation_task(class_num)
    if name not in TASKS:
        raise KeyError(f"unknown task {name!r}; known: {sorted(TASKS) + ['segmentation']}")
    return TASKS[name]
