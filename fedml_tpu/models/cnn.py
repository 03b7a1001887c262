"""FedAvg-paper CNNs for FEMNIST/MNIST (reference fedml_api/model/cv/cnn.py:5-142).

Two variants, matching the reference capabilities:

- ``cnn`` / CNN_OriginalFedAvg (cnn.py:5-70): 2x[conv5x5 -> maxpool2] ->
  dense(512) -> softmax head, McMahan et al. 2016 table 2 sizing.
- ``cnn_dropout`` / CNN_DropOut (cnn.py:74-142): the TFF baseline flavor with
  3x3 convs and dropout.

NHWC layout (TPU-native; torch reference is NCHW).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.models import ModelBundle, register_model


class CNNOriginalFedAvg(nn.Module):
    output_dim: int = 62
    only_digits: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        if x.ndim == 2:  # flat 784 -> 28x28x1
            x = x.reshape((x.shape[0], 28, 28, 1))
        x = nn.Conv(32, (5, 5), padding="SAME")(x)
        x = nn.max_pool(nn.relu(x), (2, 2), strides=(2, 2))
        x = nn.Conv(64, (5, 5), padding="SAME")(x)
        x = nn.max_pool(nn.relu(x), (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512)(x))
        return nn.Dense(self.output_dim)(x)


#: salt folded (plus the per-model layer index) into the explicit dropout
#: key so distinct dropout layers in one step draw independent masks —
#: the same fold-a-constant derivation the packed replay tables use
#: (parallel/local.EPOCH_KEY_SALT)
DROPOUT_KEY_SALT = 0xD120


def seed_dropout(x, key, rate: float, layer: int, deterministic: bool):
    """Explicit-key dropout: the masks of a step derive from the step's
    batch key alone, so a packed lane replays its client's masks
    bit-for-bit from that key (flax's ``nn.Dropout`` derives its key from
    internal module-path folding). ``layer`` is the call site's static
    index within the model; ``key`` is the step's batch key (models
    receive it as ``dropout_rng``; see ModelBundle.explicit_dropout)."""
    if deterministic or rate <= 0.0:
        return x
    if key is None:
        # same contract as flax's missing-rng error: a train-mode apply
        # without a key must fail loudly, not silently skip regularization
        raise ValueError(
            "seed_dropout: train-mode apply without a dropout key — pass "
            "dropout_rng (ModelBundle.explicit_dropout threads it)")
    k = jax.random.fold_in(key, DROPOUT_KEY_SALT + layer)
    keep = jax.random.bernoulli(k, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


class CNNDropOut(nn.Module):
    """Dropout masks derive from an EXPLICIT key (`dropout_rng`, the step's
    batch key) via :func:`seed_dropout` instead of a flax rng stream
    (ModelBundle.explicit_dropout)."""

    output_dim: int = 62

    @nn.compact
    def __call__(self, x, train: bool = False, dropout_rng=None):
        if x.ndim == 2:
            x = x.reshape((x.shape[0], 28, 28, 1))
        x = nn.relu(nn.Conv(32, (3, 3), padding="VALID")(x))
        x = nn.relu(nn.Conv(64, (3, 3), padding="VALID")(x))
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = seed_dropout(x, dropout_rng, 0.25, 0, not train)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128)(x))
        x = seed_dropout(x, dropout_rng, 0.5, 1, not train)
        return nn.Dense(self.output_dim)(x)


@register_model("cnn")
def _cnn(output_dim: int, **_):
    return ModelBundle(
        name="cnn",
        module=CNNOriginalFedAvg(output_dim),
        input_shape=(28, 28, 1),
    )


@register_model("cnn_dropout")
def _cnn_dropout(output_dim: int, **_):
    return ModelBundle(
        name="cnn_dropout",
        module=CNNDropOut(output_dim),
        input_shape=(28, 28, 1),
        uses_dropout=True,
        explicit_dropout=True,
    )
