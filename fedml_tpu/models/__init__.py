"""Model zoo registry.

Counterpart of ``fedml_api/model/`` + the ``create_model`` factory embedded in
every reference main (fedml_experiments/distributed/fedavg/main_fedavg.py:232-267).
Models are flax modules; ``create_model(name, ...)`` returns a ``ModelBundle``
with pure init/apply functions so algorithms never touch module objects.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.obs.tracer import SPAN_SETUP_INIT, setup_span

_REGISTRY: dict[str, Callable[..., "ModelBundle"]] = {}


def register_model(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


#: the flax collection of a model's own counts (rows an expert computed,
#: steps): ``apply_train`` updates it like ``batch_stats``, and the packed
#: simulation round (``FedAvgAPI.build_round_step_packed``) SUMS what the
#: round's clients added, where every other leaf of the state is their
#: weighted mean. Any other round form, and an algorithm whose hooks bring
#: extras of their own, averages it with the rest.
COUNTERS = "counters"


@dataclass
class ModelBundle:
    """A model as pure functions over variable pytrees.

    ``variables`` is the full flax collection dict {'params': ..., maybe
    'batch_stats': ..., maybe 'counters': ...}. ``apply_train`` returns
    (logits, new_variables) with mutable collections updated; ``apply_eval``
    is deterministic.
    """

    name: str
    module: nn.Module
    input_shape: tuple          # single-example shape, no batch dim
    input_dtype: Any = jnp.float32
    task: str = "classification"
    has_batch_stats: bool = False
    uses_dropout: bool = False
    #: explicit-key dropout (models/cnn.seed_dropout): apply_train hands
    #: ``rng`` to the module as a ``dropout_rng`` kwarg instead of a flax
    #: rng stream, so a step's masks derive from its batch key alone
    explicit_dropout: bool = False
    #: the single-example shape ``init`` traces with, where parameter shapes
    #: do not depend on it (a sequence model's length) and a forward pass at
    #: ``input_shape`` would be minutes of op-by-op work; such an init is
    #: also jitted, one program instead of one per op
    init_shape: Optional[tuple] = None
    #: ``counters(variables) -> {name: number}``: host numbers read from the
    #: model's :data:`COUNTERS` collection (the sparse layers' rows per
    #: expert), published by the round driver's ``close()`` into the
    #: ``model`` counter group
    counters: Optional[Callable[[dict], dict]] = None

    def init(self, rng: jax.Array, batch_size: int = 2) -> dict:
        shape = self.init_shape or self.input_shape
        x = jnp.zeros((batch_size,) + tuple(shape), self.input_dtype)

        def init(r):
            return self.module.init({"params": r}, x, train=False)

        jitted = self.init_shape is not None
        with setup_span(SPAN_SETUP_INIT, model=self.name, jitted=jitted):
            return (jax.jit(init) if jitted else init)(rng)

    def apply_train(self, variables: dict, x: jax.Array, rng: jax.Array):
        rngs, kwargs = {}, {}
        if self.explicit_dropout:
            kwargs["dropout_rng"] = rng     # raw key(s); module derives masks
        elif self.uses_dropout:
            rngs = {"dropout": rng}
        mutable = (["batch_stats"] if self.has_batch_stats else []) + (
            [COUNTERS] if COUNTERS in variables else [])
        if mutable:
            logits, updated = self.module.apply(
                variables, x, train=True, mutable=mutable, rngs=rngs,
                **kwargs
            )
            new_vars = dict(variables)
            new_vars.update(updated)
            return logits, new_vars
        out = self.module.apply(variables, x, train=True, rngs=rngs, **kwargs)
        return out, variables

    def apply_eval(self, variables: dict, x: jax.Array) -> jax.Array:
        return self.module.apply(variables, x, train=False)


def create_model(model_name: str, output_dim: int, input_shape: Optional[Sequence[int]] = None, **kw) -> ModelBundle:
    """Factory keyed by the reference's --model flag values
    (main_fedavg.py:232-267: lr, cnn, resnet18_gn, rnn, resnet56, mobilenet,
    ...)."""
    # Import lazily so optional model families don't slow cold start.
    from fedml_tpu.models import cnn, linear, mobilenet, moe, resnet, resnet_gn, rnn, segmentation, transformer, vgg  # noqa: F401
    try:
        from fedml_tpu.models import efficientnet  # noqa: F401
    except ImportError:
        pass
    if model_name not in _REGISTRY:
        raise KeyError(f"unknown model {model_name!r}; known: {sorted(_REGISTRY)}")
    bundle = _REGISTRY[model_name](output_dim=output_dim, **kw)
    if input_shape is not None:
        bundle.input_shape = tuple(input_shape)
    return bundle


def known_models() -> list[str]:
    from fedml_tpu.models import cnn, linear, mobilenet, moe, resnet, resnet_gn, rnn, segmentation, transformer, vgg  # noqa: F401
    try:
        from fedml_tpu.models import efficientnet  # noqa: F401
    except ImportError:
        pass
    return sorted(_REGISTRY)
