"""Set-up of one cell: the program's objects, built from the cell's and the
configuration's files. Everything a cell is, is a key in its file."""

from __future__ import annotations

import importlib


def build_api(config: dict, cell: dict, dataset):
    """The system under test: the API class the cell names, on the
    configuration's model and recipe, telemetry planes off."""
    import jax.numpy as jnp
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.models import create_model

    recipe, prec = config["recipe"], config["precision"]
    fed = dict(
        model=config["model"]["program_name"], dataset=config["name"],
        batch_size=int(recipe["batch_size"]), epochs=int(recipe["epochs"]),
        client_optimizer=recipe["client_optimizer"], lr=float(recipe["lr"]),
        momentum=float(recipe["momentum"]), dtype=prec["module"],
        client_num_in_total=int(cell["clients"]),
        # sample_clients keys on config.seed: the cohorts belong to the cell
        seed=int(cell["sampling_seed"]),
        # the loop drives run_round itself, far past any schedule
        comm_round=1_000_000_000, frequency_of_the_test=1_000_000_000,
        async_rounds=True)
    fed.update(cell["fed_config"])
    cfg = FedConfig(**fed)
    dtype = jnp.bfloat16 if prec["module"] == "bfloat16" else jnp.float32
    bundle = create_model(cfg.model, dataset.class_num,
                          input_shape=dataset.train_x.shape[2:] or None,
                          dtype=dtype)
    module, _, cls = cell["api"].partition(":")
    api_cls = getattr(importlib.import_module(module), cls)
    return api_cls(dataset, cfg, bundle)


def seed_program(api, ref, config: dict, seed: int) -> dict:
    """Hand the program the run's seeded weights (made by the reference's
    ``init``) and its root key; -> the same weights as a host tree."""
    import jax

    from benchmarks.harness import check, protocol

    root = protocol.run_key(seed)
    init = jax.jit(lambda k: ref.init(k, config))(jax.random.fold_in(root, 0x1417))
    init_host = jax.device_get(init)
    diff = check.same_tree(jax.device_get(api.variables), init_host)
    if diff:
        raise RuntimeError(f"the reference's seeded weights do not fit the "
                           f"program's variable tree at {diff[:5]}")
    mesh = getattr(api, "mesh", None)
    if mesh is not None:
        # a mesh API hands its round replicated variables back; seeded ones
        # that start on one chip would make round 1 a program of its own,
        # traced and compiled for that one call
        init = jax.device_put(init, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    api.variables, api.root_key = init, root
    return init_host
