"""fedml_tpu — a TPU-native federated learning framework.

A from-scratch reimplementation of the capabilities of FedML
(reference: /root/reference, arXiv:2007.13518) designed for TPU hardware:

- models are pure-functional flax modules (pytrees of params instead of
  ``nn.Module.state_dict()``),
- per-client local training is a jit-compiled ``lax.scan`` over batches
  instead of a Python epoch/batch loop,
- the standalone simulator runs clients with ``vmap`` on one chip,
- the cross-silo distributed paradigm shards clients over a
  ``jax.sharding.Mesh`` with ``shard_map`` and aggregates with a weighted
  ``psum`` over ICI, replacing the reference's MPI/gRPC/MQTT state-dict
  message passing (reference fedml_core/distributed/communication/),
- a Message/Observer gRPC edge transport is kept only for genuinely
  off-pod (mobile / external silo) clients.

Layer map (mirrors SURVEY.md §1):

    experiments/   entry points (argparse mains, --ci fast path)
    algorithms/    FL algorithm zoo (FedAvg .. FedNAS)
    models/ data/  model zoo + federated data layer
    parallel/      mesh, sim (vmap), cross-silo (shard_map) paradigms
    distributed/   node runtimes + topology (edge federation)
    comm/          Message, Observer, backends (in-proc, gRPC, MQTT)
    core/          pytree aggregation, partitioners, config, serialization
"""

__version__ = "0.1.0"

from fedml_tpu.core import aggregation, partition, pytree  # noqa: F401
