"""Centralized (non-FL) baseline trainer over the same federated dataset —
the sanity baseline and the other half of the federated==centralized
equivalence gate (reference fedml_api/centralized/centralized_trainer.py:9-104
and CI-script-fedavg.sh:43-47).

Implementation: the federation's records are merged into ONE logical client
and trained with the same jitted local-train program — so the equivalence
test compares two code paths that share only the math, not the loop.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.rng import round_key, seed_everything
from fedml_tpu.core.tasks import get_task
from fedml_tpu.data import FedDataset
from fedml_tpu.data.batching import pad_to_multiple
from fedml_tpu.models import ModelBundle, create_model
from fedml_tpu.parallel.local import finalize_metrics, make_eval_fn, make_local_train_fn


def merge_clients(dataset: FedDataset, batch_size: int):
    """Flatten the stacked per-client arrays back into one masked pool."""
    C, n_pad = dataset.train_mask.shape
    flat_x = dataset.train_x.reshape((C * n_pad,) + dataset.train_x.shape[2:])
    flat_y = dataset.train_y.reshape((C * n_pad,) + dataset.train_y.shape[2:])
    flat_m = dataset.train_mask.reshape(-1)
    keep = flat_m > 0
    x, y = flat_x[keep], flat_y[keep]
    n = pad_to_multiple(len(x), batch_size)
    pad = n - len(x)
    if pad:
        x = np.concatenate([x, x[:pad]])
        y = np.concatenate([y, y[:pad]])
    m = np.concatenate([np.ones(len(flat_m[keep]), np.float32), np.zeros(pad, np.float32)])
    return x, y, m


class CentralizedTrainer:
    def __init__(self, dataset: FedDataset, config: FedConfig, bundle: ModelBundle | None = None):
        self.dataset = dataset
        self.config = config
        self.bundle = bundle or create_model(
            config.model, dataset.class_num, input_shape=dataset.train_x.shape[2:] or None
        )
        self.task = get_task(dataset.task, dataset.class_num)
        self.root_key = seed_everything(config.seed)
        self.variables = self.bundle.init(self.root_key)
        self.x, self.y, self.mask = merge_clients(dataset, config.batch_size)
        from fedml_tpu.parallel.local import local_train_kwargs

        self._train = jax.jit(make_local_train_fn(
            self.bundle, self.task, **local_train_kwargs(config),
        ))
        self._eval = make_eval_fn(self.bundle, self.task)
        # ship the merged dataset ONCE: jnp.asarray inside the round loop
        # re-transferred the full array every round (600 MB/round at
        # flagship scale)
        from fedml_tpu.utils.dtypes import host_bf16_cast

        self._dev = (jax.device_put(host_bf16_cast(self.x, config.dtype)),
                     jax.device_put(self.y), jax.device_put(self.mask))
        self._count = float(self.mask.sum())
        # the device copies are the working set now; keep only them
        del self.x, self.y

    def train(self) -> dict:
        history = {"round": [], "Test/Acc": [], "Test/Loss": []}
        count = jnp.asarray(self._count)
        dx, dy, dm = self._dev
        for r in range(self.config.comm_round):
            res = self._train(
                self.variables, dx, dy, dm, count,
                round_key(self.root_key, r),
            )
            self.variables = res.variables
            if r % self.config.frequency_of_the_test == 0 or r == self.config.comm_round - 1:
                m = finalize_metrics(jax.tree.map(np.asarray, self._eval(
                    self.variables, self.dataset.test_x, self.dataset.test_y, self.dataset.test_mask
                )))
                history["round"].append(r)
                history["Test/Acc"].append(m.get("acc"))
                history["Test/Loss"].append(m.get("loss"))
        return history


class StreamingCentralizedTrainer:
    """Centralized training for datasets that do NOT fit on device: batches
    are assembled by the native threaded pipeline (fedml_tpu/native) and
    double-buffered onto the device while the previous step computes. One
    jitted per-batch SGD step with donated state; the device never waits on
    the Python interpreter for batch assembly."""

    def __init__(self, dataset: FedDataset, config: FedConfig, bundle: ModelBundle | None = None,
                 n_threads: int = 4, depth: int = 6, mesh=None):
        from fedml_tpu.parallel.local import make_optimizer

        self.dataset = dataset
        self.config = config
        self.mesh = mesh  # optional ('batch',) mesh: batch-sharded DP + sync-BN
        self.bundle = bundle or create_model(
            config.model, dataset.class_num, input_shape=dataset.train_x.shape[2:] or None
        )
        self.task = get_task(dataset.task, dataset.class_num)
        self.root_key = seed_everything(config.seed)
        self.variables = self.bundle.init(self.root_key)
        self.n_threads, self.depth = n_threads, depth
        x, y, mask = merge_clients(dataset, config.batch_size)
        keep = mask > 0
        self.x, self.y = x[keep], y[keep]
        self.tx = make_optimizer(config.client_optimizer, config.lr, config.momentum, config.wd)
        self.opt_state = self.tx.init(self.variables["params"])

        # One step builder for both paths: mesh=None compiles the plain
        # donated single-device step; a ('batch',) mesh adds GSPMD batch
        # sharding + sync-BN + grad all-reduce (nn.DataParallel counterpart,
        # GKTServerTrainer.py:28-29).
        from fedml_tpu.parallel.dataparallel import make_dp_train_step

        dp = make_dp_train_step(self.bundle, self.task, self.tx, self.mesh,
                                grad_clip=config.grad_clip)

        # drop_last=True fixes the batch size, so the all-ones mask is one
        # constant made (and, on a mesh, sharded) once — not per step
        ones_mask = jnp.ones(config.batch_size, jnp.float32)
        if self.mesh is not None:
            from fedml_tpu.parallel.dataparallel import place_batch

            ones_mask = place_batch(self.mesh, ones_mask)

            def step(variables, opt_state, bx, by, key):
                # pipeline batches arrive committed to one device; respread
                bx, by = place_batch(self.mesh, bx, by)
                return dp(variables, opt_state, bx, by, ones_mask, key)
        else:
            def step(variables, opt_state, bx, by, key):
                return dp(variables, opt_state, bx, by, ones_mask, key)

        self._step = step
        self._eval = make_eval_fn(self.bundle, self.task)

    def train(self) -> dict:
        from fedml_tpu.data.pipeline import HostPipeline, device_stream

        history = {"round": [], "Test/Acc": [], "Test/Loss": []}
        x, y = self.x, self.y
        if len(x) < self.config.batch_size:  # tiny sets: repeat to one batch
            reps = -(-self.config.batch_size // len(x))
            x = np.concatenate([x] * reps)[: self.config.batch_size]
            y = np.concatenate([y] * reps)[: self.config.batch_size]
        step_no = 0
        with HostPipeline(x, y, self.config.batch_size, seed=self.config.seed,
                          n_threads=self.n_threads, depth=self.depth,
                          drop_last=True) as pipe:
            for r in range(self.config.comm_round):
                for _ in range(self.config.epochs):
                    for bx, by in device_stream(pipe):
                        self.variables, self.opt_state, _ = self._step(
                            self.variables, self.opt_state, bx, by,
                            round_key(self.root_key, step_no))
                        step_no += 1
                if r % self.config.frequency_of_the_test == 0 or r == self.config.comm_round - 1:
                    m = finalize_metrics(jax.tree.map(np.asarray, self._eval(
                        self.variables, self.dataset.test_x, self.dataset.test_y,
                        self.dataset.test_mask)))
                    history["round"].append(r)
                    history["Test/Acc"].append(m.get("acc"))
                    history["Test/Loss"].append(m.get("loss"))
        return history
