"""Silo training harness — the fork's cross-silo workflow.

Counterpart of the fork's silo variants (fedml_api/standalone/fedavg/
silo_fedavg.py:11-162, silo_fedopt.py:13, silo_fednova.py:12,
silo_fedagc.py:31) and fedml_core/instances/ (Client with trn/val/tst splits
and history, client.py:6-83): all clients participate every round, validation
drives early stopping, the best model is saved, and per-client + GLOBAL
histories are recorded with a pluggable ``history_save_fn``.

Implemented as a harness over ANY algorithm API (FedAvg/FedOpt/FedNova/
FedAGC/...), since the fork's four silo classes differ only in aggregation.
"""

from __future__ import annotations

import logging
import os
from collections import defaultdict
from typing import Callable, Optional, Type

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data import FedDataset
from fedml_tpu.parallel.local import finalize_metrics
from fedml_tpu.utils.checkpoint import save_checkpoint

log = logging.getLogger(__name__)


class SiloRunner:
    """Early-stopping round loop around an algorithm API.

    Two stopping scopes:

    - GLOBAL (``patience``): the fork's validation-driven stop — training
      ends when the global metric stalls (silo_fedavg.py:87-95).
    - PER-CLIENT (``client_patience``, off by default): a client whose own
      metric stalls EXITS the federation — its aggregation weight zeroes
      on every schedule, and under the packed schedule its lane span
      becomes a structural no-op in the SAME compiled program
      (FedAvgAPI.set_client_active -> parallel/packed.masked_plan):
      masked lane freeze/exit, never a vmap fallback or a recompile.
      Exits take effect from the next round.
    """

    def __init__(
        self,
        dataset: FedDataset,
        config: FedConfig,
        api_cls: Type[FedAvgAPI] = FedAvgAPI,
        bundle=None,
        patience: int = 10,
        min_delta: float = 0.0,
        model_dir: Optional[str] = None,
        history_save_fn: Optional[Callable[[dict], None]] = None,
        client_patience: Optional[int] = None,
        client_min_delta: float = 0.0,
    ):
        # silo mode: every client participates every round (silo_fedavg.py:55)
        config = config.replace(
            client_num_per_round=min(config.client_num_in_total, dataset.num_clients),
            client_num_in_total=min(config.client_num_in_total, dataset.num_clients),
        )
        self.api = api_cls(dataset, config, bundle)
        self.patience = patience
        self.min_delta = min_delta
        self.model_dir = model_dir
        self.history_save_fn = history_save_fn
        self.client_patience = client_patience
        self.client_min_delta = client_min_delta
        n = self.api.dataset.num_clients
        self._client_best = np.full(n, -np.inf)
        self._client_stall = np.zeros(n, np.int64)
        self._client_on = np.ones(n, bool)
        self.history: dict[str, list] = defaultdict(list)
        self.best_metric = -np.inf
        self.best_round = -1

    @staticmethod
    def _validation_metric(m: dict) -> float:
        """Early-stopping metric from an already-computed global eval (the
        fork early-stops on validation accuracy, silo_fedavg.py:87-95); falls
        back to -loss only when accuracy is absent (not when it is 0.0)."""
        acc = m.get("acc")
        if acc is not None:
            return float(acc)
        return -float(m.get("loss", np.inf))

    def _eval_client(self, idx: int) -> dict:
        ds = self.api.dataset
        x, y, mask = ds.train_x[idx], ds.train_y[idx], ds.train_mask[idx]
        sums = self.api._eval(self.api.variables, x, y, mask)
        return finalize_metrics(jax.tree.map(np.asarray, sums))

    def train(self) -> dict:
        cfg = self.api.config
        stall = 0
        for r in range(cfg.comm_round):
            # float() per run_round's contract: under async_rounds the
            # return is an un-synced device scalar, and this history is
            # host data (json-serialized by history_save_fn)
            train_loss = float(self.api.run_round(r))
            gm = self.api.evaluate_global()
            val = self._validation_metric(gm)
            self.history["round"].append(r)
            self.history["GLOBAL/Train/Loss"].append(train_loss)
            self.history["GLOBAL/Test/Acc"].append(gm.get("acc"))
            self.history["GLOBAL/Test/Loss"].append(gm.get("loss"))
            # per-client histories (fork logs Client.<id> metrics,
            # instances/client.py:59-60) + per-client early EXIT
            if r % cfg.frequency_of_the_test == 0:
                exited = False
                for c in range(self.api.dataset.num_clients):
                    if not self._client_on[c]:
                        # exited clients stop costing eval passes too —
                        # None keeps the per-round history lists aligned
                        self.history[f"Client.{c}/Train/Acc"].append(None)
                        continue
                    cm = self._eval_client(c)
                    self.history[f"Client.{c}/Train/Acc"].append(cm.get("acc"))
                    if self.client_patience:
                        cv = self._validation_metric(cm)
                        if cv > self._client_best[c] + self.client_min_delta:
                            self._client_best[c] = cv
                            self._client_stall[c] = 0
                        else:
                            self._client_stall[c] += 1
                            if self._client_stall[c] >= self.client_patience:
                                self._client_on[c] = False
                                exited = True
                                self.history[
                                    f"Client.{c}/stopped_round"].append(r)
                                log.info("client %d early-exits at round %d "
                                         "(best %g)", c, r,
                                         self._client_best[c])
                if exited:
                    if not self._client_on.any():
                        # everyone exited: stop instead of training no-op
                        # (all-zero-weight, elastic-rollback) rounds
                        log.info("all clients early-exited at round %d", r)
                        self.api.set_client_active(None)
                        break
                    self.api.set_client_active(
                        self._client_on.astype(np.float32))

            if val > self.best_metric + self.min_delta:
                self.best_metric, self.best_round, stall = val, r, 0
                if self.model_dir:
                    save_checkpoint(
                        os.path.join(self.model_dir, "model_best.ckpt"),
                        self.api.variables, self.api.server_state, r,
                        extra={"val": val},
                    )
            else:
                stall += 1
                if stall >= self.patience:
                    log.info("early stop at round %d (best %g @ %d)", r, self.best_metric, self.best_round)
                    break
        if self.model_dir:
            save_checkpoint(
                os.path.join(self.model_dir, "model_last.ckpt"),
                self.api.variables, self.api.server_state, r,
            )
        if self.history_save_fn:
            self.history_save_fn(dict(self.history))
        self.history["best_round"] = self.best_round
        self.history["best_metric"] = self.best_metric
        return dict(self.history)


def SiloFedAvg(dataset, config, **kw) -> SiloRunner:
    return SiloRunner(dataset, config, FedAvgAPI, **kw)


def SiloFedOpt(dataset, config, **kw) -> SiloRunner:
    from fedml_tpu.algorithms.fedopt import FedOptAPI

    return SiloRunner(dataset, config, FedOptAPI, **kw)


def SiloFedProx(dataset, config, **kw) -> SiloRunner:
    from fedml_tpu.algorithms.fedprox import FedProxAPI

    return SiloRunner(dataset, config, FedProxAPI, **kw)


def SiloFedNova(dataset, config, **kw) -> SiloRunner:
    from fedml_tpu.algorithms.fednova import FedNovaAPI

    return SiloRunner(dataset, config, FedNovaAPI, **kw)


def SiloFedAGC(dataset, config, **kw) -> SiloRunner:
    from fedml_tpu.algorithms.fedagc import FedAGCAPI

    return SiloRunner(dataset, config, FedAGCAPI, **kw)
