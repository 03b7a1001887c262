"""FedProx — local proximal regularization (Li et al. 2018).

The reference ADVERTISES FedProx (fedml_api/distributed/fedprox/) but its
trainer is byte-identical to FedAvg's — the proximal term was never
implemented (verified in SURVEY.md §2.2: MyModelTrainer.py:18-48 is plain
SGD/Adam). This implementation adds the real term: each local step minimizes

    F_k(w) + (mu/2) ||w - w_global||^2

which is exactly the ``prox_mu`` hook of the shared local trainer
(fedml_tpu/parallel/local.py) — the gradient gains mu*(w - w_global).
Aggregation is unchanged FedAvg.
"""

from __future__ import annotations

from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI, FedAvgAPI


class FedProxAPI(FedAvgAPI):
    def _local_train_kwargs(self) -> dict:
        # inject via the shared kwargs mapping (not build_local_train) so
        # EVERY trainer form — the vmapped cohort and the packed lanes —
        # carries the proximal term
        return dict(super()._local_train_kwargs(),
                    prox_mu=self.config.fedprox_mu)


class CrossSiloFedProxAPI(CrossSiloFedAvgAPI, FedProxAPI):
    """FedProx on the cross-silo mesh path: the proximal term is entirely
    client-side (build_local_train), aggregation is plain weighted psum —
    the MRO composes the two with no extra code (the reference would run
    this as its fedprox MPI deployment, which is FedAvg's)."""
