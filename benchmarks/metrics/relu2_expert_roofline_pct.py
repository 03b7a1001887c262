"""Kernels: the least time the chip could take for the executed steps'
grouped matmuls of the two-matrix experts in the latent, over
``expert_mm_ms``, at the rows the held experts computed: rows a token and
SPARSE layer by the program's counter (``sparse_rows_per_token``: the sparse
layers' own steps), which ``benchmarks/flops/nemotron3_super_120b.py:
expert_train_cost_per_sample`` takes as they are. (``expert_mm_roofline_pct``
takes its rows over every layer that counts steps, a state-space layer too,
and is not reported in a cell whose layers are one sub-layer each.)"""

from benchmarks.metrics.sparse_rows_per_token import rows_per_token
from benchmarks.trace import lm_scopes


def read(ctx):
    if lm_scopes.parts_s(ctx) is None:      # no trace, or not this model's
        return None
    got = rows_per_token(ctx)
    if got is None:
        return None
    print(f"relu2_expert_roofline_pct: {got[0]:.6g} rows of held experts a "
          "token and sparse layer by the program's counter (expected from "
          "shapes: top_k * held / n_routed)", flush=True)
    return lm_scopes.roofline_pct(ctx, "experts",
                                  "expert_train_cost_per_sample",
                                  "relu2_expert_roofline_pct",
                                  rows_per_token=got[0])
