"""Kernels: device self time under ``fedml.lm.dense`` (attention projections,
shared experts, dense MLP, head: XLA's own matmuls and what it fuses
behind them), ms a round."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "dense")
