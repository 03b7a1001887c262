"""Kernels: device self time under ``fedml.lm.experts`` (the grouped matmuls
over the rows of the experts held here, all passes), ms a round."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "experts")
