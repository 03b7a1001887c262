"""Kernels: device self time under ``fedml.lm.kda`` (the delta rule's
chunked scan: intra-chunk products, the triangular solve, the state's
recurrence and the output, forward, recomputed forward and backward), ms a
round."""

from benchmarks.trace import hybrid_scopes


def read(ctx):
    return hybrid_scopes.part_ms(ctx, "kda")
