"""Kernels: device self time under ``fedml.lm.ssd`` (the state-space
recurrence in chunks: ``C B^T``, the masked decays, the intra-chunk product,
the scan over chunks and the read-out, forward, recomputed forward and
backward), ms a round."""

from benchmarks.trace import ssd_scopes


def read(ctx):
    return ssd_scopes.part_ms(ctx, "ssd")
