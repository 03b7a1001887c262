"""Kernels: device self time under ``fedml.lm.attn`` (scores, softmax,
values: the attention kernels forward and backward, and the recomputed
forward), ms a round."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "attn")
