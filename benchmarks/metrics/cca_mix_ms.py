"""Kernels: device self time under ``fedml.lm.cca_mix`` (what compressed
convolutional attention does between its projections and the attention
kernels: the means of q and k, the depthwise and the head-wise convolution,
the normalisation with the key temperature, the value shift; forward,
recomputed forward and backward), ms a round."""

from benchmarks.trace import cca_scopes


def read(ctx):
    return cca_scopes.part_ms(ctx, "cca_mix")
