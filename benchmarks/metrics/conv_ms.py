"""Kernels: device self time of the convolution ops inside
``fedml.step.train``, forward and backward (XLA fuses the reductions that
follow a convolution into its op: they are in it), ms a round."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "conv")
