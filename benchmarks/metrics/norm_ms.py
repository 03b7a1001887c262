"""Local training program: device self time of the normalisation ops inside
``fedml.step.train`` (ops of a ``...Norm`` module that are not fused into a
convolution), ms a round."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "norm")
