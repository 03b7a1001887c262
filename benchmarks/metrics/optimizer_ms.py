"""Local training program: device self time under ``fedml.step.opt`` (the
client optimizer's update and its application), ms a round."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "optimizer")
