"""Aggregation: device self time under ``fedml.aggregate`` and
``fedml.server`` (sums over lanes or the psum, division, cast back, server
hook, rollback), ms a round."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "aggregate")
