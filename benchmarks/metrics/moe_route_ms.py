"""Local training program: device self time under ``fedml.lm.route`` (router
matmul, selection, sort, the rows' fan-out and weighted add-back), ms a
round."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "route")
