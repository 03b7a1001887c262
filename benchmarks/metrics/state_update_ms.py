"""Local training program: every pass over the parameter tree, ms a round:
``fedml.step.reset`` (select against the global), ``fedml.step.opt``,
``fedml.step.emit`` (dead-step blend, running sum) and ``fedml.aggregate`` /
``fedml.server``."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "state_update")
