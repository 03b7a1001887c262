"""Local training program: device self time under ``fedml.lm.kda_prep``
(what a linear-attention mixer does around its scan: short convolutions,
SiLU, the norms of q and k, the decay and step gates, the output's norm and
gate), ms a round."""

from benchmarks.trace import hybrid_scopes


def read(ctx):
    return hybrid_scopes.part_ms(ctx, "kda_prep")
