"""Local training program: device self time under ``fedml.lm.ssd_prep``
(what a state-space mixer does around its recurrence: the convolution and
SiLU, the splits and head reshapes, softplus, the gated norm), ms a round."""

from benchmarks.trace import ssd_scopes


def read(ctx):
    return ssd_scopes.part_ms(ctx, "ssd_prep")
