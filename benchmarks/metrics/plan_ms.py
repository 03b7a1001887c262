"""Round driver: host self time of the program's ``fedml/round/plan`` spans
(cohort sampling, the lane plan, its arrays and weights), ms a round over
the traced rounds. None on a program or a run without the spans."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.host_span_ms(ctx, "fedml/round/plan")
