"""Round driver: seconds inside the round driver's constructor: the program's
``fedml/setup/api`` set-up span (model variables, the local-training
functions, the client stack's placement, the programs' construction).
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "api_init_s")
