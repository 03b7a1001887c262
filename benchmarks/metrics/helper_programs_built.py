"""Compile: the program's own executables built or read back during set-up
OUTSIDE a round program's first call: ``fedml/build/load`` records under a
``fedml/setup/*`` span or asked for by the program's code (an eager op is a
program). A count.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "helper_programs_built")
