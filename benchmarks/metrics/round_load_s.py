"""Compile: seconds of the round programs' backend compile, or of the read of
their executables from the persistent cache: ``fedml/build/load`` records
under a ``first_call`` record.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "round_load_s")
