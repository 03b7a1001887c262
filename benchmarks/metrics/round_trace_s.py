"""Compile: seconds of Python tracing of the round programs: the self time of
the program's ``fedml/round/build`` ``first_call`` records (their seconds less
the compiler's ``lower`` and ``load`` records inside them). No cache skips it.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "round_trace_s")
