"""Compile: seconds the round programs took from jaxpr to MLIR: the compiler's
``fedml/build/lower`` records under a ``first_call`` record.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "round_lower_s")
