"""Compile: ``lower`` + ``load`` seconds of the helper programs that
``helper_programs_built`` counts.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "helper_build_s")
