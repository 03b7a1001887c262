"""Compile: seconds JAX spent tracing, lowering and compiling (or reading
executables back from the persistent cache) during set-up."""


def read(ctx):
    e = ctx["compile_events"]
    return e["trace_secs"] + e["lower_secs"] + e["compile_secs"]
