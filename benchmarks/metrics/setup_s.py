"""Process start to the first timed round, in seconds: import, data,
placement, trace / lower / compile-or-cache-read, warm-up. The output check
runs after the window and is not part of it."""


def read(ctx):
    return ctx["setup_s"]
