"""Device: share of the busiest chip's busy time in ops that carry no
``fedml.*`` scope: what the six parts of the round program leave out."""

from benchmarks.trace import scopes


def read(ctx):
    red = scopes.reduce_ctx(ctx)
    if red is None or not red["busy_s"]:
        return None
    return 100.0 * red["parts_s"]["unscoped"] / red["busy_s"]
