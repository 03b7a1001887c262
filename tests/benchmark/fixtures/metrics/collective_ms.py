"""Aggregation: device time in cross-chip collective operations
(all-reduce and kin) per round, largest over the chips."""


def read(ctx):
    t = ctx["trace"]
    n = len(ctx["window"].rounds)
    if not t or not n or t["collective_s"] is None:
        return None
    return t["collective_s"] / n * 1e3
