"""Local training program: device time of the round's jitted module per
round, from the trace's module line (the module that took most time),
largest over the chips."""


def read(ctx):
    t = ctx["trace"]
    n = len(ctx["window"].rounds)
    if not t or not n or not t["modules"]:
        return None
    return t["modules"][0][1] / n * 1e3
