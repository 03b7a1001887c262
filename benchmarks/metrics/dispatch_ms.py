"""Round driver: host milliseconds inside ``run_round`` per round (the
benchmark's own span around the call), mean over the window's rounds."""


def read(ctx):
    rounds = ctx["window"].rounds
    if not rounds:
        return None
    return sum(d1 - d0 for _r, d0, d1, _t in rounds) / len(rounds) * 1e3
