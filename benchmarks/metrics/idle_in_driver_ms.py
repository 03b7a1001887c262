"""Round driver: device idle time of the busiest chip that falls inside one
of the program's ``fedml/round`` spans, ms a round over the traced rounds:
the idle time the round driver itself could give back. The rest of the idle
time is the caller blocking or looping. None without the spans."""

from benchmarks.trace import scopes


def read(ctx):
    red = scopes.reduce_ctx(ctx)
    if red is None or scopes.ROUND_SPAN not in red["host_self_s"]:
        return None
    return scopes.per_round_ms(ctx, red["idle_in_round_s"])
