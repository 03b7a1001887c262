"""Host data path: materialize + host-to-device milliseconds per round
(the prefetcher's own stage rows), mean over the window's rounds. Nothing
to read where the client stack is resident on the device."""


def read(ctx):
    rows = ctx["stage_rows"]
    if not rows:
        return None
    return sum(r["materialize_ms"] + r["h2d_ms"] for r in rows) / len(rows)
