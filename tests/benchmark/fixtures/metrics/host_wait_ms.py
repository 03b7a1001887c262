"""Host data path: the exposed part of the host stages, the milliseconds a
round waited for its inputs, mean over the window's rounds."""


def read(ctx):
    rows = ctx["stage_rows"]
    if not rows:
        return None
    return sum(r["wait_ms"] for r in rows) / len(rows)
