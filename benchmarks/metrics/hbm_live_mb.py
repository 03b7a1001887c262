"""Device: the most the process's live arrays ever held on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``: client stack, weights, round
outputs), in MB of 1e6 bytes. One part of ``hbm_peak_mb``."""


def read(ctx):
    return ctx["live_bytes"] / 1e6 if ctx.get("live_bytes") else None
