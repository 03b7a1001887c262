"""Device: the HBM the TPU runtime reserved for the largest program's
temporaries (``memory_stats()["peak_bytes_reserved"]``: the round program's
activations, gathered cohort and gradients), in MB of 1e6 bytes. The other
part of ``hbm_peak_mb``."""


def read(ctx):
    return ctx["scratch_bytes"] / 1e6 if ctx.get("scratch_bytes") else None
