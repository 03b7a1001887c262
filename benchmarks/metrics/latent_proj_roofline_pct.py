"""Kernels: the least time the chip could take for the executed steps' two
latent projections (their products and the bytes of their operands and
results, from shapes: ``benchmarks/flops/nemotron3_super_120b.py``) over
``latent_proj_ms``."""

from benchmarks.trace import cca_scopes, latent_scopes


def read(ctx):
    parts = latent_scopes.parts_s(ctx)
    if parts is None:
        return None
    return cca_scopes.roofline_pct(
        ctx, parts["latent_proj"], "latent_proj_train_cost_per_sample",
        "latent_proj_roofline_pct", " (recomputed work not counted)")
