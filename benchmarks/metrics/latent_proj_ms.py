"""Kernels: device self time under ``fedml.lm.latent_proj`` (a sparse layer's
two projections around its routed experts, into the latent and back:
forward, recomputed forward and backward), ms a round."""

from benchmarks.trace import latent_scopes


def read(ctx):
    return latent_scopes.part_ms(ctx, "latent_proj")
