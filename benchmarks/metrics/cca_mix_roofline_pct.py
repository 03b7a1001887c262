"""Kernels: the least time the chip could take for the executed steps'
mixing (the head-wise convolution's own products and the bytes of q, k and v
each way, whatever form the program computes them in:
``benchmarks/flops/zaya1_8b.py``) over ``cca_mix_ms``."""

from benchmarks.trace import cca_scopes


def read(ctx):
    parts = cca_scopes.parts_s(ctx)
    if parts is None:
        return None
    return cca_scopes.roofline_pct(
        ctx, parts["cca_mix"], "cca_mix_train_cost_per_sample",
        "cca_mix_roofline_pct", " (the mixing's own work; recomputed work "
        "and float32 intermediates not counted)")
