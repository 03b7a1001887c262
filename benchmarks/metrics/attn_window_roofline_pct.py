"""Kernels: the least time the chip could take for the executed steps'
window attention (the band's own pairs and the bytes of q, k, v, o and their
cotangents once each way: ``benchmarks/flops/<config>.py:
attn_window_train_cost_per_sample``) over ``attn_window_ms``."""

from benchmarks.trace import window_scopes


def read(ctx):
    return window_scopes.roofline_pct(
        ctx, "attn_window", "attn_window_train_cost_per_sample",
        "attn_window_roofline_pct")
