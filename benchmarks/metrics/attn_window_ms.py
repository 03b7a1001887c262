"""Kernels: device self time under ``fedml.lm.attn_window`` (scores, softmax
and values of the window layers: the attention kernels over the band,
forward, recomputed forward and backward), ms a round."""

from benchmarks.trace import window_scopes


def read(ctx):
    return window_scopes.part_ms(ctx, "attn_window")
