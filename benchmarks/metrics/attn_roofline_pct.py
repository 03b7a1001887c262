"""Kernels: the least time the chip could take for the executed steps'
attention (causal scores and values, forward and backward; the recomputed
forward and the padded key width not counted) over ``attn_ms``."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.roofline_pct(ctx, "attn", "attn_train_cost_per_sample",
                                  "attn_roofline_pct")
