"""Local training program: the remainder of the busy time, ms a round:
norms, rotary, residual adds, the embedding, the loss, the prologue, the
batch gather and the scan's own time. With ``attn_ms``, ``expert_mm_ms``,
``moe_route_ms``, ``dense_mm_ms`` and ``state_update_ms`` it sums to the
round program."""

from benchmarks.trace import lm_scopes


def read(ctx):
    return lm_scopes.part_ms(ctx, "other")
