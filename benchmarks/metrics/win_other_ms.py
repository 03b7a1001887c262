"""Local training program: the remainder of the busy time of a decoder with
window-attention layers, ms a round: norms, rotary, the head-wise gate,
residual adds, the embedding, the loss, the prologue and the scan's own
time. With ``attn_window_ms`` and the LM cells' ``attn_ms``,
``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms`` and ``state_update_ms``
it sums to the round program."""

from benchmarks.trace import window_scopes


def read(ctx):
    return window_scopes.part_ms(ctx, "other")
