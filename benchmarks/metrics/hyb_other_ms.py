"""Local training program: the remainder of the hybrid decoder's busy time,
ms a round: norms, rotary, residual adds, the embedding, the loss, the
prologue and the scan's own time. With ``kda_ms``, ``kda_prep_ms`` and the
LM cells' ``attn_ms``, ``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms``
and ``state_update_ms`` it sums to the round program."""

from benchmarks.trace import hybrid_scopes


def read(ctx):
    return hybrid_scopes.part_ms(ctx, "other")
