"""Local training program: the remainder of the compressed-attention
decoder's busy time, ms a round: norms, rotary and head transposes, the
scaled residual adds, the embedding, the loss, the prologue and the scan's
own time. With ``cca_mix_ms`` and the LM cells' ``attn_ms``,
``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms`` and ``state_update_ms``
it sums to the round program."""

from benchmarks.trace import cca_scopes


def read(ctx):
    return cca_scopes.part_ms(ctx, "other")
