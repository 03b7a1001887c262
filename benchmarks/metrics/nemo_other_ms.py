"""Local training program: the remainder of the hybrid state-space /
latent-MoE decoder's busy time, ms a round: norms, residual adds, head
transposes, the embedding, the loss, the prologue and the scan's own time.
With ``latent_proj_ms`` and the LM cells' ``attn_ms``, ``ssd_ms``,
``ssd_prep_ms``, ``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms`` and
``state_update_ms`` it sums to the round program."""

from benchmarks.trace import latent_scopes


def read(ctx):
    return latent_scopes.part_ms(ctx, "other")
