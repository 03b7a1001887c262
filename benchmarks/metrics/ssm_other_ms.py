"""Local training program: the remainder of the state-space decoder's busy
time, ms a round: norms, residual adds and their multiplier, the embedding,
the loss, the prologue and the scan's own time. With ``ssd_ms``,
``ssd_prep_ms`` and the LM cells' ``attn_ms``, ``dense_mm_ms`` and
``state_update_ms`` it sums to the round program."""

from benchmarks.trace import ssd_scopes


def read(ctx):
    return ssd_scopes.part_ms(ctx, "other")
