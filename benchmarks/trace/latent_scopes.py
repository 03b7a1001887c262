"""What a decoder whose routed experts work in a latent adds to
``lm_scopes.py``'s parts: the same reduction of the busiest chip's ops, with
one name more and the remainder it leaves in a model that also has
state-space mixers.

    latent_proj  fedml.lm.latent_proj  a sparse layer's two projections around
                                       its routed experts, into the latent and
                                       back, forward, recomputed forward and
                                       backward (NOT under ``fedml.lm.dense``,
                                       which stays the head, the mixers'
                                       projections and the shared MLP)
    other        all the rest: ``lm_scopes``'s ``other`` less the above and
                 less the state-space mixers' two names (``ssd_scopes.py``)

It reads the trace and edits nothing. The other eight parts are read by the
readers the benchmark had (``attn_ms``, ``expert_mm_ms``, ``moe_route_ms``,
``dense_mm_ms``, ``state_update_ms`` through ``lm_scopes.py``; ``ssd_ms``,
``ssd_prep_ms`` through ``ssd_scopes.py``), which list the cell too: with
these two they partition the busy time, so the nine sum to the round program.
Readers that use this module: ``latent_proj_ms``, ``latent_proj_roofline_pct``
(the two projections' operations and bytes from shapes,
``benchmarks/flops/<config>.py: latent_proj_train_cost_per_sample``, through
``cca_scopes.roofline_pct``, which serves whatever part it is told and raises
over 105%) and ``nemo_other_ms``. Beside them the cell has the experts'
share of their roofline under a name of its own,
``relu2_expert_roofline_pct``: ``lm_scopes.roofline_pct`` on the ``experts``
part at the rows ``sparse_rows_per_token`` counts (rows over the SPARSE
layers' steps: here the state-space layers write ``steps.<layer>`` too and
bring no row, so ``held_rows_per_token`` and ``expert_mm_roofline_pct``,
which divide by every counting layer, are not reported in this cell). A
trace of a program without the
``fedml.lm.latent_proj`` name (the parent commit, another model's cell)
reduces to None: the readers then report nothing.
"""

from __future__ import annotations

from benchmarks.trace import lm_scopes, scopes, ssd_scopes

LATENT = "fedml.lm.latent_proj"


def parts_s(ctx):
    """{"latent_proj", "other": seconds over the traced window}, or None."""
    red, parts = lm_scopes.reduce_ctx(ctx), lm_scopes.parts_s(ctx)
    if parts is None or LATENT not in red["by_scope_s"]:
        return None
    by = red["by_scope_s"]
    latent = by[LATENT]
    mixers = by.get(ssd_scopes.SSD, 0.0) + by.get(ssd_scopes.SSD_PREP, 0.0)
    return {"latent_proj": latent, "other": parts["other"] - latent - mixers}


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])
