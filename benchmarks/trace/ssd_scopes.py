"""What a decoder with state-space mixers adds to ``lm_scopes.py``'s parts:
the same reduction of the busiest chip's ops, with two names more and the
remainder they leave.

    ssd       fedml.lm.ssd       the chunked recurrence: intra-chunk products,
                                 the scan over chunks, the read-out
    ssd_prep  fedml.lm.ssd_prep  the convolution and SiLU, splits and head
                                 reshapes, softplus, the gated norm
    other     all the rest: ``lm_scopes``'s ``other`` less the two above

Three of the other parts are ``lm_scopes.py``'s own (``attn_ms``,
``dense_mm_ms``, ``state_update_ms`` list the cell too; a dense decoder has
no ``experts`` and no ``route``, which read 0 and stay inside ``other``'s
sum): with these three they partition the busy time. A trace of a program
without the ``fedml.lm.ssd`` name (the parent commit, another model's cell)
reduces to None: the readers then report nothing.
"""

from __future__ import annotations

from benchmarks.trace import lm_scopes, scopes

SSD = "fedml.lm.ssd"
SSD_PREP = "fedml.lm.ssd_prep"


def parts_s(ctx):
    """{"ssd", "ssd_prep", "other": seconds over the traced window}, or None."""
    red, parts = lm_scopes.reduce_ctx(ctx), lm_scopes.parts_s(ctx)
    if parts is None or SSD not in red["by_scope_s"]:
        return None
    ssd = red["by_scope_s"][SSD]
    prep = red["by_scope_s"].get(SSD_PREP, 0.0)
    other = parts["other"] + parts["experts"] + parts["route"] - ssd - prep
    return {"ssd": ssd, "ssd_prep": prep, "other": other}


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])
