"""What a hybrid decoder's round program (linear-attention and
latent-attention mixers over sparse experts) adds to ``lm_scopes.py``'s
parts: the same reduction of the busiest chip's ops, with two names more
and the remainder they leave.

    kda       fedml.lm.kda       the delta rule's chunked scan
    kda_prep  fedml.lm.kda_prep  short convolutions, SiLU, q/k norms, the
                                 decay and step gates, output norm and gate
    other     all the rest: ``lm_scopes``'s ``other`` less the two above

The other five parts are ``lm_scopes.py``'s own (``attn_ms``,
``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms``, ``state_update_ms``
list the hybrid cell too): with these three they partition the busy time.
A trace of a program without the ``fedml.lm.kda`` name (the parent commit,
another model's cell) reduces to None: the readers then report nothing.
"""

from __future__ import annotations

from benchmarks.trace import lm_scopes, scopes

KDA = "fedml.lm.kda"
KDA_PREP = "fedml.lm.kda_prep"


def parts_s(ctx):
    """{"kda", "kda_prep", "other": seconds over the traced window}, or None."""
    red, parts = lm_scopes.reduce_ctx(ctx), lm_scopes.parts_s(ctx)
    if parts is None or KDA not in red["by_scope_s"]:
        return None
    kda = red["by_scope_s"][KDA]
    prep = red["by_scope_s"].get(KDA_PREP, 0.0)
    return {"kda": kda, "kda_prep": prep, "other": parts["other"] - kda - prep}


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])
