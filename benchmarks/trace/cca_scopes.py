"""What a decoder with compressed convolutional attention adds to
``lm_scopes.py``'s parts: the same reduction of the busiest chip's ops, with
one name more and the remainder it leaves.

    cca_mix  fedml.lm.cca_mix  between the projections and the kernels: the
                               means of q and k, both convolutions, the
                               normalisation with the key temperature, the
                               value shift
    other    all the rest: ``lm_scopes``'s ``other`` less the above

The other five parts are ``lm_scopes.py``'s own (``attn_ms``,
``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms``, ``state_update_ms``
list the cell too): with these two they partition the busy time. A trace of
a program without the ``fedml.lm.cca_mix`` name (the parent commit, another
model's cell) reduces to None: the readers then report nothing.

:func:`roofline_pct` serves whatever part it is TOLD (seconds, the name of
the cost function): ``lm_scopes.roofline_pct`` serves only the parts it
names, and three readers repeat its arithmetic for theirs.
"""

from __future__ import annotations

from benchmarks.trace import lm_scopes, scopes

CCA_MIX = "fedml.lm.cca_mix"


def parts_s(ctx):
    """{"cca_mix", "other": seconds over the traced window}, or None."""
    red, parts = lm_scopes.reduce_ctx(ctx), lm_scopes.parts_s(ctx)
    if parts is None or CCA_MIX not in red["by_scope_s"]:
        return None
    mix = red["by_scope_s"][CCA_MIX]
    return {"cca_mix": mix, "other": parts["other"] - mix}


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])


def roofline_pct(ctx, seconds, cost_fn: str, name: str, what: str = "",
                 **cost_kw):
    """``max(FLOPs / peak, bytes / peak)`` of the executed slots' work, from
    shapes (``benchmarks/flops/<config>.py: <cost_fn>``, which also takes
    ``cost_kw``), over ``seconds`` of device time; None where there is no
    time, no slot, no such function or no peak for the module's precision.
    Over 105% raises."""
    if not seconds or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    cost = getattr(spec.module("flops", config["flops"]), cost_fn, None)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if cost is None or peak_flops is None:
        return None
    flops, nbytes = cost(config, **cost_kw)
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / seconds
    print(f"{name}: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'} "
          f"({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms at "
          f"the byte peak, {seconds * 1e3:.3f} ms taken); from shapes "
          f"{slots * flops:.6g} FLOPs, {slots * nbytes:.6g} bytes{what}",
          flush=True)
    if share > 105.0:
        raise RuntimeError(f"{name} {share:.1f} is over 105%: the operations "
                           "or bytes are counted too high, or the time leaves "
                           "out part of the work")
    return share
