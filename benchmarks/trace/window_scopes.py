"""What a decoder with window-attention layers adds to ``lm_scopes.py``'s
parts: the same reduction of the busiest chip's ops, with one name more and
the remainder it leaves.

    attn_window  fedml.lm.attn_window  scores, softmax, values of the window
                                       layers (the kernels' band)
    other        all the rest: ``lm_scopes``'s ``other`` less the above

The other five parts are ``lm_scopes.py``'s own (``attn_ms``, which is then
the FULL layers' alone, ``expert_mm_ms``, ``moe_route_ms``, ``dense_mm_ms``,
``state_update_ms`` list the cell too): with these two they partition the
busy time. A trace of a program without the ``fedml.lm.attn_window`` name
(the parent commit, another model's cell) reduces to None: the readers then
report nothing.
"""

from __future__ import annotations

from benchmarks.trace import lm_scopes, scopes

ATTN_WINDOW = "fedml.lm.attn_window"


def parts_s(ctx):
    """{"attn_window", "other": seconds over the traced window}, or None."""
    red, parts = lm_scopes.reduce_ctx(ctx), lm_scopes.parts_s(ctx)
    if parts is None or ATTN_WINDOW not in red["by_scope_s"]:
        return None
    band = red["by_scope_s"][ATTN_WINDOW]
    return {"attn_window": band, "other": parts["other"] - band}


def part_ms(ctx, part: str):
    parts = parts_s(ctx)
    return None if parts is None else scopes.per_round_ms(ctx, parts[part])


def roofline_pct(ctx, part: str, cost_fn: str, name: str):
    """``max(FLOPs / peak, bytes / peak)`` of the executed slots' work in
    ``part``, from shapes (``benchmarks/flops/<config>.py: <cost_fn>``), over
    the part's device time. Over 105% raises. (``lm_scopes.roofline_pct``
    serves only the parts it names.)"""
    parts = parts_s(ctx)
    if parts is None or not parts[part] or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    cost = getattr(spec.module("flops", config["flops"]), cost_fn, None)
    if cost is None:
        return None
    flops, nbytes = cost(config)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if peak_flops is None:
        return None
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / parts[part]
    print(f"{name}: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'} "
          f"({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms at "
          f"the byte peak, {parts[part] * 1e3:.3f} ms taken); from shapes "
          f"{slots * flops:.6g} FLOPs, {slots * nbytes:.6g} bytes (the band's "
          "own pairs; recomputed and out-of-band work not counted)", flush=True)
    if share > 105.0:
        raise RuntimeError(f"{name} {share:.1f} is over 105%: the operations "
                           "or bytes are counted too high, or the time leaves "
                           "out part of the work")
    return share
