"""Kernels: the least time the chip could take for the executed steps'
convolutions, ``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)``, over the
time their ops took (``conv_ms``). FLOPs and bytes from shapes
(``benchmarks/flops/<config>.py: conv_train_cost_per_sample``) times the
sample slots the traced rounds executed (``padded_samples``: the chip runs
the padded steps too); prints which of the two bounds. Over 105% raises."""

from benchmarks.trace import scopes


def read(ctx):
    red = scopes.reduce_ctx(ctx)
    if red is None or not red["parts_s"]["conv"] or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    flops, nbytes = spec.module(
        "flops", config["flops"]).conv_train_cost_per_sample(config)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if peak_flops is None:      # no published peak at this precision
        return None
    # the reduction reads the busiest chip; each chip runs its share
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / red["parts_s"]["conv"]
    xla = red["conv_xla"]
    print(f"conv_roofline_pct: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'}"
          f" ({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms "
          f"at the byte peak, {red['parts_s']['conv'] * 1e3:.3f} ms taken); "
          f"from shapes {slots * flops:.6g} FLOPs, {slots * nbytes:.6g} bytes; "
          f"by XLA's own count {xla['flops']:.6g} FLOPs, "
          f"{xla['bytes_accessed']:.6g} bytes accessed", flush=True)
    if share > 105.0:
        raise RuntimeError(f"conv_roofline_pct {share:.1f} is over 105%: the "
                           "operations or bytes are counted too high, or "
                           "conv_ms leaves out part of the work")
    return share
