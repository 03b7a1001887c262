"""Kernels: the least time the chip could take for the executed steps'
state-space recurrence (the state's own operations and bytes, whatever chunk
the program computes it in: ``benchmarks/flops/granite4_h_micro.py``) over
``ssd_ms``."""

from benchmarks.trace import lm_scopes, ssd_scopes


def read(ctx):
    parts = ssd_scopes.parts_s(ctx)
    if parts is None or not parts["ssd"] or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    cost = getattr(spec.module("flops", config["flops"]),
                   "ssd_train_cost_per_sample", None)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if cost is None or peak_flops is None:
        return None
    flops, nbytes = cost(config)
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / parts["ssd"]
    xla = lm_scopes.xla_count(ctx, ssd_scopes.SSD)
    print(f"ssd_roofline_pct: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'} "
          f"({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms at "
          f"the byte peak, {parts['ssd'] * 1e3:.3f} ms taken); the recurrence's "
          f"own {slots * flops:.6g} FLOPs and {slots * nbytes:.6g} bytes; by "
          f"XLA's count over {xla['ops']} executed ops {xla['flops']:.6g} FLOPs, "
          f"{xla['bytes_accessed']:.6g} bytes accessed", flush=True)
    if share > 105.0:
        raise RuntimeError(f"ssd_roofline_pct {share:.1f} is over 105%: the "
                           "operations or bytes are counted too high, or the "
                           "time leaves out part of the work")
    return share
