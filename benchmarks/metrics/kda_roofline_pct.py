"""Kernels: the least time the chip could take for the executed steps' delta
rule (the recurrence's own operations and bytes, whatever chunk the program
computes it in: ``benchmarks/flops/ling3_flash_vl.py``) over ``kda_ms``."""

from benchmarks.trace import hybrid_scopes, lm_scopes


def read(ctx):
    parts = hybrid_scopes.parts_s(ctx)
    if parts is None or not parts["kda"] or not ctx["padded_samples"]:
        return None
    spec, config, dev = ctx["spec"], ctx["config"], ctx["devices"]
    flops, nbytes = spec.module(
        "flops", config["flops"]).kda_train_cost_per_sample(config)
    peaks = spec.peaks(dev["kind"])
    peak_flops = peaks["flops_per_s"].get(config["precision"]["module"])
    if peak_flops is None:
        return None
    slots = ctx["padded_samples"] / dev["count"]
    t_flops = slots * flops / peak_flops
    t_bytes = slots * nbytes / peaks["hbm_bytes_per_s"]
    share = 100.0 * max(t_flops, t_bytes) / parts["kda"]
    xla = lm_scopes.xla_count(ctx, hybrid_scopes.KDA)
    print(f"kda_roofline_pct: bound by {'FLOPs' if t_flops >= t_bytes else 'bytes'} "
          f"({t_flops * 1e3:.3f} ms at the FLOP peak, {t_bytes * 1e3:.3f} ms at "
          f"the byte peak, {parts['kda'] * 1e3:.3f} ms taken); the recurrence's "
          f"own {slots * flops:.6g} FLOPs and {slots * nbytes:.6g} bytes; by "
          f"XLA's count over {xla['ops']} executed ops {xla['flops']:.6g} FLOPs, "
          f"{xla['bytes_accessed']:.6g} bytes accessed", flush=True)
    if share > 105.0:
        raise RuntimeError(f"kda_roofline_pct {share:.1f} is over 105%: the "
                           "operations or bytes are counted too high, or the "
                           "time leaves out part of the work")
    return share
