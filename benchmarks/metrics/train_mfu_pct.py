"""Local training program: real samples per second times the training
FLOPs a sample requires (from shapes, ``benchmarks/flops/<config>.py``;
padded and recomputed work does not count) over chips times the peak."""


def read(ctx):
    spec, config = ctx["spec"], ctx["config"]
    flops = spec.module("flops", config["flops"]).train_flops_per_sample(config)
    dev = ctx["devices"]
    peak = spec.peaks(dev["kind"])["flops_per_s"][config["precision"]["module"]]
    rate = ctx["real_samples"] / ctx["window"].elapsed
    share = 100.0 * rate * flops / (dev["count"] * peak)
    if share > 105.0:
        raise RuntimeError(f"train_mfu_pct {share:.1f} is over 105% of the "
                           "peak: the FLOP count or the time is wrong")
    return share
