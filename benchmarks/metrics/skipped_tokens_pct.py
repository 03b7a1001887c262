"""Local training program: the share of trained tokens whose ONE choice was
the router's last output, which is no expert (the sparse sub-layer adds
nothing for them, on either chip of the layer), the mean over the layers
and the program's steps; from the program's ``model`` counter group
(``skipped.<layer>`` over ``steps.<layer>`` x tokens, which the packed round
sums over its clients' steps). 1 / 17 where the choices are even. A routing
statistic: ``better: lower`` because the form wants a direction, and none is
meant. None where the program keeps no such counter."""


def read(ctx):
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:
        return None
    group = dict(model_counters().items())
    skipped = {k.partition(".")[2]: v for k, v in group.items()
               if k.startswith("skipped.")}
    steps = sum(group.get(f"steps.{layer}", 0.0) for layer in skipped)
    if not steps:
        return None
    config = ctx["config"]
    tokens = int(config["recipe"]["batch_size"]) * int(config["data"]["seq_len"])
    print(f"skipped_tokens_pct: over {steps:.0f} layer-steps of {tokens} "
          f"tokens in {len(skipped)} layers", flush=True)
    return 100.0 * sum(skipped.values()) / (steps * tokens)
