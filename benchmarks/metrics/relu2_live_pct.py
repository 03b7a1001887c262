"""Local training program: the share of the hidden units of a sparse layer's
squared-ReLU MLPs (the held experts' rows and the shared MLP on every token)
that are positive before the square, the mean over the layers and the
program's steps; from the program's ``model`` counter group
(``live_units.<layer>`` over ``steps.<layer>``, which the packed round sums
over its clients' steps). What a kernel that skips dead units would still
have to compute: ``better: lower`` because the form wants a direction, and a
routing-like statistic is meant. None where the program keeps no such
counter."""


def read(ctx):
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:
        return None
    group = dict(model_counters().items())
    live = {k.partition(".")[2]: v for k, v in group.items()
            if k.startswith("live_units.")}
    steps = sum(group.get(f"steps.{layer}", 0.0) for layer in live)
    if not steps:
        return None
    print(f"relu2_live_pct: over {steps:.0f} layer-steps of {len(live)} "
          "sparse layers", flush=True)
    return 100.0 * sum(live.values()) / steps
