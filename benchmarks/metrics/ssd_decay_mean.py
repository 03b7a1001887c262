"""Local training program: the mean over the state-space layers, their
training steps, positions and heads of ``exp(dt A)``, what is left of a
state one position later, from the program's ``model`` counter group
(``decay.<layer>`` over ``steps.<layer>``, which the packed round sums over
its clients' steps): the one number that says whether a state outlives a
chunk (``mean^Q``). None where the program keeps no such counter."""


def read(ctx):
    try:
        from fedml_tpu.obs import model_counters
    except ImportError:
        return None
    group = dict(model_counters().items())
    decay = {k.partition(".")[2]: v for k, v in group.items()
             if k.startswith("decay.")}
    steps = sum(group.get(f"steps.{layer}", 0.0) for layer in decay)
    if not steps:
        return None
    print(f"ssd_decay_mean: over {steps:.0f} layer-steps of {len(decay)} "
          "state-space layers", flush=True)
    return sum(decay.values()) / steps
