"""Local training program: everything else inside the scan, ms a round: the
``while``'s own time, ``fedml.step.reset`` / ``gather`` / ``emit``, and in
``fedml.step.train`` the activations, the loss and whatever is neither a
convolution nor a normalisation."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "step_other")
