"""Local training program: device self time under ``fedml.prologue`` (the
stack's cast, reshapes, key splits, member gathers, replay tables,
optimizer-state init), ms a round over the traced rounds."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.part_ms(ctx, "prologue")
