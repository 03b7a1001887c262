"""Round driver: seconds of ``ModelBundle.init`` inside the constructor (the
program's ``fedml/setup/init_variables`` span): one jitted program where the
model gives an ``init_shape``, else a program an op.
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "init_variables_s")
