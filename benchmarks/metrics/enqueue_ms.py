"""Round driver: host self time of the program's ``fedml/round/enqueue``
spans (the call of the jitted round step with the ``jnp.asarray`` of its
host arguments; a new program's build is a child span and not in it), ms a
round over the traced rounds. None without the spans."""

from benchmarks.trace import scopes


def read(ctx):
    return scopes.host_span_ms(ctx, "fedml/round/enqueue")
