"""Round driver: host seconds inside the calls that put the client stack on the
device (the program's ``fedml/setup/place_data`` span; ``device_put`` returns
before the copy ends).
None on a program without the set-up log."""

from benchmarks.trace import setup_spans


def read(ctx):
    return setup_spans.metric(ctx, "place_data_s")
