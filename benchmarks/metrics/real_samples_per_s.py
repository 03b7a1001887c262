"""Real (unmasked) training samples through local SGD per second of window:
all completed rounds' real records (the program's ``round_counts``, held
equal to the benchmark's own count of the same cohorts) over all the time
from the first dispatch to the last completion. Padded steps do not count."""


def read(ctx):
    return ctx["real_samples"] / ctx["window"].elapsed
