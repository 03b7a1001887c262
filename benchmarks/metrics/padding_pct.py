"""Cohort scheduling: share of the executed sample slots that held no real
record, 1 - real/padded, from the program's ``round_counts``. A count."""


def read(ctx):
    if not ctx["padded_samples"]:
        return None
    return 100.0 * (1.0 - ctx["real_samples"] / ctx["padded_samples"])
