"""90th percentile of the gap between consecutive round completions, in
ms, over all the window's rounds: the stalls a cross-device operator sees
when the host falls behind."""

from benchmarks.harness.loop import percentile


def read(ctx):
    gaps = ctx["window"].gaps_ms()
    return percentile(gaps, 90.0) if gaps else None
