"""Device: 1 - union of the device-op intervals over the traced window,
mean over the cell's chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
