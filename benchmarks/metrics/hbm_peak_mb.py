"""Peak device memory at the end of the window, before the output check
allocates anything, on the fullest of the cell's chips, in MB of 1e6 bytes:
the live arrays' peak (``hbm_live_mb``) plus the running program's scratch
(``hbm_scratch_mb``), which the TPU's allocator keeps in separate counters
(``harness/device.memory_split``). The same bytes as the result's
``memory_peak_bytes``."""


def read(ctx):
    return ctx["peak_bytes"] / 1e6 if ctx["peak_bytes"] else None
