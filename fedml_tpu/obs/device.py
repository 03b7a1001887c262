"""Device-memory sampler: an HBM lane in the trace without a profiler run.

The ROADMAP gap this closes: device-side visibility used to require a
separate ``--profile_dir`` run through the jax profiler. This sampler
instead snapshots ``jax.local_devices()`` ``memory_stats()`` (bytes_in_use
and the peak watermark, and where the backend gives them ``bytes_reserved``
/ ``peak_bytes_reserved``: the running program's scratch, which no
``*_in_use`` figure holds) at ROUND BOUNDARIES and emits them as ``device``-
category counter events, which the Perfetto export renders as a dedicated
"devices" counter lane next to the span timeline.

Overhead contract (the sampler's side of DESIGN.md §12):

- only runs when tracing is enabled — the untraced hot path never reaches
  this module;
- one ``memory_stats()`` call per local device per round, host-side only:
  it reads allocator counters, never syncs or touches the device stream;
- backends without allocator stats (CPU returns None) fall back to ONE
  host RSS read (``/proc/self/statm``) so the lane exists everywhere the
  tests run; the keys name their source (``d<i>/...`` vs ``host/...``).
"""

from __future__ import annotations

import os
from typing import Optional

_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def _host_rss_bytes() -> Optional[int]:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        return None


def sample_device_memory(tr, round_idx: Optional[int] = None) -> dict:
    """Snapshot per-device memory onto ``tr`` as a ``device_mem`` counter.

    Returns the sampled values (tests read them directly). ``tr`` must be
    an ENABLED tracer — call sites gate on ``tracer_if_enabled``."""
    import jax

    vals: dict = {}
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        vals[f"d{d.id}/bytes_in_use"] = int(ms.get("bytes_in_use", 0))
        peak = ms.get("peak_bytes_in_use")
        if peak is not None:
            vals[f"d{d.id}/peak_bytes"] = int(peak)
        # the running program's scratch is in no *_in_use figure: the TPU
        # runtime keeps it in a region of its own (PERF.md, Memory)
        for key in ("bytes_reserved", "peak_bytes_reserved"):
            if ms.get(key) is not None:
                vals[f"d{d.id}/{key}"] = int(ms[key])
    if not vals:
        rss = _host_rss_bytes()
        if rss is not None:
            vals["host/rss_bytes"] = rss
    if vals:
        tr.counter("device_mem", vals, cat="device",
                   args=None if round_idx is None else {"round": round_idx})
    return vals
