"""The device a run is made on: found or refused, stamped, and read."""

from __future__ import annotations

import sys


class NoAccelerator(SystemExit):
    """The run's device is not what the cell asks for: exit code 3, no result."""


def require_tpu(chips: int):
    """The cell's chips, or no run: a measurement path never falls back to
    the CPU, and a cell on 4 chips is not measured on 1 (or 1 on 4)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). No result.",
              file=sys.stderr)
        raise NoAccelerator(3)
    return devices


def stamp(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _peak(stats: dict) -> int:
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def _fullest(devices) -> dict:
    """``memory_stats()`` of the chip that held most at its peak; {} where
    the backend keeps none (the CPU, in the tests)."""
    return max((d.memory_stats() or {} for d in devices), key=_peak)


def memory_split(devices) -> tuple:
    """(live, scratch) bytes of the fullest chip at their peaks. Live is
    ``peak_bytes_in_use``: the arrays the process holds (client stack,
    weights, round outputs). Scratch is ``peak_bytes_reserved``: the region
    at the bottom of HBM that the TPU runtime reserves for the running
    program's temporaries (the round program's activations, gathered cohort
    and gradients), sized to the largest program run so far and counted in
    no ``*_in_use`` figure (``benchmarks/memory_probe.py`` shows both on
    the chip; PERF.md section 2). 0s where the backend keeps no figures."""
    stats = _fullest(devices)
    return (int(stats.get("peak_bytes_in_use", 0)),
            int(stats.get("peak_bytes_reserved", 0)))


def memory_report(devices) -> str:
    """Every figure ``memory_stats()`` keeps for the fullest chip, on one
    line: information, outside the result."""
    stats = _fullest(devices)
    return "device memory (fullest chip): " + (
        "  ".join(f"{k} {v}" for k, v in sorted(stats.items())) or "no figures")


def memory_brief(devices) -> str:
    """Live and scratch bytes now, for the lines that follow set-up's stages."""
    stats = _fullest(devices)
    return (f"in use {stats.get('bytes_in_use', 0)}  "
            f"reserved {stats.get('bytes_reserved', 0)}")
