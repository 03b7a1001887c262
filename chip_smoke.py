#!/usr/bin/env python3
"""chip_smoke.py — the flagship federated rounds, once, on the chip.

The quickest proof that the system still starts on the accelerator: drives
the normal entry point (``fedml_tpu.experiments.run.main``, the README
Quickstart) through the three round paths at the full width of the
flagship model, in ONE process (one process per chip), and checks what
comes back:

- **sim**: FedAvg, ResNet-56, 32 non-IID clients (hetero alpha 0.5), 8 per
  round, batch 64, bf16 module, 2 packed lanes, 4 rounds, evals on the way;
- **cross-silo**: the same model and data, full participation, sharded over
  every chip the process sees;
- **host path**: 342,477 logical clients, 50 per round, prefetched two
  rounds deep — once as whole-cohort rounds, once streamed in sub-cohort
  chunks (the donated steps); the two must agree.

Each phase must end with finite losses, at least one eval, a model that
lives on TPU devices, and (flagship phases) ``device_data='auto'`` resolved
to a resident client stack — spread over all chips in the cross-silo phase.
Any failure is an exception, hence a non-zero exit; nothing is caught.

Without a TPU the script fails at once and prints no result. It selects no
platform itself. Speed figures it prints are information, not records.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import json
import math
import sys
import time
from importlib import metadata
from typing import Sequence


class SmokeFailure(RuntimeError):
    """A phase finished but what came out of it is wrong."""


# -- phases: functions of their sizes, so tests can call them tiny on CPU ----

def flagship_argv(algorithm: str, *, model: str = "resnet56",
                  clients: int = 32, per_round: int = 8, batch: int = 64,
                  rounds: int = 4, eval_every: int = 2,
                  extra: Sequence[str] = ()) -> list:
    """CLI flags of the flagship round (the r01-r05 configuration)."""
    return [
        "--algorithm", algorithm, "--model", model, "--dataset", "cifar10",
        "--client_num_in_total", str(clients),
        "--client_num_per_round", str(per_round),
        "--partition_method", "hetero", "--partition_alpha", "0.5",
        "--batch_size", str(batch), "--epochs", "1",
        "--client_optimizer", "sgd", "--lr", "0.1", "--momentum", "0.9",
        "--dtype", "bfloat16", "--pack_lanes", "2",
        "--comm_round", str(rounds),
        "--frequency_of_the_test", str(eval_every), *extra]


def host_argv(*, clients: int = 342_477, per_round: int = 50,
              rounds: int = 3, cohort_chunk: int = 0,
              extra: Sequence[str] = ()) -> list:
    """CLI flags of the cross-device host round (the r05 operating point);
    ``cohort_chunk > 0`` streams each cohort through the chunked fold."""
    stream = (["--stream_aggregate", "deterministic",
               "--cohort_chunk", str(cohort_chunk)] if cohort_chunk else [])
    return [
        "--algorithm", "fedavg", "--model", "lr",
        "--dataset", "stackoverflow_lr_full",
        "--client_num_in_total", str(clients),
        "--client_num_per_round", str(per_round),
        "--batch_size", "10", "--epochs", "1", "--lr", "0.05",
        "--dtype", "bfloat16", "--host_pipeline_depth", "2",
        "--comm_round", str(rounds), "--frequency_of_the_test", "2",
        *stream, *extra]


def check_result(name: str, result: dict, *, platform: str,
                 resident: bool, spread: bool = False) -> None:
    """Raise :class:`SmokeFailure` unless the phase's history shows at
    least one eval, only finite losses, a model held on ``platform``
    devices, the expected ``device_data`` resolution and (``spread``) a
    resident client stack with a shard on every device."""
    losses = result.get("Test/Loss") or []
    if not losses:
        raise SmokeFailure(f"{name}: no eval ran")
    if not all(isinstance(v, float) and math.isfinite(v) for v in losses):
        raise SmokeFailure(f"{name}: non-finite eval loss in {losses}")
    where = result["placement"]
    held = where["variables_on"]
    if not held or any(not d.startswith(platform + ":") for d in held):
        raise SmokeFailure(
            f"{name}: model lives on {held}, expected {platform} devices")
    if where["device_resident"] != resident:
        raise SmokeFailure(
            f"{name}: device_resident={where['device_resident']}, "
            f"expected {resident} (device_data='auto' resolved wrongly)")
    if spread:
        shard_devs = {dev for dev, _shape in where["resident_shards"]}
        if len(shard_devs) != where["device_count"]:
            raise SmokeFailure(
                f"{name}: resident client stack has shards on devices "
                f"{sorted(shard_devs)} of {where['device_count']}")


def check_same_losses(name: str, got: Sequence[float],
                      ref: Sequence[float], rtol: float = 1e-3) -> None:
    """The repo's own reference check: the streamed fold computes the batch
    round's aggregate (unchunked it is bit-identical by construction,
    tests/test_fedsched.py; chunks reduce in another order, hence a
    tolerance)."""
    if len(got) != len(ref) or any(
            abs(a - b) > rtol * abs(b) for a, b in zip(got, ref)):
        raise SmokeFailure(f"{name}: losses {list(got)} disagree with the "
                           f"reference {list(ref)} beyond rtol {rtol:g}")


def run_phase(name: str, argv: Sequence[str], events: dict) -> dict:
    """One ``run.main(argv)`` in this process. Prints the phase's wall
    seconds split into set-up (JAX trace, lower, compile-or-cache-read) and
    the rest, with the persistent cache's counters for the phase
    (``events`` is ``compile_cache.count_cache_events()``'s live dict)."""
    from fedml_tpu.experiments import run

    before = dict(events)
    t0 = time.perf_counter()
    result = run.main(list(argv))
    wall = time.perf_counter() - t0
    d = {k: events[k] - before[k] for k in events}
    setup = d["trace_secs"] + d["lower_secs"] + d["compile_secs"]
    print(f"phase {name}: wall {wall:.1f} s = set-up {setup:.1f} s (trace "
          f"{d['trace_secs']:.1f} + lower {d['lower_secs']:.1f} + compile "
          f"{d['compile_secs']:.1f}) + steady {wall - setup:.1f} s; compile "
          f"cache {d['requests']} request(s), {d['hits']} hit(s), "
          f"{d['misses']} miss(es)", flush=True)
    where = result["placement"]
    print(f"phase {name}: evals at rounds {result['round']} "
          f"Test/Loss {result['Test/Loss']}; model on "
          f"{where['variables_on']}; resident={where['device_resident']} "
          f"shards={where['resident_shards']} "
          f"bytes_in_use={where['bytes_in_use']}", flush=True)
    return result


def sim_phase(events: dict, *, platform: str = "tpu", **sizes) -> dict:
    result = run_phase("sim", flagship_argv("fedavg", **sizes), events)
    check_result("sim", result, platform=platform, resident=True)
    return result


def crosssilo_phase(events: dict, *, platform: str = "tpu", **sizes) -> dict:
    """Full participation: ``per_round`` follows ``clients``."""
    sizes.setdefault("clients", 32)
    sizes["per_round"] = sizes["clients"]
    result = run_phase("cross-silo",
                       flagship_argv("crosssilo_fedavg", **sizes), events)
    check_result("cross-silo", result, platform=platform, resident=True,
                 spread=True)
    return result


def host_phase(events: dict, *, platform: str = "tpu", **sizes) -> dict:
    name = "host-streamed" if sizes.get("cohort_chunk") else "host"
    result = run_phase(name, host_argv(**sizes), events)
    # a virtual 342k-client stack is never resident: rounds ship cohorts
    check_result(name, result, platform=platform, resident=False)
    return result


# -- the command: never runs without a chip ---------------------------------

def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{dev.platform!r} ({len(devices)} device(s)). This script "
              f"only runs on the accelerator and selects no platform "
              f"itself.", file=sys.stderr)
        return 1

    import jaxlib

    import fedml_tpu.native as native
    from fedml_tpu.ops import common
    from fedml_tpu.ops.attention import _pick_impl
    from fedml_tpu.utils.compile_cache import (count_cache_events,
                                               enable_compile_cache)

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a distribution"
    cache_dir = enable_compile_cache()
    events = count_cache_events()
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {len(devices)}")
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}")
    print(f"compile cache: {cache_dir}")
    print(f"native: {'library' if native.available() else 'python fallback'}",
          flush=True)

    # the chip path must compile its kernels, not interpret them or fall to
    # the XLA reference (ops/common.py and ops/attention.py keep those as
    # the CPU test route)
    if common.interpret() is not False:
        raise SmokeFailure("ops.common.interpret() is not False on a TPU")
    if _pick_impl("auto") != "pallas":
        raise SmokeFailure("ops.attention 'auto' does not pick pallas on a TPU")

    t0 = time.perf_counter()
    sim_phase(events)
    crosssilo_phase(events)
    batch = host_phase(events)
    streamed = host_phase(events, cohort_chunk=25)
    check_same_losses("host-streamed vs host", streamed["Test/Loss"],
                      batch["Test/Loss"])
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f} s;"
          f" compile cache totals: {events['requests']} request(s), "
          f"{events['hits']} hit(s), {events['misses']} miss(es)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
