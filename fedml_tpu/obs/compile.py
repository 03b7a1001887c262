"""Compile telemetry: attribute program-build time per round-program shape.

Every distinct round plan (cohort bucket tuple, packed shape key, super-step
block length) compiles its own XLA program, and a fresh compile of a
flagship round program takes seconds to minutes, not milliseconds.
Before this module that cost was invisible: it landed inside
whichever round happened to trigger the build. :func:`timed_build` makes it
first-class:

- a ``compile`` :class:`CounterGroup` on the default registry accumulates
  ``hits`` / ``misses`` / ``build_ms`` / ``first_call_ms`` — cheap enough to
  run unconditionally (each event is one dict store), so the numbers exist
  even in untraced runs (bench.py embeds them in its JSON tail);
- when tracing is on, each build also emits two ``compile``-category spans:
  ``<name>:build`` around the program CONSTRUCTION (builder() returns the
  jitted callable without compiling — usually sub-ms) and
  ``<name>:first_call`` around the first invocation, which is where jax
  traces and XLA compiles before dispatch. With ``async_rounds`` the first
  call still blocks until the executable exists (dispatch needs it), so
  first_call_ms ≈ trace + compile time — the set-up cost a cold compile
  cache pays — without the tracer ever forcing a device sync.

The wrapper returned by :func:`timed_build` is numerically transparent: it
forwards ``*args`` untouched and only reads clocks, preserving the
traced == untraced bit-identity contract.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from fedml_tpu.obs.registry import CounterGroup, default_registry
from fedml_tpu.obs.tracer import (NOOP_SPAN, SPAN_BUILD, span,
                                  tracer_if_enabled)

_KEYS = ("hits", "misses", "build_ms", "first_call_ms")
#: module-global strong ref: the registry only holds weakrefs, and compile
#: accounting is process-lifetime (rank 0 owns it so per-rank registry
#: snapshots don't multiply-count one process-wide group)
_GROUP: Optional[CounterGroup] = None


def compile_counters() -> CounterGroup:
    """The process-wide ``compile`` counter group (created on first use)."""
    global _GROUP
    if _GROUP is None:
        _GROUP = default_registry().group("compile", rank=0, keys=_KEYS)
    return _GROUP


_MODEL_GROUP: Optional[CounterGroup] = None


def model_counters() -> CounterGroup:
    """The process-wide ``model`` counter group: host numbers a model keeps
    in its ``counters`` collection (``ModelBundle.counters``: the sparse
    layers' rows per held expert and their steps, summed on the device over
    every step since the variables were seeded), written by the round
    driver's ``close()``: each key holds the last value written."""
    global _MODEL_GROUP
    if _MODEL_GROUP is None:
        _MODEL_GROUP = default_registry().group("model", rank=0)
    return _MODEL_GROUP


def record_cache_hit(name: str) -> None:
    """One LRU hit: the compiled program was reused, no build happened.
    Attributed both in aggregate and per program name, so a report can say
    which cache is hot vs thrashing."""
    g = compile_counters()
    g["hits"] = g.get("hits", 0) + 1
    g[f"hits.{name}"] = g.get(f"hits.{name}", 0) + 1


def timed_build(name: str, shape_key, builder: Callable) -> Callable:
    """Run ``builder()`` under compile telemetry; return the built step
    wrapped so its FIRST invocation (where trace + XLA compile happen) is
    timed and attributed too. ``shape_key`` is recorded (repr'd) on the
    spans so a report can say WHICH program shape cost the time."""
    g = compile_counters()
    tr = tracer_if_enabled(0)
    t0 = time.perf_counter()
    # fedml/round/build: the profiler-clock span of a new program, around
    # its construction here and around its first call below (where jax
    # traces and XLA compiles); the ring's compile spans and the counters
    # stay as they were
    ring = NOOP_SPAN if tr is None else tr.span(
        f"{name}:build", cat="compile", args={"shape_key": repr(shape_key)})
    with span(SPAN_BUILD, program=name), ring:
        fn = builder()
    # counters bump only once the builder has RETURNED a program: a raising
    # builder propagates with no partial misses/build_ms entry (the caller's
    # LRU never stores the step, so a retry is a fresh build, counted once)
    g["misses"] = g.get("misses", 0) + 1
    g[f"misses.{name}"] = g.get(f"misses.{name}", 0) + 1
    g["build_ms"] = g.get("build_ms", 0.0) + (time.perf_counter() - t0) * 1e3

    # a packed round program says how it runs its lanes (`.lane_ids`:
    # lanes, lane_width — parallel/packed.lane_vmap_width): the last value
    # per program name is kept here, and the span of the first call, where
    # that choice is traced and compiled, carries it
    ids = getattr(fn, "lane_ids", None) or {}
    for k, v in ids.items():
        g[f"{k}.{name}"] = v

    first = [True]

    def step(*args):
        if not first[0]:
            return fn(*args)
        tr = tracer_if_enabled(0)
        t0 = time.perf_counter()
        ring = NOOP_SPAN if tr is None else tr.span(
            f"{name}:first_call", cat="compile",
            args={"shape_key": repr(shape_key)})
        with span(SPAN_BUILD, program=name, **ids), ring:
            out = fn(*args)
        # only a SUCCESSFUL first call records first_call_ms: a raise
        # propagates, the flag stays set, and the next invocation is timed
        # as the first (the compile genuinely happens on whichever call
        # completes). The :first_call SPAN above does close on the failed
        # attempt — deliberately: spans record attempts (the time was truly
        # spent), counters record successful compile accounting, so after a
        # retry a trace may carry more first_call spans than the counter.
        first[0] = False
        g["first_call_ms"] = g.get("first_call_ms", 0.0) + (
            time.perf_counter() - t0) * 1e3
        # fedcost static attribution (obs/cost): lower the program we just
        # paid to compile and record its per-op roofline table. Pure
        # tracing — no second compile, no sync — and only when enabled.
        from fedml_tpu.obs import cost as _cost

        if _cost.cost_attribution_enabled():
            _cost.attribute_program(name, shape_key, fn, args)
        return out

    # packed programs carry fedcost packing hints as `.cost_hints` and
    # their lane geometry as `.lane_ids`; keep such sidecar attributes
    # reachable
    for attr in ("cost_hints", "lane_ids"):
        val = getattr(fn, attr, None)
        if val is not None:
            setattr(step, attr, val)
    return step
