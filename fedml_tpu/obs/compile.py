"""Compile telemetry: attribute program-build time per round-program shape.

Every distinct round plan (cohort bucket tuple, packed shape key, super-step
block length) compiles its own XLA program, and a fresh compile of a
flagship round program takes seconds to minutes, not milliseconds.
Before this module that cost was invisible: it landed inside
whichever round happened to trigger the build. :func:`timed_build` makes it
first-class, with ONE timing mechanism, the set-up span
(``obs.setup_span``, ``obs/tracer.py``):

- each build is two ``fedml/round/build`` set-up spans: ``phase=construct``
  around the program CONSTRUCTION (builder() returns the jitted callable
  without compiling, usually sub-ms) and ``phase=first_call`` around the
  first invocation, which is where jax traces and XLA compiles before
  dispatch; both carry ``program``, ``shape_key`` and a packed program's
  lane ids. With ``async_rounds`` the first call still blocks until the
  executable exists (dispatch needs it), so its seconds are trace + lower +
  compile-or-read, the set-up cost, without the tracer ever forcing a
  device sync. A span is a profiler annotation, under ``--trace_dir`` one
  ring record (``tools/trace_report.py``'s compile section reads it), and
  always one record in the set-up log;
- a ``compile`` :class:`CounterGroup` on the default registry accumulates
  ``hits`` / ``misses`` / ``build_ms`` / ``first_call_ms`` (the last two
  also per program name), derived from those records, so the numbers
  exist even in untraced runs (bench.py embeds them in its JSON tail);
- one ``jax.monitoring`` listener, registered when this module is first
  imported, turns the compiler's own events into records of the same log:
  ``fedml/build/lower`` (jaxpr -> MLIR) and ``fedml/build/load`` (the
  backend compile, or the executable's read from the persistent cache:
  ``cache=hit|miss|none``), each with JAX's ``fun_name``, its start and end
  moved from ``time.time`` onto ``time.perf_counter``, the set-up span open
  on its thread as parent and, where code of this package asked for the
  compile, ``by=<module>:<function>`` (the innermost such frame). So a
  build's Python trace is the SELF time of its ``first_call`` record (its
  seconds less the ``lower`` and ``load`` inside it); a compile under a
  ``fedml/setup/*`` span, or with ``by`` and outside a ``first_call``, is a
  helper program of the program's own (an eager op is a program); one with
  neither is the caller's. JAX's ``jaxpr_trace_duration`` events are NOT
  read: one fires for every ``jit`` traced, the inner ones inside the outer
  one's interval.

The set-up log (``obs.setup_log()``) is host memory, bounded (a few
thousand records, the oldest fall off and are counted), written only by
set-up spans and compile events, never by a round's steady path, and on
``time.perf_counter``: the clock of ``benchmarks/run.py``'s ``Clock`` and
``harness/loop.Window``, so a reader (``benchmarks/trace/setup_spans.py``)
cuts it at ``window.t0`` and lays it beside the benchmark's own marks.

The wrapper returned by :func:`timed_build` is numerically transparent: it
forwards ``*args`` untouched and only reads clocks, preserving the
traced == untraced bit-identity contract.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Callable, Optional

from jax import monitoring

from fedml_tpu.obs.registry import CounterGroup, default_registry
from fedml_tpu.obs.tracer import (SPAN_BUILD, SPAN_BUILD_LOAD,
                                  SPAN_BUILD_LOWER, setup_log, setup_span)

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


def _bump(g: CounterGroup, key: str, name: str, by) -> None:
    """``key`` in aggregate and per program name (``<key>.<name>``)."""
    for k in (key, f"{key}.{name}"):
        g[k] = g.get(k, 0) + by


def record_cache_hit(name: str) -> None:
    """One LRU hit: the compiled program was reused, no build happened.
    Attributed both in aggregate and per program name, so a report can say
    which cache is hot vs thrashing."""
    _bump(compile_counters(), "hits", name, 1)


def timed_build(name: str, shape_key, builder: Callable) -> Callable:
    """Run ``builder()`` under compile telemetry; return the built step
    wrapped so its FIRST invocation (where trace + XLA compile happen) is
    timed and attributed too. ``shape_key`` is recorded (repr'd) on the
    spans so a report can say WHICH program shape cost the time."""
    g = compile_counters()
    key = repr(shape_key)
    with setup_span(SPAN_BUILD, program=name, phase="construct",
                    shape_key=key) as built:
        fn = builder()
    # counters bump only once the builder has RETURNED a program: a raising
    # builder propagates with no partial misses/build_ms entry (the caller's
    # LRU never stores the step, so a retry is a fresh build, counted once)
    _bump(g, "misses", name, 1)
    _bump(g, "build_ms", name, built.rec.seconds * 1e3)

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
        with setup_span(SPAN_BUILD, program=name, phase="first_call",
                        shape_key=key, **ids) as called:
            out = fn(*args)
        # only a SUCCESSFUL first call records first_call_ms: a raise
        # propagates, the flag stays set, and the next invocation is timed
        # as the first (the compile genuinely happens on whichever call
        # completes). The span above does close on the failed attempt,
        # deliberately: spans record attempts (the time was truly spent),
        # counters record successful compile accounting, so after a retry
        # the log may carry more first_call records than the counter.
        first[0] = False
        _bump(g, "first_call_ms", name, called.rec.seconds * 1e3)
        # fedcost static attribution (obs/cost): lower the program we just
        # paid to compile and record its per-op roofline table. Pure
        # tracing — no second compile, no sync — and only when enabled.
        from fedml_tpu.obs import cost as _cost

        if _cost.cost_attribution_enabled():
            _cost.attribute_program(name, shape_key, fn, args)
        return out

    # packed programs carry their lane geometry as `.lane_ids`; keep it
    # reachable
    if ids:
        step.lane_ids = ids
    return step


# -- the compiler's own events, as records of the set-up log -----------------

_STAGES = {"/jax/core/compile/jaxpr_to_mlir_module_duration": SPAN_BUILD_LOWER,
           "/jax/core/compile/backend_compile_duration": SPAN_BUILD_LOAD}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
_PACKAGE = __name__.partition(".")[0] + "."
#: per thread: what the persistent cache said since the thread's last load
_cache_said = threading.local()


def _asked_by() -> Optional[str]:
    """``<module>:<function>`` of the innermost frame of this package (this
    module apart) on the stack of the compile being reported, or None where
    the caller's own code asked for it."""
    frame = sys._getframe(2)
    while frame is not None:
        module = frame.f_globals.get("__name__", "")
        if module.startswith(_PACKAGE) and module != __name__:
            return f"{module}:{frame.f_code.co_name}"
        frame = frame.f_back
    return None


def _on_cache_event(event: str, **_) -> None:
    said = _CACHE.get(event)
    if said is not None:
        _cache_said.value = said


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    name = _STAGES.get(event)
    if name is None:
        return
    # JAX stamps these on time.time(); the log's clock is perf_counter
    offset = time.perf_counter() - time.time()
    ids = {"fun_name": kw.get("fun_name")}
    by = _asked_by()
    if by is not None:
        ids["by"] = by
    if name == SPAN_BUILD_LOAD:
        ids["cache"] = getattr(_cache_said, "value", "none")
        _cache_said.value = "none"
    log = setup_log()
    rec = log.new(name, ids)
    rec.t0, rec.t1 = start + offset, end + offset
    log.close(rec)


# once a process (a module is imported once); they fire on compile events alone
monitoring.register_event_time_span_listener(_on_time_span)
monitoring.register_event_listener(_on_cache_event)
