"""fedtrace: span tracing + unified metrics registry (DESIGN.md §12).

The paper's observability story is rank-0 wandb scalars plus ad-hoc
wall-clock pairs; this package is the reproduction's replacement — the
timing instrumentation FedJAX ships built-in (arXiv:2108.02117) and the
cross-rank visibility FedML Parrot's heterogeneity-aware scheduling
assumes (arXiv:2303.01778):

- :mod:`fedml_tpu.obs.registry` — one process-wide
  :class:`MetricsRegistry`; every counter surface in the tree
  (``RoundTimer`` phase sums, the reliable/chaos wire counters, pipeline
  stage rows) is a :class:`CounterGroup` attached to it, so the existing
  public APIs become *views* over one store instead of four disjoint dicts.
- :mod:`fedml_tpu.obs.tracer` — per-rank span tracer: monotonic
  durations, ring-buffered events, allocation-free when disabled. Its
  ``span(name, **ids)`` is the round path's one span primitive: always a
  ``jax.profiler.TraceAnnotation`` (so any profiler session sees the
  program's ``fedml/...`` spans), and a ring record too under
  ``--trace_dir``; the module also holds the table of span and device-scope
  (``jax.named_scope``) names. Trace
  context piggybacks on ``comm/message.py`` envelopes so send spans stitch
  to recv spans across ranks and transports by message id.
  ``setup_span(name, **ids)`` is the same span for SET-UP (a round driver's
  constructor and its parts, both intervals of every program build): it
  also appends one record (name, start and end on ``time.perf_counter``,
  the set-up span open on the same thread as parent, ``ids``) to the
  always-on, bounded in-memory set-up log, ``setup_log()``, whether or not
  any tracer is on. ``time.perf_counter`` is the clock of
  ``benchmarks/run.py``'s ``Clock`` and of its window, so the log's readers
  (``benchmarks/trace/setup_spans.py`` and the eight set-up metrics over
  it; ``timed_build``'s counters) lay it beside the benchmark's own marks.
- :mod:`fedml_tpu.obs.export` — Perfetto/Chrome ``trace_event`` JSON and
  JSONL exporters; ``tools/trace_report.py`` is the analyzer.
- :mod:`fedml_tpu.obs.compile` (fedscope) — per-program compile telemetry:
  LRU hit/miss counters plus one set-up span around each program's
  construction and one around its first call, and the listener that files
  the compiler's own lower / load events under the set-up span that caused
  them, so compile-vs-execute time is a first-class, regression-testable
  metric and set-up can be told by named part from inside the program.
- :mod:`fedml_tpu.obs.device` (fedscope) — device-memory sampler at round
  boundaries; a "devices" counter lane in the Perfetto export without a
  separate ``--profile_dir`` profiler run.
- :mod:`fedml_tpu.obs.cost` (fedcost) — static per-op roofline
  attribution: every round program built through ``timed_build`` can be
  lowered to HLO and read back as a GEMM table (M/K/N, FLOPs, MXU lane
  fills) with a flop-weighted lane ceiling per program; also the single
  shared peak-FLOPs table behind every MFU number.
- :mod:`fedml_tpu.obs.profile` / :mod:`fedml_tpu.obs.live` /
  :mod:`fedml_tpu.obs.health` (fedpulse) — the LIVE plane: a bounded
  array-backed per-client profile store (EMA train-ms, upload bytes,
  participation, staleness — the signals cohort scheduling and FedBuff
  weighting consume), a ``pulse.jsonl`` streaming exporter of atomic
  round-boundary snapshots (registry lanes, profiler aggregates, cost
  MFU) with an optional Prometheus textfile mirror, and a rule-driven
  health watchdog (NaN/divergent loss, round stall, ``gave_up``/
  ``stale_uploads`` spikes, straggler skew) with an escalate-to-raise
  mode. ``tools/fedtop.py`` tails the stream live.
- :mod:`fedml_tpu.obs.sketch` (fedsketch) — fixed-memory, mergeable
  log-bucketed distribution sketches (~1% relative error, exact
  order-independent merge, compact JSON codec) behind the profiler's
  train-ms / upload-latency / payload-bytes / staleness percentile lanes;
  paired with the tracer's deterministic head-based round sampling
  (``--trace_sample_rate``, a pure function of (seed, round, id)) so
  thousand-client cohorts keep bounded spans while sampled-out rounds
  still feed every sketch.
- :mod:`fedml_tpu.obs.flight` (fedflight, DESIGN.md §21) — the black-box
  recorder: while ``--flight_dir`` is armed, a second per-rank FULL-rate
  span ring (sampled-out rounds included, via a shadow tracer), per-scope
  pulse-snapshot windows and watchdog transitions are retained for the
  last ``--flight_window`` rounds; watchdog escalation (dump BEFORE the
  raise), gateway quarantine, peer death, or SIGUSR2 dumps a
  self-contained ``incident-<id>/`` bundle whose id is pure in
  ``(seed, round, rule)`` — every rank converges on one bundle with no
  coordination. ``tools/fedpost.py`` renders the postmortem verdict.

Tracing is OFF by default and enabled per run via ``--trace_dir``
(core/config.py); the pulse plane likewise via ``--pulse_path``. The
contract: a traced or pulsed run is bit-identical to a plain run — these
modules only ever read clocks and counters.
"""

from fedml_tpu.obs.compile import (compile_counters, model_counters,
                                   record_cache_hit, timed_build)
from fedml_tpu.obs.cost import (
    cost_attribution_enabled,
    cost_tables,
    enable_cost_attribution,
    fwd_flops_per_image,
    peak_flops,
    reset_cost_tables,
)
from fedml_tpu.obs.device import sample_device_memory
from fedml_tpu.obs.flight import (
    FlightRecorder,
    flight_enabled,
    incident_id,
    recorder_if_enabled,
)
from fedml_tpu.obs.health import FederationHealthError, HealthWatchdog
from fedml_tpu.obs.live import (
    LiveExporter,
    PulsePlane,
    plane_scope,
    pulse_enabled,
    pulse_if_enabled,
)
from fedml_tpu.obs.profile import ClientProfiler
from fedml_tpu.obs.registry import (
    CounterGroup,
    MetricsRegistry,
    default_registry,
    registry_scope,
)
from fedml_tpu.obs.sketch import Sketch, merge_all
from fedml_tpu.obs.tracer import (
    Tracer,
    configure,
    configure_from,
    flush_all,
    get_tracer,
    reset,
    set_process_index,
    setup_log,
    setup_span,
    span,
    span_sampled,
    trace_filename,
    tracer_if_enabled,
    tracer_if_sampled,
    tracing_enabled,
)

__all__ = [
    "ClientProfiler",
    "CounterGroup",
    "FederationHealthError",
    "FlightRecorder",
    "HealthWatchdog",
    "LiveExporter",
    "MetricsRegistry",
    "PulsePlane",
    "Sketch",
    "Tracer",
    "compile_counters",
    "model_counters",
    "configure",
    "configure_from",
    "cost_attribution_enabled",
    "cost_tables",
    "default_registry",
    "enable_cost_attribution",
    "flight_enabled",
    "fwd_flops_per_image",
    "incident_id",
    "merge_all",
    "peak_flops",
    "reset_cost_tables",
    "flush_all",
    "get_tracer",
    "plane_scope",
    "pulse_enabled",
    "pulse_if_enabled",
    "record_cache_hit",
    "recorder_if_enabled",
    "registry_scope",
    "reset",
    "sample_device_memory",
    "set_process_index",
    "setup_log",
    "setup_span",
    "span",
    "span_sampled",
    "timed_build",
    "trace_filename",
    "tracer_if_enabled",
    "tracer_if_sampled",
    "tracing_enabled",
]
