"""Per-rank span tracer with cross-rank causality.

One :class:`Tracer` per rank (in-process federations run many ranks in one
process; the per-rank deployment runs one per OS process). Tracer identity
is ``(process_index, rank)``: under ``jax.distributed`` every HOST process
runs the same mesh loop, so each host tags its events with its process
index and flushes to its own file (``trace-p<p>-rank<r>.jsonl``; process 0
keeps the legacy ``trace-rank<r>.jsonl`` name so single-host traces are
unchanged). ``tools/trace_report.py`` merges the per-host files on the
shared wall-µs timebase. Each tracer records
spans (duration events), instants, and counters into a bounded ring buffer
— monotonic-clock durations, wall-clock timestamps for cross-process
alignment — and flushes to ``<trace_dir>/trace-rank<r>.jsonl``.

Causality across ranks: ``comm/message.py:MSG_ARG_KEY_TRACE_CTX``
piggybacks ``(trace_id, parent span id, message uid)`` on every traced
protocol send
(stamped by ``comm/managers._ManagerBase.send_message``, read back on
dispatch), so the analyzer (tools/trace_report.py) links each send span to
the recv span that handled it BY MESSAGE ID, through every transport and
through the reliable/chaos middleware — a retransmit storm collapses onto
the one logical edge it belongs to.

Deterministic head-based sampling (fedsketch): at thousand-client cohorts
the full-fidelity per-round span volume is the plane's scaling wall, so
``--trace_sample_rate r`` keeps only a reproducible fraction of the ROUND
trees. The keep/drop verdict is :func:`span_sampled` — a pure splitmix64
hash of ``(trace seed, round, client/rank id)``, no RNG state, no clocks —
so every rank (and every host, and every re-run) derives the SAME verdict
for a round: a sampled trace is a consistent subset (no rounds missing
ranks), and two runs with the same seed sample the same rounds. Round-level
call sites gate through :func:`tracer_if_sampled`; sampled-out rounds skip
span emission entirely while counters, pulse snapshots and sketch lanes
still see every round — percentiles stay exact while spans stay bounded.

The round path's ONE span primitive is :func:`span`: a
``jax.profiler.TraceAnnotation`` (the profiler's runtime drops it unless a
profiler session is running, so any ``jax.profiler.trace`` — the
benchmark's traced run, an operator's ``--profile_dir`` — sees the
program's spans with no switch in the program) that, with ``--trace_dir``
set, also records into this tracer's ring. The names of every host span
and of every device scope (``jax.named_scope`` inside the round programs)
are the constants below: one table, read by ``benchmarks/trace/scopes.py``.

Overhead contract (pinned by tests/test_trace.py):

- disabled (the default): ``tracer_if_enabled(rank)`` is a module-global
  flag check returning ``None`` — call sites skip ALL tracing work,
  allocating nothing;
- enabled: one clock read at span open, one at close, one dict append into
  a bounded ``deque`` (old events fall off; a trace can never exhaust
  memory);
- always: the tracer only reads clocks — a traced run's training outputs
  are bit-identical to an untraced run's.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

# -- the names' table --------------------------------------------------------
# Device scopes: ``jax.named_scope`` inside the jitted round programs. They
# are metadata of the HLO (no op is added, no program split) and arrive in
# the profiler trace as components of each op's ``tf_op`` path. Dots inside
# a name, since ``/`` separates the path.
#: stack cast, reshapes, key splits, member gathers, replay tables, opt init
SCOPE_PROLOGUE = "fedml.prologue"
#: the local-training ``lax.scan`` itself: the ``while``'s own time and
#: whatever its body runs outside the five step scopes below; also the
#: ``lax.map`` over lane chunks around it (parallel/packed.make_lanes_train)
SCOPE_STEP = "fedml.step"
#: resets of variables / optimizer state / loss at a client's first step
SCOPE_STEP_RESET = "fedml.step.reset"
#: the batch's order slice and the ``take`` of its records
SCOPE_STEP_GATHER = "fedml.step.gather"
#: forward and backward (``jvp`` / ``transpose(jvp)`` tell them apart)
SCOPE_STEP_TRAIN = "fedml.step.train"
#: the client optimizer: ``tx.update`` + ``apply_updates``
SCOPE_STEP_OPT = "fedml.step.opt"
#: dead-step freeze, loss / weight accumulation, the emit into the sums
SCOPE_STEP_EMIT = "fedml.step.emit"
#: inside ``fedml.step.train``, the parts of a decoder LM's step
#: (models/transformer.py, models/moe.py, core/tasks.py). What carries none
#: of them (norms, rotary, residual adds, the embedding) stays step.train's.
#: attention proper: scores, softmax, values (ops/attention.py's kernels)
SCOPE_LM_ATTN = "fedml.lm.attn"
#: the same in a layer whose queries see a window of keys: the band's work,
#: apart from the full layers' (the kernels inside keep their names)
SCOPE_LM_ATTN_WINDOW = "fedml.lm.attn_window"
#: the delta rule's chunked scan (ops/kda.py): intra-chunk products, the
#: triangular solve, the state's recurrence, the output; its backward too
SCOPE_LM_KDA = "fedml.lm.kda"
#: what a linear-attention mixer does around the scan: short convolutions,
#: SiLU, the L2 norms of q and k, the decay and step gates, the output's
#: norm and gate (its projections are ``fedml.lm.dense``)
SCOPE_LM_KDA_PREP = "fedml.lm.kda_prep"
#: the selective state-space recurrence in chunks (ops/ssd.py): the
#: intra-chunk products, the scan over chunks, the read-out; its backward too
SCOPE_LM_SSD = "fedml.lm.ssd"
#: what a state-space mixer does around it: the convolution and SiLU, the
#: splits and head reshapes, ``softplus``, the gated norm (its two
#: projections are ``fedml.lm.dense``)
SCOPE_LM_SSD_PREP = "fedml.lm.ssd_prep"
#: what compressed convolutional attention adds between its projections and
#: the kernels: the means of ``q`` and ``k``, both convolutions, the L2
#: normalisation with the key temperature, the value shift; its backward
#: too (the projections are ``fedml.lm.dense``, the scores ``fedml.lm.attn``)
SCOPE_LM_CCA_MIX = "fedml.lm.cca_mix"
#: router matmul, selection, sort, the rows' fan-out and weighted add-back
SCOPE_LM_ROUTE = "fedml.lm.route"
#: the grouped matmuls over the rows of the experts held here
SCOPE_LM_EXPERTS = "fedml.lm.experts"
#: a sparse layer's two projections around its routed experts, into their
#: latent and back (``SharedRoutedMoe.latent``); the backward keeps it
SCOPE_LM_LATENT = "fedml.lm.latent_proj"
#: every other matmul: attention projections, shared experts, dense MLP, head
SCOPE_LM_DENSE = "fedml.lm.dense"
#: next-token cross-entropy over the logits
SCOPE_LM_LOSS = "fedml.lm.loss"
#: sums over lanes / clients (the ``psum`` on a mesh), division, cast back
SCOPE_AGGREGATE = "fedml.aggregate"
#: server update hook and the all-failed rollback
SCOPE_SERVER = "fedml.server"

# Host spans (:func:`span`), on the profiler's clock.
SPAN_ROUND = "fedml/round"
#: the host's part of a round before its program: cohort, weights, the lane
#: plan. A packed round's says what the plan's loop does (``round=<index>``,
#: ``steps_planned``: chunks of lanes x the plan's T; ``steps_run``: the
#: steps the chunks walk, parallel/packed.chunk_bounds; ``tree_pass_steps``:
#: the client boundaries on them, PackPlan.tree_pass_steps: reset and emit
#: flags under the chunks' bounds, which in a ONE-lane round are the passes
#: over the parameter tree its program still makes, of two a step)
SPAN_PLAN = "fedml/round/plan"
SPAN_BUILD = "fedml/round/build"
SPAN_ENQUEUE = "fedml/round/enqueue"
SPAN_WAIT_INPUTS = "fedml/round/wait_inputs"
SPAN_MATERIALIZE = "fedml/prefetch/materialize"
SPAN_H2D = "fedml/prefetch/h2d"

# Set-up spans (:func:`setup_span`): the same host spans, and one record
# each in the always-on set-up log (:class:`SetupLog`), on
# ``time.perf_counter``. ``fedml/round/build`` above is one of them.
#: a round driver's whole constructor (``api=<class>``)
SPAN_SETUP_API = "fedml/setup/api"
#: ``ModelBundle.init``: the model's variables from the root key
SPAN_SETUP_INIT = "fedml/setup/init_variables"
#: ``build_local_train``, ``make_eval_fn``, ``init_server_state``
SPAN_SETUP_LOCAL_TRAIN = "fedml/setup/local_train"
#: the client stack put on the device(s), host seconds inside the calls
#: (``device_put`` returns before the copy ends); ``bytes`` put
SPAN_SETUP_PLACE = "fedml/setup/place_data"
#: the compiler's own events (``obs/compile.py``'s listener), children of the
#: set-up span open on their thread: jaxpr -> MLIR of one program ...
SPAN_BUILD_LOWER = "fedml/build/lower"
#: ... and its backend compile, or the read of its executable from the
#: persistent cache (``cache=hit|miss|none``); both carry JAX's ``fun_name``
SPAN_BUILD_LOAD = "fedml/build/load"


def _now_us() -> int:
    # wall-clock µs for CROSS-PROCESS alignment of the per-rank files;
    # durations always come from the monotonic clock below
    return time.time_ns() // 1_000


class _NoopSpan:
    """Singleton returned by a disabled tracer's span() — enter/exit no-ops."""

    __slots__ = ()
    span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("_tr", "name", "cat", "args", "span_id", "parent_id",
                 "_ts_us", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: Optional[dict],
                 parent_id: Optional[int], ann=None):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self.span_id = tr._next_id()
        self.parent_id = parent_id
        self._ts_us = 0
        self._t0 = 0.0
        #: the profiler annotation :func:`span` opens with this ring record
        self._ann = ann

    def set(self, key, value) -> None:
        if self.args is None:
            self.args = {}
        self.args[key] = value

    def __enter__(self):
        tr = self._tr
        stack = tr._stack()
        if self.parent_id is None and stack:
            self.parent_id = stack[-1]
        stack.append(self.span_id)
        if self._ann is not None:
            self._ann.__enter__()
        self._ts_us = _now_us()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur_us = int((time.perf_counter() - self._t0) * 1e6)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tr
        stack = tr._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        tr._emit("X", self.name, self.cat, self._ts_us, dur_us,
                 self.span_id, self.parent_id, self.args)
        return False


class Tracer:
    """Thread-safe per-rank event buffer; see module docstring."""

    def __init__(self, rank: int = 0, buffer_events: int = 65536,
                 trace_id: Optional[str] = None, process: int = 0):
        self.rank = int(rank)
        self.process = int(process)
        self.enabled = True
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        # deque.append is atomic under the GIL; the ring bound makes an
        # unflushed long run degrade to keep-latest instead of OOM
        self._ring: deque = deque(maxlen=int(buffer_events))
        self._ids = iter(range(1, 1 << 62))
        self._id_lock = threading.Lock()
        self._tls = threading.local()
        #: open cross-method spans: key -> (span_id, parent_id, name, cat,
        #: ts_us, t0, args); e.g. the server's round span opens at broadcast
        #: and closes at aggregate, in different handlers
        self._open: dict = {}
        self._open_lock = threading.Lock()
        #: fedflight full-rate retrospective ring (obs/flight.py): when the
        #: flight recorder is armed, every event ALSO lands here — the head
        #: sampler keeps gating what streams, the recorder keeps everything
        #: recent. None (the default) costs one attribute check per emit.
        self._flight_ring = None
        #: lazily-built shadow tracer for sampled-OUT rounds while the
        #: recorder is armed (tracer_if_sampled)
        self._flight_shadow = None

    # -- internals ---------------------------------------------------------
    def _next_id(self) -> int:
        with self._id_lock:
            return next(self._ids)

    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _make_ev(self, ph: str, name: str, cat: str, ts_us: int, dur_us,
                 span_id, parent_id, args) -> dict:
        ev = {"ph": ph, "name": name, "cat": cat, "ts": ts_us,
              "rank": self.rank, "tid": threading.get_ident() & 0xFFFF}
        if self.process:
            # only multi-host events carry the field: single-process traces
            # (and their golden fixtures) keep the exact legacy shape
            ev["proc"] = self.process
        if dur_us is not None:
            ev["dur"] = dur_us
        if span_id:
            ev["sid"] = span_id
        if parent_id:
            ev["psid"] = parent_id
        if args:
            ev["args"] = args
        return ev

    def _emit(self, ph: str, name: str, cat: str, ts_us: int, dur_us,
              span_id, parent_id, args) -> None:
        ev = self._make_ev(ph, name, cat, ts_us, dur_us, span_id,
                           parent_id, args)
        self._ring.append(ev)
        fr = self._flight_ring
        if fr is not None:
            fr.append(ev)

    # -- public API --------------------------------------------------------
    def span(self, name: str, cat: str = "app", args: Optional[dict] = None,
             parent: Optional[int] = None):
        """Context manager tracing a duration event. ``parent`` overrides
        the thread-ambient parent (used to stitch a recv span under the
        sender's context)."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, cat, args, parent)

    def begin_span(self, key, name: str, cat: str = "app",
                   args: Optional[dict] = None) -> int:
        """Open a span that a DIFFERENT handler/thread will close (the
        message-driven round spans). Returns the span id."""
        if not self.enabled:
            return 0
        sid = self._next_id()
        stack = self._stack()
        parent = stack[-1] if stack else None
        with self._open_lock:
            self._open[key] = (sid, parent, name, cat, _now_us(),
                               time.perf_counter(), dict(args or {}))
        return sid

    def end_span(self, key, args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        with self._open_lock:
            rec = self._open.pop(key, None)
        if rec is None:
            return
        sid, parent, name, cat, ts_us, t0, a = rec
        if args:
            a.update(args)
        self._emit("X", name, cat, ts_us,
                   int((time.perf_counter() - t0) * 1e6), sid, parent, a)

    def instant(self, name: str, cat: str = "app",
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        stack = self._stack()
        self._emit("i", name, cat, _now_us(), None, 0,
                   stack[-1] if stack else None, args)

    def counter(self, name: str, values, cat: str = "counter",
                args: Optional[dict] = None) -> None:
        """Counter sample; ``values`` is a number or a {series: number}
        dict (Chrome counter-event semantics)."""
        if not self.enabled:
            return
        v = values if isinstance(values, dict) else {"value": values}
        a = dict(args or {})
        a["values"] = v
        self._emit("C", name, cat, _now_us(), None, 0, None, a)

    def emit_complete(self, name: str, cat: str, ts_us: int, dur_us: int,
                      parent_id: Optional[int] = None,
                      args: Optional[dict] = None) -> int:
        """Emit a complete span with an EXPLICIT placement on the timeline.

        For synthetic attribution spans whose extent was computed, not
        measured around a ``with`` block — e.g. the super-step path amortizes
        one measured device span over its covered rounds by emitting one
        child span per round at ``blk_dur / h`` each. Returns the span id."""
        if not self.enabled:
            return 0
        sid = self._next_id()
        self._emit("X", name, cat, int(ts_us), max(int(dur_us), 0), sid,
                   parent_id, args)
        return sid

    def make_ctx(self, span_id: int) -> list:
        """Wire context for one message: (trace id, parent span id, uid)."""
        return [self.trace_id, int(span_id), uuid.uuid4().hex[:16]]

    def drain(self) -> list[dict]:
        """Atomically take the buffered events (flush consumes them)."""
        out = []
        ring = self._ring
        while True:
            try:
                out.append(ring.popleft())
            except IndexError:
                return out

    def unclosed(self) -> list[dict]:
        """Snapshot of still-open cross-method spans (emitted at flush with
        ph="O" so the analyzer can flag a rank that died mid-round)."""
        with self._open_lock:
            items = list(self._open.items())
        return [{"ph": "O", "name": name, "cat": cat, "ts": ts_us,
                 "rank": self.rank, "sid": sid,
                 **({"proc": self.process} if self.process else {}),
                 **({"psid": parent} if parent else {}),
                 **({"args": a} if a else {})}
                for _k, (sid, parent, name, cat, ts_us, _t0, a) in items]

    def flush(self, path: str, registry=None) -> int:
        """Append drained events (+ a header and a per-rank counter
        snapshot) to ``path`` as JSONL. Returns the event count written."""
        events = self.drain()
        extra = []
        if registry is not None:
            snap = registry.snapshot(rank=self.rank)
            if snap:
                extra.append({"ph": "C", "name": "registry", "cat": "registry",
                              "ts": _now_us(), "rank": self.rank,
                              "args": {"values": snap}})
        extra.extend(self.unclosed())
        if not events and not extra:
            return 0
        header = {"ph": "M", "name": "trace_meta", "rank": self.rank,
                  **({"proc": self.process} if self.process else {}),
                  "ts": _now_us(), "args": {"trace_id": self.trace_id}}
        with open(path, "a") as f:
            for ev in [header, *events, *extra]:
                f.write(json.dumps(ev) + "\n")
        return len(events) + len(extra)


class _FlightShadowTracer(Tracer):
    """Handed out by :func:`tracer_if_sampled` for sampled-OUT rounds while
    the flight recorder is armed: the full public span API, but every
    event lands ONLY in the parent tracer's flight ring — the streamed
    trace keeps the head sampler's reproducible subset while the recorder
    retains everything recent. Span ids come from the parent's counter so
    an incident's merged ring never collides ids with streamed spans of
    neighboring rounds. Cached per parent (``_flight_shadow``), so a
    round's begin_span/end_span pair lands on one ``_open`` table even
    when the two calls re-derive the tracer in different handlers."""

    def __init__(self, parent: Tracer):
        super().__init__(rank=parent.rank, buffer_events=1,
                         trace_id=parent.trace_id, process=parent.process)
        self._parent = parent

    def _next_id(self) -> int:
        return self._parent._next_id()

    def _emit(self, ph, name, cat, ts_us, dur_us, span_id, parent_id,
              args) -> None:
        fr = self._parent._flight_ring
        if fr is None:
            return
        fr.append(self._make_ev(ph, name, cat, ts_us, dur_us, span_id,
                                parent_id, args))


class _DisabledTracer(Tracer):
    """Shared no-op tracer handed out while tracing is off; every public
    entry point early-returns on ``enabled`` before touching state."""

    def __init__(self):
        super().__init__(rank=-1, buffer_events=1, trace_id="disabled")
        self.enabled = False


_DISABLED = _DisabledTracer()

# -- process-wide hub ------------------------------------------------------

_lock = threading.Lock()
_ENABLED = False
_TRACE_DIR: Optional[str] = None
_BUFFER = 65536
_TRACERS: dict[int, Tracer] = {}
_TRACE_ID: Optional[str] = None
#: head-based span sampling: keep fraction + the seed the pure verdict
#: hashes (defaults = keep everything, the pre-fedsketch behavior)
_SAMPLE_RATE = 1.0
_SAMPLE_SEED = 0
#: fedflight hook (obs/flight.py): ``recorder.ring_for`` while the flight
#: recorder is armed — get_tracer attaches the per-(process, rank) flight
#: ring at tracer creation; None (the default) keeps the hot path at one
#: attribute check per emit
_FLIGHT_RING_FACTORY = None


def set_flight_ring_factory(factory) -> None:
    """Install (or, with None, remove) the flight-ring factory and
    re-attach/detach the ring on every LIVE tracer — called by
    ``obs.flight.configure`` so a recorder armed mid-process still
    captures ranks that started tracing earlier."""
    global _FLIGHT_RING_FACTORY
    with _lock:
        _FLIGHT_RING_FACTORY = factory
        for tr in _TRACERS.values():
            tr._flight_ring = (None if factory is None
                               else factory(tr.rank, tr.process))

_M64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """One splitmix64 mixing step — the standard 64-bit finalizer; full
    avalanche, so adjacent (seed, round, id) triples decorrelate."""
    x = (x + 0x9E3779B97F4A7C15) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return (x ^ (x >> 31)) & _M64


def span_sampled(round_idx: int, entity: int = 0, *,
                 rate: Optional[float] = None,
                 seed: Optional[int] = None) -> bool:
    """The head-based keep/drop verdict: a pure function of
    ``(trace seed, round, entity)`` — deterministic across ranks, hosts,
    threads and re-runs; no state is consulted or advanced.

    ``entity`` defaults to 0 so every rank of a federation derives ONE
    shared verdict per round (a sampled trace never has rounds missing
    ranks); pass a client/rank id for finer per-entity span families (the
    FedBuff per-client spans to come)."""
    r = _SAMPLE_RATE if rate is None else float(rate)
    if r >= 1.0:
        return True
    if r <= 0.0:
        return False
    s = _SAMPLE_SEED if seed is None else int(seed)
    h = _splitmix64(s & _M64)
    h = _splitmix64(h ^ (int(round_idx) & _M64))
    h = _splitmix64(h ^ (int(entity) & _M64))
    # top 53 bits -> uniform [0, 1): exact on every platform's float64
    return (h >> 11) * (2.0 ** -53) < r
#: this host's process index under jax.distributed; None = resolve lazily
#: from jax.process_index() at first tracer creation
_PROCESS: Optional[int] = None


def set_process_index(process_index: Optional[int]) -> None:
    """Pin this process's tracer identity (the ``p`` of (process, rank)).

    ``parallel/mesh.init_multihost`` calls this with ``jax.process_index()``
    after joining the cluster; ``None`` restores lazy resolution. Existing
    tracers are NOT retagged — set it before the run starts tracing."""
    global _PROCESS
    with _lock:
        _PROCESS = None if process_index is None else int(process_index)


def _process_index() -> int:
    """Resolved process index (0 outside multi-process runs). Never forces
    backend init: an unpinned index only asks jax when a distributed client
    is already up, so single-process tracing stays jax-init-free."""
    if _PROCESS is not None:
        return _PROCESS
    try:
        import jax

        if jax.distributed.is_initialized():
            return jax.process_index()
    except Exception:  # pragma: no cover - jax always importable here
        pass
    return 0


def configure(trace_dir: Optional[str], buffer_events: int = 65536,
              trace_id: Optional[str] = None,
              sample_rate: float = 1.0, sample_seed: int = 0) -> None:
    """Enable tracing into ``trace_dir`` (None disables). Existing
    per-rank tracers are kept so an in-flight run reconfiguring is safe.
    ``sample_rate``/``sample_seed`` drive :func:`span_sampled`'s
    deterministic head-based round sampling (1.0 = keep every round)."""
    global _ENABLED, _TRACE_DIR, _BUFFER, _TRACE_ID
    global _SAMPLE_RATE, _SAMPLE_SEED
    if not 0.0 <= sample_rate <= 1.0:
        raise ValueError(
            f"sample_rate must be in [0, 1], got {sample_rate}")
    with _lock:
        _TRACE_DIR = trace_dir
        _ENABLED = bool(trace_dir)
        _BUFFER = max(int(buffer_events), 1)
        _TRACE_ID = trace_id or uuid.uuid4().hex[:16]
        _SAMPLE_RATE = float(sample_rate)
        _SAMPLE_SEED = int(sample_seed)
        if _ENABLED:
            os.makedirs(trace_dir, exist_ok=True)


_NO_TRACE_DIR = object()


def configure_from(config) -> bool:
    """Configure from a FedConfig-shaped object; returns whether tracing is
    now enabled. The one call every entry point (train()/run loops) makes —
    the config's ``trace_dir`` is authoritative, so a run with it unset
    DISABLES tracing left on by an earlier run in the same process (its
    events would otherwise append into the previous run's trace files).
    Only a config without the attribute at all leaves tracing untouched."""
    # fedcost, fedpulse and fedflight ride the same entry-point hook: a
    # config carrying cost_attribution / pulse_path / flight_dir configures
    # static roofline attribution, the live telemetry plane and the flight
    # recorder here too
    from fedml_tpu.obs import cost as _cost
    from fedml_tpu.obs import flight as _flight
    from fedml_tpu.obs import live as _live

    _cost.configure_from(config)
    _live.configure_from(config)
    _flight.configure_from(config)
    trace_dir = getattr(config, "trace_dir", _NO_TRACE_DIR)
    if trace_dir is _NO_TRACE_DIR:
        return tracing_enabled()
    if not trace_dir:
        if tracing_enabled():
            configure(None)
        return False
    configure(trace_dir,
              buffer_events=getattr(config, "trace_buffer_events", 65536),
              # the run seed doubles as the trace seed: re-running the same
              # config samples the same rounds (BlazeFL-grade replays)
              sample_rate=getattr(config, "trace_sample_rate", 1.0),
              sample_seed=getattr(config, "seed", 0))
    return True


def tracing_enabled() -> bool:
    return _ENABLED


def get_tracer(rank: int = 0) -> Tracer:
    """The rank's tracer (created on first use), or the shared disabled
    tracer while tracing is off."""
    if not _ENABLED:
        return _DISABLED
    rank = int(rank)
    with _lock:
        tr = _TRACERS.get(rank)
        if tr is None:
            tr = _TRACERS[rank] = Tracer(rank, buffer_events=_BUFFER,
                                         trace_id=_TRACE_ID,
                                         process=_process_index())
            if _FLIGHT_RING_FACTORY is not None:
                tr._flight_ring = _FLIGHT_RING_FACTORY(tr.rank, tr.process)
        return tr


def tracer_if_enabled(rank: int = 0) -> Optional[Tracer]:
    """Hot-path gate: ``None`` while tracing is off — one global read, no
    allocation — else the rank's tracer."""
    if not _ENABLED:
        return None
    return get_tracer(rank)


def tracer_if_sampled(rank: int = 0, round_idx: int = 0) -> Optional[Tracer]:
    """Round-level hot-path gate: ``None`` while tracing is off (one global
    read, nothing allocated — same contract as :func:`tracer_if_enabled`)
    OR while this round is head-sampled out; else the rank's tracer. The
    per-round span call sites (round/mesh_step/prefetch/edge train) gate
    through this so a ``--trace_sample_rate`` run emits a bounded,
    reproducible span subset."""
    if not _ENABLED:
        return None
    if _SAMPLE_RATE < 1.0 and not span_sampled(round_idx):
        # fedflight retroactive capture: while the recorder is armed the
        # sampled-out round still emits — through a shadow tracer whose
        # events land ONLY in the flight ring, never in the stream
        # benign racy read of the arm gate: the factory is installed at
        # configure time before federations start; the worst a torn read
        # costs is one sampled-out round missing from a recorder armed
        # mid-run, never a wrong event  # fedlint: disable=check-then-act
        if _FLIGHT_RING_FACTORY is None:
            return None
        tr = get_tracer(rank)
        if tr._flight_ring is None:
            return None
        shadow = tr._flight_shadow
        if shadow is None:
            shadow = tr._flight_shadow = _FlightShadowTracer(tr)
        return shadow
    return get_tracer(rank)


def _ring_span(tr: Tracer, name: str, ids: dict, ann) -> _Span:
    """``name`` as the ring keeps it (``fedml/round/plan`` is cat ``round``,
    name ``plan``), opened together with the profiler annotation ``ann``."""
    parts = name.split("/")
    parts = parts[1:] or parts
    return _Span(tr, parts[-1], parts[0], ids or None, None, ann)


def span(name: str, **ids):
    """THE round-path span: a context manager around one layer boundary of
    the round driver or the host data path (names: the ``SPAN_*`` table).

    With the tracer off (the default) it is a ``TraceAnnotation(name,
    **ids)`` and nothing else. With ``--trace_dir`` set the same span also
    lands in rank 0's ring (name, start, end, parent, ``ids`` as args),
    following the head-sampling verdict of ``ids["round"]``; the ring keeps
    its ``cat`` / ``name`` split: ``fedml/round`` is (round, round),
    ``fedml/prefetch/h2d`` is (prefetch, h2d). ``ids`` carries
    ``round=<index>`` wherever the call site knows it, so the spans of one
    round share an identifier, also across threads."""
    ann = TraceAnnotation(name, **ids)
    if not _ENABLED:
        return ann
    tr = tracer_if_sampled(0, ids.get("round", 0))
    if tr is None:
        return ann
    return _ring_span(tr, name, ids, ann)


# -- the set-up log ----------------------------------------------------------

class SetupRecord:
    """One interval of set-up on ``time.perf_counter``: a :func:`setup_span`
    or one of the compiler's events. ``parent`` is the ``id`` of the record
    that was open on the same thread when this one started, or None."""

    __slots__ = ("id", "name", "t0", "t1", "parent", "thread", "ids")

    def __init__(self, rec_id: int, name: str, ids: dict):
        self.id = rec_id
        self.name = name
        self.t0 = self.t1 = 0.0
        self.parent: Optional[int] = None
        self.thread = threading.get_ident()
        self.ids = ids

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:
        return (f"SetupRecord({self.id}, {self.name!r}, {self.seconds:.6f} s, "
                f"parent={self.parent}, {self.ids!r})")


class SetupLog:
    """Bounded in-memory log of closed :class:`SetupRecord` s, kept whether
    or not any tracer is on. Set-up's records number tens to a few hundred a
    process and never one a round; past ``cap`` the oldest fall off and are
    counted in ``dropped``."""

    def __init__(self, cap: int = 4096):
        self._records: deque = deque(maxlen=int(cap))
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._records)

    def records(self) -> list:
        """The closed records, oldest first (a copy)."""
        with self._lock:
            return list(self._records)

    def open_stack(self) -> list:
        """This thread's open set-up spans, outermost first (the list
        itself: :class:`_SetupSpan` pushes and pops it)."""
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def new(self, name: str, ids: dict) -> SetupRecord:
        """A record whose parent is the span open on this thread now; not
        in the log until :meth:`close`."""
        rec = SetupRecord(next(self._ids), name, ids)
        stack = self.open_stack()
        if stack:
            rec.parent = stack[-1].id
        return rec

    def close(self, rec: SetupRecord) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(rec)


_SETUP_LOG = SetupLog()


def setup_log() -> SetupLog:
    """The process-wide set-up log (``obs.setup_span`` and the compile
    listener of ``obs/compile.py`` write it; ``timed_build``'s counters and
    ``benchmarks/trace/setup_spans.py`` read it)."""
    return _SETUP_LOG


class _SetupSpan:
    __slots__ = ("_inner", "_name", "_ids", "rec")

    def __init__(self, inner, name: str, ids: dict):
        self._inner = inner
        self._name = name
        self._ids = ids
        #: the span's record, from ``__enter__`` on
        self.rec: Optional[SetupRecord] = None

    def set(self, key, value) -> None:
        """An id learnt inside the span (the bytes a placement put)."""
        self._ids[key] = value
        inner_set = getattr(self._inner, "set", None)
        if inner_set is not None:
            inner_set(key, value)

    def __enter__(self):
        rec = self.rec = _SETUP_LOG.new(self._name, self._ids)
        _SETUP_LOG.open_stack().append(rec)
        self._inner.__enter__()
        rec.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        rec = self.rec
        rec.t1 = time.perf_counter()
        self._inner.__exit__(*exc)
        stack = _SETUP_LOG.open_stack()
        if stack and stack[-1] is rec:
            stack.pop()
        _SETUP_LOG.close(rec)
        return False


def setup_span(name: str, **ids) -> _SetupSpan:
    """A span of SET-UP (names: the ``SPAN_SETUP_*`` table and
    ``SPAN_BUILD``): what :func:`span` is (the profiler annotation, and under
    ``--trace_dir`` ONE ring record, whatever the round sampling says: set-up
    belongs to no round) plus one :class:`SetupRecord` in the always-on
    :func:`setup_log`, with start and end on ``time.perf_counter`` and the
    set-up span open on the same thread as its parent. ``.rec`` is the
    record, ``.set(key, value)`` adds an id learnt inside. Never on a
    round's steady path: a record a round would push set-up's off the log."""
    ann = TraceAnnotation(name, **ids)
    tr = tracer_if_enabled(0)
    inner = ann if tr is None else _ring_span(tr, name, ids, ann)
    return _SetupSpan(inner, name, dict(ids))


def trace_filename(rank: int, process: int = 0) -> str:
    """Per-(process, rank) trace file name. Process 0 keeps the legacy
    single-host name so existing traces and tooling are unchanged; other
    hosts get a distinct file they can write into a SHARED directory
    without clobbering each other."""
    if process:
        return f"trace-p{process}-rank{rank}.jsonl"
    return f"trace-rank{rank}.jsonl"


def flush_all(trace_dir: Optional[str] = None) -> list[str]:
    """Flush every live tracer to its per-(process, rank) file (append),
    including a per-rank counter snapshot from the default registry.
    Returns the paths written."""
    from fedml_tpu.obs.registry import default_registry

    d = trace_dir or _TRACE_DIR
    if not d:
        return []
    os.makedirs(d, exist_ok=True)
    with _lock:
        tracers = list(_TRACERS.values())
    paths = []
    for tr in tracers:
        p = os.path.join(d, trace_filename(tr.rank, tr.process))
        if tr.flush(p, registry=default_registry()):
            paths.append(p)
    return paths


def reset() -> None:
    """Drop all tracers and disable tracing (tests; never mid-run). Also
    tears down the fedpulse plane — a plane leaked across tests would feed
    every later run_round in the process."""
    global _ENABLED, _TRACE_DIR, _TRACE_ID, _PROCESS
    global _SAMPLE_RATE, _SAMPLE_SEED
    with _lock:
        _ENABLED = False
        _TRACE_DIR = None
        _TRACE_ID = None
        _PROCESS = None
        _SAMPLE_RATE = 1.0
        _SAMPLE_SEED = 0
        _TRACERS.clear()
    from fedml_tpu.obs import flight as _flight
    from fedml_tpu.obs import lens as _lens
    from fedml_tpu.obs import live as _live

    _live.reset()
    _flight.reset()
    _lens.reset()
