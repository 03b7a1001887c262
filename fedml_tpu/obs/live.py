"""fedpulse live exporter: streaming round-boundary telemetry.

PRs 4-6 made the observability stack deep but strictly post-hoc: spans,
registries and roofline tables land on disk and are analyzed after the run.
This module is the LIVE half — one process-wide :class:`PulsePlane` that,
at every round boundary, folds the signals the run already produces into
one JSON snapshot appended to ``pulse.jsonl``:

- the unified registry's ``time``/``wire``/``chaos``/``compile`` counter
  lanes (one ``snapshot()`` per namespace — reads, no new instrumentation),
- the latest host-pipeline stage row (``round_stats`` keys),
- the :class:`~fedml_tpu.obs.profile.ClientProfiler` aggregates (clients
  seen, participation fairness, EMA train-ms spread, top-k stragglers,
  staleness, measured store bytes),
- the profiler's fedsketch distribution lanes (train-ms, broadcast→upload
  latency, payload bytes, rounds-behind staleness) as per-round
  p50/p90/p99 + count summaries PLUS the mergeable codec, so per-host
  streams fold into one cross-host distribution after the run,
- fedcost attribution of the FLOP-dominant program against the measured
  round wall (achieved GFLOP/s, MAC-basis MFU and its share of the lane
  ceiling) when ``--cost_attribution`` is on,
- the :class:`~fedml_tpu.obs.health.HealthWatchdog` verdict for the round.

``tools/fedtop.py`` tails the file live; the Prometheus textfile mirror
(``--pulse_prometheus_dir``) re-renders each snapshot as gauges for a
node-exporter-style scraper.

Contracts (the tracer's discipline, restated for the pulse plane):

- **off by default, allocation-free when off**: ``pulse_if_enabled()`` is
  one module-global read returning ``None``; disabled call sites do no
  other work (pinned by tests/test_pulse.py's tracemalloc test);
- **bit-identity**: the plane only READS — counters, clocks, the round
  plan (a pure function of (seed, round)) — so a pulse-on run computes
  exactly the pulse-off weights;
- **atomic appends**: each snapshot is ONE ``os.write`` of one
  newline-terminated JSON line to an ``O_APPEND`` fd, so a concurrent
  tailer never observes a torn line.

Configured per run via ``--pulse_path``/``--health_*``
(:func:`configure_from`, chained from ``tracer.configure_from`` so every
existing entry point picks it up), or directly via :func:`configure` (the
bench enables a profiler-only plane with no stream).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Optional

import numpy as np

from fedml_tpu.obs.health import FederationHealthError, HealthWatchdog
from fedml_tpu.obs.profile import ClientProfiler
from fedml_tpu.obs.registry import default_registry
from fedml_tpu.obs.tracer import tracer_if_enabled

from fedml_tpu.obs.flight import recorder_if_enabled as _flight_recorder

__all__ = [
    "FederationHealthError", "LiveExporter", "PulsePlane", "configure",
    "configure_from", "plane_scope", "pulse_enabled", "pulse_if_enabled",
    "reset", "session_stats",
]

#: registry namespaces exported as pulse "lanes" every snapshot
_LANES = ("time", "wire", "chaos", "compile")

#: process-lifetime stats for the conftest session summary (NEVER reset by
#: configure()/reset() — they describe the session, not one run).
#: ``overhead_pct`` is written by the tier-1 overhead-budget pin via
#: :func:`record_overhead` so the session log carries the measured number.
_SESSION = {"snapshots": 0, "runs": 0, "critical": 0, "last_path": None,
            "overhead_pct": None, "overhead_budget_pct": None}


def record_overhead(pct: float, budget_pct: float) -> None:
    """Record the measured full-plane-on vs plane-off wall delta (percent)
    from the pinned overhead-budget test; conftest prints it as the
    ``[t1] obs-overhead:`` session line for tools/t1_report.py."""
    _SESSION["overhead_pct"] = round(float(pct), 2)
    _SESSION["overhead_budget_pct"] = round(float(budget_pct), 2)


def _round_num(v, nd: int = 3):
    return round(v, nd) if isinstance(v, float) else v


def _prom_name(key: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in key)


class LiveExporter:
    """Append-only ``pulse.jsonl`` writer + optional Prometheus mirror."""

    def __init__(self, path: str, prometheus_dir: Optional[str] = None):
        self.path = os.path.abspath(path)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # O_APPEND + a single write() per snapshot = atomic line appends
        self._fd = os.open(self.path,
                           os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
        self.prometheus_dir = prometheus_dir
        if prometheus_dir:
            os.makedirs(prometheus_dir, exist_ok=True)
        self.snapshots = 0

    def emit(self, snap: dict) -> None:
        line = json.dumps(snap, separators=(",", ":"), default=float) + "\n"
        os.write(self._fd, line.encode())
        self.snapshots += 1
        _SESSION["snapshots"] += 1
        _SESSION["last_path"] = self.path
        if self.prometheus_dir:
            self._write_prom(snap)

    def _write_prom(self, snap: dict) -> None:
        """Textfile-collector mirror: flat gauges, atomically replaced so a
        scraper never reads a half-written file."""
        lines = ["# fedpulse textfile mirror (one scrape = latest round)"]

        def gauge(name: str, v) -> None:
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                return
            if isinstance(v, float) and not np.isfinite(v):
                return
            lines.append(f"fedpulse_{_prom_name(name)} {v:g}")

        gauge("round", snap.get("round"))
        gauge("ts_ms", snap.get("ts_ms"))
        gauge("loss", snap.get("loss"))
        gauge("round_ms", snap.get("round_ms"))
        gauge("cohort", snap.get("cohort"))
        for k, v in (snap.get("rates") or {}).items():
            gauge(k, v)
        for lane, counters in (snap.get("lanes") or {}).items():
            for k, v in counters.items():
                gauge(f"{lane}_{k}", v)
        prof = snap.get("profile") or {}
        gauge("clients_seen", prof.get("clients_seen"))
        gauge("profile_store_bytes", prof.get("store_bytes"))
        gauge("profile_dropped_ids", prof.get("dropped_ids"))
        gauge("participation_gini", (prof.get("participation") or {}).get("gini"))
        gauge("ema_train_ms_p95", (prof.get("ema_train_ms") or {}).get("p95"))
        for lane, s in (snap.get("sketches") or {}).items():
            gauge(f"sketch_{lane}_p50", s.get("p50"))
            gauge(f"sketch_{lane}_p99", s.get("p99"))
            gauge(f"sketch_{lane}_count", s.get("count"))
        cost = snap.get("cost") or {}
        gauge("mfu_mac", cost.get("mfu_mac"))
        gauge("mfu_vs_lane_ceiling", cost.get("mfu_vs_ceiling"))
        health = snap.get("health") or {}
        sev = {"ok": 0, "warn": 1, "critical": 2}.get(health.get("state"), 0)
        lines.append(f"fedpulse_health_severity {sev}")
        tmp = os.path.join(self.prometheus_dir, ".fedpulse.prom.tmp")
        with open(tmp, "w") as f:
            f.write("\n".join(lines) + "\n")
        os.replace(tmp, os.path.join(self.prometheus_dir, "fedpulse.prom"))

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None


class PulsePlane:
    """Profiler + watchdog + exporter behind one round-boundary hook."""

    def __init__(self, exporter: Optional[LiveExporter] = None,
                 profiler: Optional[ClientProfiler] = None,
                 watchdog: Optional[HealthWatchdog] = None,
                 registry=None):
        self.exporter = exporter
        self.profiler = profiler
        self.watchdog = watchdog
        #: registry whose counter lanes each snapshot reads. None (the
        #: default) resolves per call — the calling thread's registry_scope
        #: or the process default. A gateway tenant's plane is PINNED to
        #: that tenant's registry so its snapshots can never pick up another
        #: tenant's counters, whichever thread emits the round.
        self.registry = registry
        #: fedflight scope tag: the gateway pins each lane's plane to its
        #: tenant id so the flight recorder keys that lane's round window
        #: (and any quarantine bundle) to the tenant, never interleaving
        #: another tenant's rounds. None = the default federation scope.
        self.tenant: Optional[str] = None
        self._t_last_ms: Optional[float] = None
        self._round_clients = 0
        self._peak = None
        self._peak_resolved = False
        #: previous round-boundary sketch copies, for the per-round deltas
        self._prev_sketches: dict = {}
        #: fedlens rows accumulated since the last round boundary (sim
        #: stash conversions + edge per-upload stats), folded into the
        #: snapshot's ``learning`` block then cleared
        self._lens_rows: list = []

    # -- feeds ---------------------------------------------------------------

    def observe_upload(self, client_ids, round_idx: int, *,
                       train_ms: Optional[float] = None,
                       upload_bytes: Optional[float] = None,
                       staleness: float = 0.0) -> None:
        """Edge-server per-upload feed (broadcast→aggregate path): attribute
        the worker's observed round latency + payload bytes to its assigned
        logical clients. ``staleness`` is the contribution's version lag on
        the staleness sketch lane — 0 for a sync round's on-time upload
        (the default), ``server_version - trained_version`` for a fedbuff
        fold (the lane the watchdog's version_lag rule reads)."""
        ids = np.atleast_1d(np.asarray(client_ids, np.int64))
        if ids.size == 0:
            return
        self._round_clients += int(ids.size)
        if self.profiler is not None:
            per_client = (None if upload_bytes is None
                          else float(upload_bytes) / ids.size)
            self.profiler.observe(ids, round_idx, train_ms=train_ms,
                                  upload_bytes=per_client)
            # sketch lanes record the UPLOAD-granular values (one sample per
            # contribution, not per assigned logical client)
            self.profiler.observe_wire(upload_ms=train_ms,
                                       payload_bytes=upload_bytes,
                                       staleness=float(staleness))

    def observe_lens(self, client_ids, round_idx: int, *, update_norm,
                     align=None, loss_delta=None) -> None:
        """fedlens per-client learning-signal feed: per-id update norms
        plus (when the path computes them) cosine alignment vs the round
        aggregate and first-to-last-epoch loss deltas. The sim paradigms
        route their device stash here one boundary later under
        ``--async_rounds``; the edge servers feed per-upload stats. Rows
        accumulate until the next :meth:`on_round` folds them into the
        snapshot's ``learning`` block (obs/lens.fold_rows)."""
        ids = np.atleast_1d(np.asarray(client_ids, np.int64))
        if ids.size == 0:
            return
        if self.profiler is not None:
            drift = None if align is None else 1.0 - np.asarray(
                align, np.float64)
            self.profiler.observe_lens(ids, round_idx,
                                       update_norm=update_norm, drift=drift)
        self._lens_rows.append({"ids": ids, "update_norm": update_norm,
                                "align": align, "loss_delta": loss_delta})

    def observe_stale(self, rounds_behind: int) -> None:
        """Stale-contribution feed (the deadline-closed late-upload path):
        record how many rounds behind the dropped upload was on the
        ``staleness`` sketch lane — the tail FedBuff's staleness weighting
        will read; a sync run's lane is all zeros plus these."""
        if self.profiler is not None:
            self.profiler.observe_wire(staleness=max(int(rounds_behind), 0))

    def on_sim_round(self, api, round_idx: int, loss, round_ms: float):
        """Simulation-paradigm feed from the traced ``run_round`` wrapper:
        ask the API which clients the round actually trained
        (``_pulse_cohort`` — the stashed round plan by default, the full
        node set for gossip paradigms) and amortize the round wall per
        client — clients train fused under one vmap there, so no finer
        per-client wall exists."""
        ids = train_ms = None
        try:
            ids = api._pulse_cohort(round_idx)
            if ids is not None and ids.size:
                # amortize the round wall by each client's share of the
                # round's RECORDS when the API can attribute it
                # (_pulse_cohort_shares): a 3x-records client consumed ~3x
                # the materialize + compute, and this is the per-client
                # cost signal the fedsched `speed` policy ranks on. Even
                # split when shares are unavailable.
                shares = getattr(api, "_pulse_cohort_shares",
                                 lambda _ids: None)(ids)
                if shares is None:
                    train_ms = round_ms / float(ids.size)
                else:
                    train_ms = np.asarray(shares, np.float64) * round_ms
        except Exception:
            # a paradigm whose dataset/plan doesn't fit the cohort contract
            # (vertical splits etc.): keep the round snapshot, skip per-client
            ids = None
        try:
            # fedlens stash drain: the lens-armed APIs hand over the
            # round's per-client device stats ONE boundary late under
            # async_rounds (no host sync on the round path); the stash
            # carries its own round index + ids so the lag can never
            # misattribute
            pl = getattr(api, "_pulse_lens", None)
            st = pl(round_idx) if pl is not None else None
            if st is not None:
                lens_round, lens_ids, lens_stats = st
                self.observe_lens(lens_ids, lens_round, **lens_stats)
        except Exception:
            pass
        host_loss = (float(loss)
                     if isinstance(loss, (int, float))
                     and not isinstance(loss, bool) else None)
        return self.on_round(round_idx, source=type(api).__name__,
                             loss=host_loss, round_ms=round_ms,
                             cohort_ids=ids, train_ms_per_client=train_ms)

    # -- the round boundary --------------------------------------------------

    def on_round(self, round_idx: int, *, source: str,
                 loss: Optional[float] = None,
                 round_ms: Optional[float] = None, cohort_ids=None,
                 train_ms_per_client: Optional[float] = None,
                 upload_bytes: Optional[float] = None,
                 extra: Optional[dict] = None) -> dict:
        """Assemble + persist one round snapshot; returns it. Raises
        :class:`FederationHealthError` AFTER the snapshot is written when
        the watchdog escalates."""
        now_ms = time.time() * 1e3
        n_cohort = None
        if cohort_ids is not None:
            ids = np.atleast_1d(np.asarray(cohort_ids, np.int64))
            n_cohort = int(ids.size)
            if self.profiler is not None and ids.size:
                self.profiler.observe(
                    ids, round_idx, train_ms=train_ms_per_client,
                    upload_bytes=(None if upload_bytes is None
                                  else float(upload_bytes) / ids.size))
        if n_cohort is None and self._round_clients:
            n_cohort = self._round_clients
        self._round_clients = 0

        reg = self.registry if self.registry is not None else default_registry()
        lanes = {}
        for ns in _LANES:
            snap = reg.snapshot(ns)
            if snap:
                lanes[ns] = {k: _round_num(v) for k, v in snap.items()}
        wire_view = dict(lanes.get("wire", {}))
        if extra:
            wire_view.update(extra)
            lanes.setdefault("wire", {}).update(
                {k: _round_num(v) for k, v in extra.items()})

        stage_rows = reg.rows("stage")
        stage = None
        if stage_rows and stage_rows[-1].get("round") == round_idx:
            stage = {k: _round_num(v) for k, v in stage_rows[-1].items()}

        profile = (self.profiler.aggregates(round_idx,
                                            include_sketches=False)
                   if self.profiler is not None else None)
        # fedsketch block, from ONE locked copy pass: per-lane cumulative
        # percentile summary, the per-ROUND delta summary (cumulative minus
        # the previous boundary — exact bucket subtraction, the sketch form
        # of the watchdog's delta counter rules), and — only when a stream
        # will actually persist it — the mergeable codec. Sketches are
        # cumulative, so any snapshot alone carries the run-so-far
        # distribution and the LAST one is the whole run — trace_report
        # merges the last snapshot of each per-host stream.
        sketches = None
        if self.profiler is not None:
            copies = self.profiler.sketch_copies()
            if copies:
                sketches = {}
                for lane, cur in copies.items():
                    prev = self._prev_sketches.get(lane)
                    delta = cur if prev is None else cur.since(prev)
                    entry = {**cur.summary(), "round": delta.summary()}
                    if self.exporter is not None:
                        entry["enc"] = cur.encode()
                    sketches[lane] = entry
                self._prev_sketches = copies
            if profile is not None and sketches:
                # the watchdog's skew basis is THIS round's distribution:
                # the cumulative lane conflates time (a compile-heavy round
                # 0 would own the p99 for the next ~100 rounds and false-
                # fire skew on healthy runs). The snapshot's profile block
                # carries the per-round summaries; the cumulative ones live
                # at the snapshot top level, never duplicated.
                profile["sketches"] = {
                    lane: s["round"] for lane, s in sketches.items()}

        # fedlens learning block: fold the rows fed since the last
        # boundary (rank + dedupe, obs/lens.fold_rows). ABSENT — not null —
        # when no lens row arrived, so lens-off snapshots (and every
        # committed golden) stay byte-identical
        learning = None
        if self._lens_rows:
            from fedml_tpu.obs import lens as _lens

            try:
                learning = _lens.fold_rows(self._lens_rows,
                                           _lens.lens_topk())
            except Exception:
                learning = None
            self._lens_rows = []
            if learning is not None and profile is not None:
                # the watchdog's attribution rules read the suspects from
                # the profile view it is handed (same round, same fold)
                profile["lens"] = learning

        events: list = []
        health = None
        if self.watchdog is not None:
            events = self.watchdog.check_round(
                round_idx, loss=loss, round_ms=round_ms, wire=wire_view,
                profile=profile)
            health = {"state": self.watchdog.state, "events": events}
            _SESSION["critical"] += sum(
                1 for e in events if e["severity"] == "critical")
            tr = tracer_if_enabled(0)
            if tr is not None:
                for ev in events:
                    tr.instant("health", cat="health", args=dict(ev))

        rates = None
        if self._t_last_ms is not None and now_ms > self._t_last_ms:
            dt_s = (now_ms - self._t_last_ms) / 1e3
            rates = {"rounds_per_s": round(1.0 / dt_s, 4)}
            if n_cohort:
                rates["clients_per_s"] = round(n_cohort / dt_s, 2)
        self._t_last_ms = now_ms

        snap = {"v": 1, "ts_ms": int(now_ms), "round": int(round_idx),
                "source": source, "loss": loss,
                "round_ms": _round_num(round_ms), "cohort": n_cohort,
                "rates": rates, "lanes": lanes, "stage": stage,
                "profile": profile, "sketches": sketches,
                "cost": self._cost(round_ms), "health": health}
        if learning is not None:
            snap["learning"] = learning
        if self.exporter is not None:
            self.exporter.emit(snap)
        # fedflight: retain the round in the recorder's window AND — when
        # this round's criticals are about to escalate below — dump the
        # incident bundle BEFORE maybe_escalate raises, so the bundle
        # exists by the time FederationHealthError propagates
        rec = _flight_recorder()
        if rec is not None:
            rec.record_round(snap, watchdog=self.watchdog,
                             tenant=self.tenant, events=events)
        if self.watchdog is not None:
            self.watchdog.maybe_escalate(events)
        return snap

    def _cost(self, round_ms: Optional[float]) -> Optional[dict]:
        """fedcost join: the FLOP-dominant attributed program against this
        round's measured wall (1 invocation/round — exact for the default
        one-program-per-round schedules)."""
        from fedml_tpu.obs import cost as _cost

        if not round_ms or not _cost.cost_attribution_enabled():
            return None
        tables = _cost.cost_tables()
        if not tables:
            return None
        rec = max(tables.values(),
                  key=lambda r: r["summary"]["gemm_flops_per_invocation"])
        if not self._peak_resolved:
            import jax

            self._peak = _cost.peak_flops(jax.devices()[0])[0]
            self._peak_resolved = True
        rf = _cost.roofline(rec["summary"], round_ms / 1e3, invocations=1,
                            peak=self._peak)
        return {"program": rec["program"],
                "out_lane_ceiling": rec["summary"].get("out_lane_ceiling"),
                "achieved_gflops_per_sec": rf["achieved_gflops_per_sec"],
                "mfu_mac": rf["mfu_mac"],
                "mfu_vs_ceiling": rf.get("mfu_vs_ceiling")}

    def aggregates(self, round_idx: Optional[int] = None) -> Optional[dict]:
        """End-of-run profiler aggregates (the bench JSON tail block)."""
        return (self.profiler.aggregates(round_idx)
                if self.profiler is not None else None)

    def close(self) -> None:
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None


# -- process-wide hub --------------------------------------------------------

_PLANE: Optional[PulsePlane] = None

#: per-thread plane override (plane_scope): the gateway runs each tenant's
#: handler lane on its own thread under a scope, so the lane's round
#:  boundaries pulse into that tenant's OWN stream/watchdog while the
#: process-wide plane (if any) keeps serving everything else.
_TLS = threading.local()


def pulse_if_enabled() -> Optional[PulsePlane]:
    """Hot-path gate: ``None`` while the pulse plane is off — a thread-local
    attribute read plus one global read, no allocation — else the calling
    thread's scoped plane (``plane_scope``) or the process-wide one."""
    plane = getattr(_TLS, "plane", None)
    return plane if plane is not None else _PLANE


@contextlib.contextmanager
def plane_scope(plane: Optional[PulsePlane]):
    """Route this THREAD's ``pulse_if_enabled()`` to ``plane`` for the
    duration of the block (previous override restored on exit). Other
    threads keep the process-wide plane."""
    prev = getattr(_TLS, "plane", None)
    _TLS.plane = plane
    try:
        yield plane
    finally:
        _TLS.plane = prev


def pulse_enabled() -> bool:
    return _PLANE is not None


def configure(path: Optional[str] = None,
              prometheus_dir: Optional[str] = None, *,
              profile_store: Optional[bool] = None,
              capacity_hint: int = 1024, sketch_alpha: float = 0.01,
              loss_limit: float = 0.0,
              stall_sec: Optional[float] = None, stale_spike: int = 8,
              skew: float = 4.0, version_lag: float = 0.0,
              update_norm: float = 0.0, drift: float = 0.0,
              escalate: bool = False) -> Optional[PulsePlane]:
    """(Re)build the process-wide plane. ``configure(None)`` disables it;
    ``configure(None, profile_store=True)`` builds a profiler-only plane
    with no stream (the bench's mode). Returns the plane (or None)."""
    global _PLANE
    if _PLANE is not None:
        _PLANE.close()
        _PLANE = None
    if profile_store is None:
        profile_store = bool(path)
    if not path and not profile_store:
        return None
    exporter = LiveExporter(path, prometheus_dir) if path else None
    profiler = (ClientProfiler(capacity_hint=capacity_hint,
                               sketch_alpha=sketch_alpha)
                if profile_store else None)
    watchdog = HealthWatchdog(loss_limit=loss_limit, stall_sec=stall_sec,
                              stale_spike=stale_spike, skew=skew,
                              version_lag=version_lag,
                              update_norm=update_norm, drift=drift,
                              escalate=escalate)
    # delta rules start from the registry's CURRENT totals: an earlier
    # federation's wire anomalies in this process are not this run's
    watchdog.baseline(default_registry().snapshot("wire"))
    _PLANE = PulsePlane(exporter=exporter, profiler=profiler,
                        watchdog=watchdog)
    if exporter is not None:
        _SESSION["runs"] += 1
    return _PLANE


_NO_PULSE = object()


def configure_from(config) -> bool:
    """Configure from a FedConfig-shaped object (chained from
    ``tracer.configure_from`` so every entry point makes the one call).
    Same semantics as the tracer: ``pulse_path`` is authoritative — unset
    DISABLES a plane left on by an earlier run in the process; only a
    config without the attribute at all leaves the plane untouched."""
    # the lens arms from its own flag, not pulse_path: chained FIRST so
    # --lens on is honored by every entry point even when no pulse stream
    # is configured (the fedlint config-flag-drift contract)
    from fedml_tpu.obs import lens as _lens

    _lens.configure_from(config)
    path = getattr(config, "pulse_path", _NO_PULSE)
    if path is _NO_PULSE:
        return pulse_enabled()
    if not path:
        if pulse_enabled():
            configure(None)
        return False
    configure(path,
              prometheus_dir=getattr(config, "pulse_prometheus_dir", None),
              sketch_alpha=getattr(config, "sketch_alpha", 0.01),
              loss_limit=getattr(config, "health_loss_limit", 0.0),
              stall_sec=getattr(config, "health_stall_sec", None),
              stale_spike=getattr(config, "health_stale_spike", 8),
              skew=getattr(config, "health_skew", 4.0),
              version_lag=getattr(config, "health_version_lag", 0.0),
              update_norm=getattr(config, "health_update_norm", 0.0),
              drift=getattr(config, "health_drift", 0.0),
              escalate=getattr(config, "health_escalate", False))
    return True


def reset() -> None:
    """Close and drop the plane (tests; never mid-run)."""
    configure(None)


def session_stats() -> dict:
    """Process-lifetime pulse stats (the conftest session summary)."""
    return dict(_SESSION)
