"""fedflight: anomaly-triggered flight recorder + incident bundles.

The observability plane DETECTS trouble (the HealthWatchdog escalates,
the gateway quarantines, the reliable layer declares peers dead) but
until now detection ended in a raised :class:`FederationHealthError`
with only the *sampled* trace stream on disk — and under
``--trace_sample_rate`` the rounds that caused the incident are usually
the rounds the sampler dropped. This module is the black-box recorder:
always-on bounded retrospective buffers plus a triggered dump.

While armed (``--flight_dir``), the recorder retains the last
``--flight_window`` rounds of:

- **full-rate round spans** — a second, per-rank ring beside the
  tracer's event ring (``Tracer._flight_ring``). The PR-10 head sampler
  keeps gating what *streams* to the trace files; the flight ring
  receives EVERY event, including those of sampled-out rounds (which
  emit through a shadow tracer that writes only here). Ring bound:
  ``flight_window * EVENTS_PER_ROUND`` events per rank, so a weeks-long
  run degrades to keep-latest instead of OOM.
- **pulse snapshots** — the per-round dicts the pulse plane assembles
  (registry counter lanes, per-round sketch deltas via ``Sketch.since``,
  profiler aggregates, the watchdog verdict), ring-keyed per scope
  (tenant or the default federation) so a gateway tenant's incident
  never interleaves another tenant's rounds.
- **watchdog state transitions** — each snapshot carries
  ``health.state``; the bundle's ``watchdog.json`` is the structured
  :meth:`~fedml_tpu.obs.health.HealthWatchdog.incident` view (rule,
  round, counter deltas vs the run baseline).

Triggers (armed by the ``--flight_on`` comma list):

==============  ============================================================
``escalate``    watchdog escalation — the pulse plane records the round and
                triggers *before* ``maybe_escalate`` raises (live.py), so
                the bundle exists when FederationHealthError propagates
``quarantine``  gateway lane escalation/crash — tenant-scoped bundle via
                the lane's pinned plane (``PulsePlane.tenant``)
``peer_dead``   reliable-layer first-death of a peer (retry budget
                exhausted; comm/reliable.py's off-lock gave-up hook)
``manual``      ``obs.flight.trigger()`` or SIGUSR2
==============  ============================================================

The incident id is PURE in ``(seed, round, rule)`` — the same splitmix64
chain the head sampler uses — so every rank (and every host, and the
re-run) derives the SAME ``incident-<id>`` name with no coordination:
cross-rank capture rides a fire-and-forget ``MSG_TYPE_FLIGHT_DUMP``
control broadcast (the edge servers send it before re-raising; each send
is individually try/excepted and nothing waits for acks, so a dead peer
bounds the flush at the transport's send deadline instead of hanging
teardown), and per-process ranks dump into the same bundle directory by
name alone. Dumps are idempotent per (incident, rank).

Bundle layout (``incident-<id>/``)::

    manifest.json       id, rule, round, trigger kind, tenant, seed,
                        chaos_seed, env versions, the sanitized config,
                        the EXACT replay command, file inventory
                        (written LAST, atomically — its presence is the
                        completeness marker tools/fedpost.py keys on)
    ring-rank<r>.jsonl  per-rank full-rate flight-ring dump
    trace-merged.jsonl  all rings merged on the wall-µs timebase
    rounds.jsonl        windowed round records + per-round lane deltas
                        (+ the fedlens ``learning`` lane — suspects and
                        all — when ``--lens on`` armed the run)
    pulse-tail.jsonl    the raw recent pulse snapshots (fedtop shape)
    watchdog.json       the structured watchdog.incident() view
    cost.json           fedcost tables, when present

Contracts (the tracer's discipline, restated):

- off by default and **allocation-free when off**: call sites gate
  through :func:`recorder_if_enabled` (one module-global read returning
  ``None``) and the tracer's hot path sees one ``_flight_ring is None``
  attribute check (pinned by tests/test_flight.py's tracemalloc test);
- **bit-identity**: the recorder only reads what the round already
  produced — snapshots, events, clocks — so a recorder-on run computes
  exactly the recorder-off weights;
- overhead rides the PR-10 ≤5% full-plane budget (re-pinned with the
  recorder on at the 10k-cohort recipe).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

from fedml_tpu.obs import tracer as _tracer

__all__ = [
    "DEFAULT_TRIGGERS", "EVENTS_PER_ROUND", "FlightRecorder", "configure",
    "configure_from", "flight_enabled", "handle_dump_message", "incident_id",
    "last_incident", "recorder_if_enabled", "replay_command", "reset",
    "session_stats", "trigger",
]

#: trigger inventory (the --flight_on vocabulary)
DEFAULT_TRIGGERS = ("escalate", "quarantine", "peer_dead", "manual")

#: flight-ring sizing: events retained per rank = window * this. A
#: round-scale span tree is the round span + per-message send/recv pairs
#: + pipeline stages + health/counter instants; the busiest edge rounds
#: in the tree emit O(10) events per worker per round, so 512 covers a
#: 32-worker federation's round ~1.5x over. Deliberately generous —
#: at ~200 B/event the window-8 default holds 4096 events ≈ 0.8 MB/rank.
EVENTS_PER_ROUND = 512

#: process-lifetime stats for the conftest session summary (NEVER reset —
#: they describe the session, not one run; a green tier-1 run expects 0)
_SESSION = {"incidents": 0, "last_bundle": None}

_M64 = (1 << 64) - 1


def incident_id(seed: int, round_idx: int, rule: str) -> str:
    """Deterministic incident id: the head sampler's splitmix64 chain over
    ``(seed, round, rule)``. Pure — no clocks, no RNG state — so every
    rank, host and replay derives the same 16-hex id for one incident and
    per-process dumps converge on one bundle directory by name alone."""
    rule_key = int.from_bytes(
        rule.encode("utf-8", "replace")[:8].ljust(8, b"\0"), "little")
    h = _tracer._splitmix64(int(seed) & _M64)
    h = _tracer._splitmix64(h ^ (int(round_idx) & _M64))
    h = _tracer._splitmix64(h ^ rule_key)
    return f"{h:016x}"


def _jsonable(v) -> bool:
    if isinstance(v, (str, int, float, bool)) or v is None:
        return True
    if isinstance(v, (list, tuple)):
        return all(_jsonable(x) for x in v)
    if isinstance(v, dict):
        return all(isinstance(k, str) and _jsonable(x) for k, x in v.items())
    return False


def replay_command(config: dict, *, seed: int = 0, chaos_seed: int = 0,
                   algorithm: Optional[str] = None) -> str:
    """The exact command reproducing the incident run: the unified launcher
    plus every flag whose value differs from the FedConfig default, with
    the determinism keys (seed, chaos_seed) always pinned. Purity of the
    run in (seed, chaos_seed, flags) — the BlazeFL replay argument — is
    what turns the bundle into a *reproducible* incident."""
    from fedml_tpu.core.config import FedConfig

    base = FedConfig().to_dict()
    parts = ["python", "-m", "fedml_tpu.experiments.run"]
    if algorithm:
        parts += ["--algorithm", str(algorithm)]
    for k in sorted(config or {}):
        if k not in base or k in ("seed", "chaos_seed"):
            continue
        v = config[k]
        if v == base[k] or v is None or not _jsonable(v):
            continue
        if isinstance(v, bool):
            v = int(v)
        parts += [f"--{k}", str(v)]
    parts += ["--seed", str(int(seed)), "--chaos_seed", str(int(chaos_seed))]
    return " ".join(parts)


class FlightRecorder:
    """Bounded retrospective buffers + the triggered bundle dump."""

    def __init__(self, flight_dir: str, *, window: int = 8,
                 triggers=DEFAULT_TRIGGERS, seed: int = 0,
                 chaos_seed: int = 0, config_dict: Optional[dict] = None,
                 algorithm: Optional[str] = None):
        self.flight_dir = os.path.abspath(flight_dir)
        self.window = max(int(window), 1)
        self.triggers = frozenset(
            t.strip() for t in (triggers.split(",")
                                if isinstance(triggers, str) else triggers)
            if t and t.strip())
        self.seed = int(seed)
        self.chaos_seed = int(chaos_seed)
        self.config = dict(config_dict or {})
        self.algorithm = algorithm
        self._lock = threading.Lock()
        #: scope ("default" or a tenant id) -> deque of recent pulse snaps
        self._rounds: dict = {}
        #: (process, rank) -> the full-rate flight event ring handed to
        #: that rank's tracer (tracer._emit appends; we only ever read)
        self._rings: dict = {}
        #: incident id -> bundle path (idempotence within this process)
        self._done: dict = {}
        self._last: Optional[dict] = None
        os.makedirs(self.flight_dir, exist_ok=True)

    # -- capture (the always-on cheap half) --------------------------------

    def ring_for(self, rank: int, process: int = 0) -> deque:
        """The (process, rank) flight ring, created on first use — the
        tracer attaches this beside its own event ring."""
        key = (int(process), int(rank))
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                ring = self._rings[key] = deque(
                    maxlen=self.window * EVENTS_PER_ROUND)
            return ring

    def record_round(self, snap: dict, *, watchdog=None,
                     tenant: Optional[str] = None,
                     events: Optional[list] = None) -> None:
        """Round-boundary feed from the pulse plane: retain the snapshot in
        the scope's window ring, then — when the round's events carry a
        critical and the watchdog would escalate — trigger the dump HERE,
        before ``maybe_escalate`` raises (the dump-before-raise ordering
        the acceptance contract pins)."""
        scope = tenant if tenant is not None else "default"
        with self._lock:
            ring = self._rounds.get(scope)
            if ring is None:
                ring = self._rounds[scope] = deque(maxlen=self.window)
            ring.append(snap)
        if not events or watchdog is None or not watchdog.escalate:
            return
        critical = [e for e in events if e["severity"] == "critical"]
        if not critical:
            return
        kind = "quarantine" if tenant is not None else "escalate"
        self.trigger(critical[0]["rule"], snap.get("round", 0), kind=kind,
                     reason=critical[0]["detail"], tenant=tenant,
                     watchdog=watchdog)

    # -- the trigger -------------------------------------------------------

    def trigger(self, rule: str, round_idx: int, *, kind: str = "manual",
                reason: str = "", tenant: Optional[str] = None,
                watchdog=None, incident: Optional[str] = None
                ) -> Optional[str]:
        """Dump an incident bundle; returns its path (or None when the
        trigger ``kind`` is not armed by --flight_on). Idempotent: a
        second trigger resolving to the same incident id returns the
        existing bundle. ``incident`` overrides the derived id — the
        cross-rank dump handler passes the broadcast id verbatim so a
        worker whose config drifted can never fork the bundle."""
        if incident is None and kind not in self.triggers:
            return None
        iid = incident or incident_id(self.seed, int(round_idx), rule)
        with self._lock:
            done = self._done.get(iid)
        if done is not None:
            return done
        path = self._dump(iid, rule, int(round_idx), kind=kind,
                          reason=reason, tenant=tenant, watchdog=watchdog)
        with self._lock:
            self._done[iid] = path
            self._last = {"id": iid, "rule": rule, "round": int(round_idx),
                          "kind": kind, "tenant": tenant, "bundle": path}
        _SESSION["incidents"] += 1
        _SESSION["last_bundle"] = path
        return path

    def last_incident(self) -> Optional[dict]:
        with self._lock:
            return dict(self._last) if self._last else None

    # -- the dump ----------------------------------------------------------

    def _dump(self, iid: str, rule: str, round_idx: int, *, kind: str,
              reason: str, tenant: Optional[str], watchdog) -> str:
        ddir = os.path.join(self.flight_dir, f"incident-{iid}")
        os.makedirs(ddir, exist_ok=True)

        with self._lock:
            rings = {k: list(r) for k, r in self._rings.items()}
            scope = tenant if tenant is not None else "default"
            snaps = list(self._rounds.get(scope, ()))

        merged = []
        for (process, rank), events in sorted(rings.items()):
            name = (f"ring-p{process}-rank{rank}.jsonl" if process
                    else f"ring-rank{rank}.jsonl")
            self._write_jsonl(os.path.join(ddir, name), events)
            merged.extend(events)
        merged.sort(key=lambda ev: ev.get("ts", 0))
        self._write_jsonl(os.path.join(ddir, "trace-merged.jsonl"), merged)

        self._write_jsonl(os.path.join(ddir, "pulse-tail.jsonl"), snaps)
        self._write_jsonl(os.path.join(ddir, "rounds.jsonl"),
                          self._round_records(snaps))

        wd = None
        if watchdog is not None:
            try:
                wd = watchdog.incident()
            except Exception:
                wd = None
        self._write_json(os.path.join(ddir, "watchdog.json"),
                         wd or {"rule": rule, "round": round_idx,
                                "detail": reason})

        # fedcost context, when that plane ran this process
        try:
            from fedml_tpu.obs import cost as _cost

            tables = _cost.cost_tables()
            if tables:
                safe = {k: v for k, v in tables.items() if _jsonable(v)}
                if safe:
                    self._write_json(os.path.join(ddir, "cost.json"), safe)
        except Exception:
            pass

        # manifest LAST (atomic replace): its presence marks the bundle
        # complete — fedpost exits 1 on a directory that lacks it
        manifest = {
            "v": 1, "id": iid, "rule": rule, "round": round_idx,
            "kind": kind, "reason": reason, "tenant": tenant,
            "ts_ms": int(time.time() * 1e3),
            "seed": self.seed, "chaos_seed": self.chaos_seed,
            "window": self.window,
            "env": self._env_versions(),
            # self.config is the plain flag DICT captured at configure
            # time, not a FedConfig — .items() is dict iteration, not a
            # flag read  # fedlint: disable=config-flag-drift
            "config": {k: v for k, v in self.config.items()
                       if _jsonable(v)},
            "replay_cmd": replay_command(
                self.config, seed=self.seed, chaos_seed=self.chaos_seed,
                algorithm=self.algorithm),
        }
        manifest["files"] = sorted(
            set(os.listdir(ddir)) | {"manifest.json"})
        self._write_json(os.path.join(ddir, "manifest.json"), manifest)
        return ddir

    def _round_records(self, snaps: list) -> list:
        """Compact windowed round records with per-round counter-lane
        deltas (cumulative lane minus the previous retained round's — the
        registry-snapshot-delta view fedpost's verdict reads)."""
        out = []
        prev_lanes: dict = {}
        for snap in snaps:
            lanes = snap.get("lanes") or {}
            deltas: dict = {}
            for ns, counters in lanes.items():
                prev = prev_lanes.get(ns) or {}
                d = {}
                for k, v in counters.items():
                    if not isinstance(v, (int, float)) or isinstance(v, bool):
                        continue
                    dv = v - prev.get(k, 0)
                    if dv:
                        d[k] = round(dv, 3) if isinstance(dv, float) else dv
                if d:
                    deltas[ns] = d
            prev_lanes = lanes
            health = snap.get("health") or {}
            rec = {
                "round": snap.get("round"), "ts_ms": snap.get("ts_ms"),
                "source": snap.get("source"), "loss": snap.get("loss"),
                "round_ms": snap.get("round_ms"),
                "cohort": snap.get("cohort"),
                "lane_deltas": deltas,
                "state": health.get("state"),
                "events": health.get("events") or [],
            }
            # fedlens lane: keep the per-round suspect attribution in the
            # compact records too, so fedpost's suspects section works from
            # rounds.jsonl alone (pulse-tail.jsonl carries the full snaps)
            learning = snap.get("learning")
            if learning is not None:
                rec["learning"] = learning
            out.append(rec)
        return out

    @staticmethod
    def _env_versions() -> dict:
        env = {"python": sys.version.split()[0]}
        for mod in ("jax", "jaxlib", "numpy"):
            try:
                env[mod] = __import__(mod).__version__
            except Exception:
                env[mod] = None
        return env

    @staticmethod
    def _write_jsonl(path: str, rows: list) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            for row in rows:
                f.write(json.dumps(row, default=float) + "\n")
        os.replace(tmp, path)

    @staticmethod
    def _write_json(path: str, obj) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1, sort_keys=True, default=float)
            f.write("\n")
        os.replace(tmp, path)


# -- process-wide hub --------------------------------------------------------

_RECORDER: Optional[FlightRecorder] = None
_SIGUSR2_INSTALLED = False


def recorder_if_enabled() -> Optional[FlightRecorder]:
    """Hot-path gate: ``None`` while the recorder is off — one module
    global read, no allocation — else the process recorder."""
    return _RECORDER


def flight_enabled() -> bool:
    return _RECORDER is not None


def configure(flight_dir: Optional[str], *, window: int = 8,
              triggers=DEFAULT_TRIGGERS, seed: int = 0, chaos_seed: int = 0,
              config_dict: Optional[dict] = None,
              algorithm: Optional[str] = None) -> Optional[FlightRecorder]:
    """(Re)build the process recorder (``configure(None)`` disarms it) and
    attach/detach the full-rate flight rings on every live tracer plus all
    tracers created later. Returns the recorder (or None)."""
    global _RECORDER
    if not flight_dir:
        _RECORDER = None
        _tracer.set_flight_ring_factory(None)
        return None
    rec = FlightRecorder(flight_dir, window=window, triggers=triggers,
                         seed=seed, chaos_seed=chaos_seed,
                         config_dict=config_dict, algorithm=algorithm)
    _RECORDER = rec
    _tracer.set_flight_ring_factory(rec.ring_for)
    if "manual" in rec.triggers:
        _install_sigusr2()
    return rec


_NO_FLIGHT = object()


def configure_from(config) -> bool:
    """Configure from a FedConfig-shaped object (chained from
    ``tracer.configure_from`` so every entry point makes the one call).
    Same semantics as the tracer/pulse planes: ``flight_dir`` is
    authoritative — unset DISARMS a recorder left on by an earlier run in
    the process; only a config without the attribute leaves it alone."""
    fdir = getattr(config, "flight_dir", _NO_FLIGHT)
    if fdir is _NO_FLIGHT:
        return flight_enabled()
    if not fdir:
        if flight_enabled():
            configure(None)
        return False
    cfg_dict: dict = {}
    to_dict = getattr(config, "to_dict", None)
    if callable(to_dict):
        try:
            cfg_dict = {k: v for k, v in to_dict().items() if _jsonable(v)}
        except Exception:
            cfg_dict = {}
    configure(fdir,
              window=getattr(config, "flight_window", 8),
              triggers=getattr(config, "flight_on",
                               ",".join(DEFAULT_TRIGGERS)),
              seed=getattr(config, "seed", 0),
              chaos_seed=getattr(config, "chaos_seed", 0),
              config_dict=cfg_dict)
    return True


def trigger(rule: str = "manual", round_idx: int = 0, *,
            kind: str = "manual", reason: str = "",
            tenant: Optional[str] = None) -> Optional[str]:
    """Manual trigger: dump a bundle now (None when the recorder is off or
    the kind is not armed). The SIGUSR2 handler routes here."""
    rec = _RECORDER
    if rec is None:
        return None
    return rec.trigger(rule, round_idx, kind=kind, reason=reason,
                       tenant=tenant)


def last_incident() -> Optional[dict]:
    """The most recent incident's {id, rule, round, kind, tenant, bundle}
    — what the edge servers broadcast as MSG_TYPE_FLIGHT_DUMP args."""
    rec = _RECORDER
    return rec.last_incident() if rec is not None else None


def handle_dump_message(msg_params: dict, rank: int = 0) -> Optional[str]:
    """Receiver side of the MSG_TYPE_FLIGHT_DUMP broadcast: flush this
    process's rings into the broadcast incident id's bundle. Idempotent —
    in-process federations share one recorder that already dumped every
    rank, so the handler resolves to the existing bundle; a per-process
    gRPC rank writes its own ring files into the same directory name."""
    from fedml_tpu.comm.message import (
        MSG_ARG_KEY_FLIGHT_ID,
        MSG_ARG_KEY_FLIGHT_ROUND,
        MSG_ARG_KEY_FLIGHT_RULE,
    )

    rec = _RECORDER
    if rec is None:
        return None
    iid = msg_params.get(MSG_ARG_KEY_FLIGHT_ID)
    if not iid:
        return None
    return rec.trigger(str(msg_params.get(MSG_ARG_KEY_FLIGHT_RULE, "remote")),
                       int(msg_params.get(MSG_ARG_KEY_FLIGHT_ROUND, 0) or 0),
                       kind="remote", reason=f"flight_dump received on "
                       f"rank {rank}", incident=str(iid))


def _install_sigusr2() -> None:
    """Best-effort SIGUSR2 -> manual trigger (main thread only; platforms
    without the signal, or handler installation from a worker thread,
    silently skip — the in-process trigger() path always works)."""
    global _SIGUSR2_INSTALLED
    if _SIGUSR2_INSTALLED:
        return
    try:
        import signal

        def _on_sigusr2(signum, frame):  # pragma: no cover - signal path
            trigger("sigusr2", 0, kind="manual", reason="SIGUSR2")

        signal.signal(signal.SIGUSR2, _on_sigusr2)
        _SIGUSR2_INSTALLED = True
    except Exception:
        pass


def reset() -> None:
    """Disarm and drop the recorder (tests; never mid-run). Chained from
    ``tracer.reset()``. Session stats survive — they describe the
    process, not one run."""
    configure(None)


def session_stats() -> dict:
    """Process-lifetime flight stats (the conftest ``[t1] incidents:``
    session line)."""
    return dict(_SESSION)
