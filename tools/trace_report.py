#!/usr/bin/env python
"""trace_report: merge per-rank fedtrace files into one cross-rank round
timeline and analyze it.

Input: a ``--trace_dir`` directory of per-rank trace files written by
fedml_tpu/obs — ``trace-rank<r>.jsonl`` (single host) and/or
``trace-p<p>-rank<r>.jsonl`` (one per HOST under jax.distributed; copy all
hosts' files into one directory to analyze a real multi-host run). Events
carry wall-clock µs timestamps, so per-host files align on the shared
timebase; when multiple hosts are present, ranks are reported as
``p<process>/r<rank>`` labels.

The analyzer reconstructs causality the same way the tracer recorded it:
every traced protocol send carries a message uid in its envelope, the recv
span that handled it carries the same uid, so each wire edge — through the
local/grpc/mqtt transports AND the reliable/chaos middleware, retransmits
collapsed onto their logical message — is one (send span, recv span) pair.
Mesh (in-mesh cross-silo / gossip) rounds have no wire legs; their
decomposition comes from the fedscope device spans instead: ``mesh_step``
per-round device dispatch and the ``fedml/round/build`` set-up spans (cat
``round``, name ``build``; ``program`` and ``phase=construct|first_call``).

Report sections:
- round timeline: wall-clock per round with per-rank presence,
- critical path: per round — the slowest broadcast->train->upload->aggregate
  chain through the span graph for edge rounds (which worker, where the
  time went), or the device-step decomposition for mesh rounds,
- straggler ranking: per-rank mean end-to-end contribution,
- compile accounting: program builds / first-call (trace+XLA) time per
  program name, LRU hit/miss counters from the registry snapshots,
- cost attribution (fedcost, ``--cost_attribution`` runs): per program the
  static GEMM/lane-fill table's ceiling and top ops, plus achieved-FLOP/s
  (and MFU on TPU) against measured device spans / round walls,
- device memory: per-rank high-water of the round-boundary sampler lane,
- wire anomalies: retransmits / gave_up / dup_dropped / chaos counters,
- overlap_frac per round (host pipeline stage counters, where present),
- per-client profiles (fedpulse join): when a ``pulse.jsonl`` sits beside
  the trace files (a run with BOTH ``--trace_dir`` and ``--pulse_path``
  pointing into the same directory), the straggler story extends below
  rank granularity — the profiler's per-client EMA train-ms ranking,
  participation fairness, and the stream's health verdict join the
  per-rank causal-chain ranking. Absent the file, the report (and every
  existing golden) is unchanged,
- distribution sketches (fedsketch): every ``pulse*.jsonl`` stream in the
  directory contributes its last snapshot's mergeable lane encodings
  (sketches are run-cumulative); the lanes fold ACROSS hosts with the
  exact order-independent merge, so a multi-host run's p50/p90/p99
  train-ms / upload-latency / payload / staleness — and, on lens-armed
  runs (``--lens on``), the fedlens ``update_norm`` / ``drift`` learning
  lanes — read as one distribution. Streams without sketches add
  nothing; a lane that fails to decode (an unknown or corrupt encoding
  from a newer/older host) is skipped with a stderr note, never an exit
  code change.

``--incident <bundle>`` swaps the input for a fedflight ``incident-<id>/``
bundle: the per-rank flight-ring dumps (full-rate capture of the last
``--flight_window`` rounds, regardless of ``--trace_sample_rate``) feed the
same merge + critical-path machinery, the bundle's ``pulse-tail.jsonl``
feeds the fedpulse/fedsketch joins, and the report is headed by the
incident's id/rule/round from the manifest. Windowed rings legitimately
truncate the oldest round, so expect (and read past) boundary anomalies.

Exit codes: 0 clean; 1 structural anomalies — unclosed spans, rounds
missing on some rank, recv spans with no matching send (span imbalance) —
or wire gave_up; 2 nothing to analyze (no files, or files holding only
registry/counter snapshots with no span graph). ``--perfetto out.json``
exports the merged timeline as Chrome trace_event JSON for Perfetto, with
the device-memory sampler as its own counter lane.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict
from typing import Optional

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_TOOLS_DIR, ".."))
sys.path.insert(0, _TOOLS_DIR)   # fedtop (pulse.jsonl parsing) lives beside us

from fedtop import read_snapshots  # noqa: E402
from fedml_tpu.obs.cost import roofline as cost_roofline  # noqa: E402
from fedml_tpu.obs.export import read_jsonl, write_chrome_trace  # noqa: E402

#: event kinds that constitute a span graph; a file with none of these
#: (e.g. only registry snapshots) is "nothing to analyze", not a clean trace
SPAN_PHASES = ("X", "i", "O")


def load_trace_dir(trace_dir: str) -> list[dict]:
    """All events from every per-(process, rank) file, sorted by timestamp."""
    events: list[dict] = []
    for path in sorted(glob.glob(os.path.join(trace_dir, "trace-*.jsonl"))):
        events.extend(read_jsonl(path))
    events.sort(key=lambda e: e.get("ts", 0))
    return events


def load_incident_bundle(bundle: str) -> list[dict]:
    """All events from a fedflight ``incident-<id>/`` bundle's per-rank
    flight-ring dumps (``ring-rank<r>.jsonl`` / ``ring-p<p>-rank<r>.jsonl``),
    sorted by timestamp. The rings hold the last ``--flight_window`` rounds at
    FULL rate regardless of ``--trace_sample_rate``, so the analysis covers
    exactly the window leading into the incident — expect the oldest round to
    be cut mid-flight and the incident round's spans to stop at the trigger."""
    events: list[dict] = []
    for path in sorted(glob.glob(os.path.join(bundle, "ring-*.jsonl"))):
        events.extend(read_jsonl(path))
    events.sort(key=lambda e: e.get("ts", 0))
    return events


def has_span_events(events: list[dict]) -> bool:
    return any(e.get("ph") in SPAN_PHASES for e in events)


def _args(ev: dict) -> dict:
    return ev.get("args") or {}


def analyze(events: list[dict], expect_ranks: int = 0) -> dict:
    """Structure the merged events; returns the full report dict."""
    # multi-host traces label ranks p<process>/r<rank>; single-host traces
    # keep plain int ranks (the shape every existing consumer pins)
    multi = any(e.get("proc") for e in events)

    def rid(ev: dict):
        r = int(ev.get("rank", 0))
        return f"p{int(ev.get('proc', 0))}/r{r}" if multi else r

    rounds: dict[int, dict[object, dict]] = defaultdict(dict)  # round -> rank -> span
    sends: dict[str, dict] = {}
    recvs: dict[str, dict] = {}
    retransmits: list[dict] = []
    chaos_drops = 0
    unclosed: list[dict] = []
    counters: dict[object, dict] = {}
    stage_rows: dict[int, dict] = {}
    span_by_sid: dict[tuple, dict] = {}
    ranks: set = set()
    # fedscope device/compile lanes
    #: round -> rank -> mesh decomposition (per-rank: a merged multi-host
    #: trace has every host running the same mesh round — summing across
    #: hosts would double-count device time)
    device_rows: dict[int, dict] = {}
    compile_spans: dict[str, dict] = {}   # program name -> {count, ms}
    device_mem: dict[object, dict] = {}   # rank -> series -> high-water
    device_mem_samples = 0
    cost_programs: dict[str, dict] = {}   # fedcost program_cost instants

    for ev in events:
        ph, name = ev.get("ph"), ev.get("name")
        rank = rid(ev)
        if ph != "M":
            ranks.add(rank)
        if ph == "O":
            unclosed.append(ev)
        elif ph == "X":
            if ev.get("sid"):
                span_by_sid[(rank, ev["sid"])] = ev
            if name == "round" and ev.get("cat") == "round":
                r = _args(ev).get("round")
                if r is not None:
                    prev = rounds[int(r)].get(rank)
                    # a re-broadcast round keeps its LAST (authoritative) span
                    if prev is None or ev.get("ts", 0) >= prev.get("ts", 0):
                        rounds[int(r)][rank] = ev
            elif name == "send":
                m = _args(ev).get("mid")
                if m:
                    sends[m] = ev
            elif name == "recv":
                m = _args(ev).get("mid")
                if m:
                    recvs[m] = ev
            elif ev.get("cat") == "device" and name == "mesh_step":
                r = _args(ev).get("round")
                if r is not None:
                    row = device_rows.setdefault(int(r), {}).setdefault(
                        rank, {"device_ms": 0.0, "spans": 0})
                    row["device_ms"] += ev.get("dur", 0) / 1e3
                    row["spans"] += 1
                    if _args(ev).get("path"):
                        row["path"] = _args(ev)["path"]
            elif (name == "build" and ev.get("cat") == "round"
                  and "program" in _args(ev)):
                # obs/compile.timed_build's ONE ring record a build interval
                a = _args(ev)
                stage = {"construct": "build"}.get(a.get("phase"),
                                                   a.get("phase"))
                row = compile_spans.setdefault(
                    f"{a['program']}:{stage}", {"count": 0, "ms": 0.0})
                row["count"] += 1
                row["ms"] += ev.get("dur", 0) / 1e3
        elif ph == "i":
            if name == "retransmit":
                retransmits.append(ev)
            elif name == "chaos_drop":
                chaos_drops += 1
            elif name == "program_cost" and ev.get("cat") == "cost":
                a = _args(ev)
                if a.get("program"):
                    # re-attributions (new shape key) keep the LAST record
                    cost_programs[a["program"]] = a
        elif ph == "C":
            if name == "registry":
                # each flush writes a full CUMULATIVE registry snapshot, so
                # a file holding several flushes must not be summed — keep
                # the per-key high-water mark per rank
                snap = _args(ev).get("values") or {}
                dst = counters.setdefault(rank, {})
                for k, v in snap.items():
                    dst[k] = max(dst.get(k, 0), v)
            elif name == "host_stages":
                r = _args(ev).get("round")
                if r is not None:
                    stage_rows[int(r)] = _args(ev).get("values") or {}
            elif name == "device_mem":
                vals = _args(ev).get("values") or {}
                dst = device_mem.setdefault(rank, {})
                for k, v in vals.items():
                    dst[k] = max(dst.get(k, 0), v)
                device_mem_samples += 1

    # -- structural checks -------------------------------------------------
    anomalies: list[str] = []
    if unclosed:
        for ev in unclosed[:8]:
            anomalies.append(
                f"unclosed span {ev.get('name')!r} on rank {rid(ev)}"
                f" (args={_args(ev)})")
        if len(unclosed) > 8:
            anomalies.append(f"... and {len(unclosed) - 8} more unclosed spans")
    round_ranks = {rk for per in rounds.values() for rk in per}
    for r in sorted(rounds):
        missing = round_ranks - set(rounds[r])
        if missing:
            anomalies.append(
                f"round {r} missing on rank(s) {sorted(missing)}")
    orphan_recvs = [m for m in recvs if m not in sends]
    if orphan_recvs:
        anomalies.append(
            f"span imbalance: {len(orphan_recvs)} recv span(s) with no "
            f"matching send (first mid {orphan_recvs[0]})")
    if expect_ranks and len(ranks) < expect_ranks:
        anomalies.append(
            f"expected {expect_ranks} ranks, found {sorted(ranks)}")
    wire_total: dict = {}
    for snap in counters.values():
        for k, v in snap.items():
            wire_total[k] = wire_total.get(k, 0) + v
    # the compile group is process-wide (owned by rank 0): split it out of
    # the wire summary into its own section
    compile_counters = {k.split("/", 1)[1]: v for k, v in wire_total.items()
                        if k.startswith("compile/")}
    wire_total = {k: v for k, v in wire_total.items()
                  if not k.startswith("compile/")}
    if wire_total.get("wire/gave_up", 0):
        anomalies.append(
            f"wire gave_up={wire_total['wire/gave_up']}: message(s) "
            "abandoned after retry exhaustion")

    # -- round timeline + critical path ------------------------------------
    t0 = min((e.get("ts", 0) for e in events if e.get("ph") != "M"),
             default=0)
    # upload lookup for _worker_chain: (worker rank, parent round span) ->
    # send span, so chain walks don't rescan every send per worker
    sends_by_parent = {(rid(s), s["psid"]): s
                       for s in sends.values() if s.get("psid")}
    timeline = []
    stragglers: dict[object, list[float]] = defaultdict(list)
    for r in sorted(rounds):
        per = rounds[r]
        start = min(e["ts"] for e in per.values())
        end = max(e["ts"] + e.get("dur", 0) for e in per.values())
        entry = {
            "round": r,
            "start_ms": round((start - t0) / 1e3, 3),
            "wall_ms": round((end - start) / 1e3, 3),
            "ranks": sorted(per),
            "per_rank_ms": {rk: round(per[rk].get("dur", 0) / 1e3, 3)
                            for rk in sorted(per)},
        }
        # critical path: for every WORKER round span, walk its causal chain
        # (server send -> worker recv -> train -> worker send -> server recv)
        # via the recorded mids/parent ids; the slowest chain is the path.
        chains = {}
        for rk, span in per.items():
            if _args(span).get("role") != "worker":
                continue
            chain = _worker_chain(span, rk, span_by_sid, sends,
                                  sends_by_parent, recvs)
            if chain:
                chains[rk] = chain
        if chains:
            best_rk = max(chains, key=lambda rk: chains[rk]["total_ms"])
            entry["critical_path"] = {"worker_rank": best_rk, **chains[best_rk]}
            for rk, chain in chains.items():
                stragglers[rk].append(chain["total_ms"])
        per_rank_dev = device_rows.get(r)
        if per_rank_dev:
            # critical-path semantics across hosts: the round is gated by
            # the SLOWEST host's device step, not the sum over hosts
            slow_rk = max(per_rank_dev, key=lambda k: per_rank_dev[k]["device_ms"])
            dev = per_rank_dev[slow_rk]
            entry["device"] = {
                "device_ms": round(dev["device_ms"], 3),
                "path": dev.get("path"),
                **({"rank": slow_rk} if len(per_rank_dev) > 1 else {}),
            }
            if "critical_path" not in entry:
                # mesh rounds: no wire legs — the critical path IS the
                # device step (host residual = round wall minus device)
                entry["critical_path"] = {
                    "kind": "mesh",
                    "device_ms": entry["device"]["device_ms"],
                    "host_ms": round(
                        max(entry["wall_ms"]
                            - entry["device"]["device_ms"], 0.0), 3),
                    "path": dev.get("path"),
                }
        if r in stage_rows:
            row = stage_rows[r]
            host = row.get("materialize_ms", 0) + row.get("h2d_ms", 0)
            entry["overlap_frac"] = round(
                max(0.0, 1.0 - row.get("wait_ms", 0) / host), 4) if host > 0 \
                else 0.0
            entry["stages_ms"] = {k: round(v, 3) for k, v in row.items()}
        timeline.append(entry)

    ranking = sorted(
        ({"rank": rk, "mean_chain_ms": round(sum(v) / len(v), 3),
          "rounds": len(v)} for rk, v in stragglers.items()),
        key=lambda x: -x["mean_chain_ms"])

    rep = {
        "ranks": sorted(ranks),
        "rounds": len(rounds),
        "events": len(events),
        "timeline": timeline,
        "straggler_ranking": ranking,
        "wire": {
            **{k: v for k, v in sorted(wire_total.items())},
            "retransmit_instants": len(retransmits),
            "chaos_drop_instants": chaos_drops,
        },
        "anomalies": anomalies,
    }
    if compile_spans or compile_counters:
        rep["compile"] = {
            "counters": compile_counters,
            "spans": {k: {"count": v["count"], "ms": round(v["ms"], 3)}
                      for k, v in sorted(compile_spans.items())},
        }
    if cost_programs:
        # achieved-FLOP/s per program: static GEMM FLOPs per invocation
        # against the MEASURED duration — fedscope device spans for mesh
        # programs (matched by path), the round wall for a sim
        # program when it is unambiguous (exactly one sim program, no
        # device lanes to confuse it with).
        path_ms: dict[str, list] = {}
        for _r, per in device_rows.items():
            # one entry per ROUND per path, slowest rank/host wins — summing
            # over ranks would double-count the same device step in a merged
            # multi-host trace (same critical-path convention as above)
            per_path: dict[str, float] = {}
            for row in per.values():
                if row.get("path"):
                    p = row["path"]
                    per_path[p] = max(per_path.get(p, 0.0), row["device_ms"])
            for p, ms in per_path.items():
                path_ms.setdefault(p, []).append(ms)
        sim_progs = [p for p, a in cost_programs.items() if not a.get("path")]
        achieved: dict[str, dict] = {}
        for pname, a in cost_programs.items():
            s = a.get("summary") or {}
            flops = s.get("gemm_flops_per_invocation") or 0.0
            entry = None
            if a.get("path") and path_ms.get(a["path"]):
                ms = path_ms[a["path"]]
                entry = {"rounds": len(ms),
                         "measured_ms": round(sum(ms), 3),
                         "basis": "device spans"}
            elif (not a.get("path") and len(sim_progs) == 1
                  and timeline and not device_rows):
                walls = [e["wall_ms"] for e in timeline]
                entry = {"rounds": len(walls),
                         "measured_ms": round(sum(walls), 3),
                         "basis": "round wall (host+device)"}
            if entry and flops and entry["measured_ms"] > 0:
                # ONE achieved-FLOP/s / MFU convention (obs/cost.roofline):
                # reimplementing the division here is exactly the drift the
                # shared module exists to prevent
                rf = cost_roofline(s, entry["measured_ms"] / 1e3,
                                   invocations=entry["rounds"],
                                   peak=a.get("peak_bf16_flops"))
                entry["achieved_gflops_per_sec"] = \
                    rf["achieved_gflops_per_sec"]
                if rf["mfu_mac"] is not None:
                    entry["mfu_mac"] = rf["mfu_mac"]
                    if "mfu_vs_ceiling" in rf:
                        entry["mfu_vs_ceiling"] = rf["mfu_vs_ceiling"]
                achieved[pname] = entry
        rep["cost"] = {
            "programs": {
                p: {"shape_key": a.get("shape_key"), "path": a.get("path"),
                    "summary": a.get("summary"),
                    "xla_cost": a.get("xla_cost"),
                    "peak_table_entry": a.get("peak_table_entry")}
                for p, a in sorted(cost_programs.items())},
            "achieved": achieved,
        }
    if device_mem:
        rep["device_mem"] = {
            "samples": device_mem_samples,
            "high_water": {str(rk): dict(sorted(v.items()))
                           for rk, v in device_mem.items()},
        }
    return rep


def load_pulse_streams(trace_dir: str) -> dict:
    """Every ``pulse*.jsonl`` stream in the dir -> {basename: snapshots}.
    A single-host run has one (``pulse.jsonl``, the primary stream the
    client-profiles join reads); a multi-host run flushes one per host
    into the shared directory (any ``pulse*.jsonl`` name). The parsing
    (skip blanks/torn lines, keep round-carrying dicts) is fedtop's
    ``read_snapshots`` — ONE implementation of the JSONL contract, so the
    two tools can never diverge on what they accept."""
    out = {}
    for path in sorted(glob.glob(os.path.join(trace_dir, "pulse*.jsonl"))):
        snaps, _offset = read_snapshots(path)
        if snaps:
            out[os.path.basename(path)] = snaps
    return out


def sketch_section(streams: dict) -> Optional[dict]:
    """Cross-host fedsketch fold: decode each stream's LAST snapshot's lane
    encodings (run-cumulative, so the last snapshot IS the stream) and
    merge per lane. The merge is exact, commutative and order-independent
    (obs/sketch contract), so the result is independent of host order and
    identical to a sketch fed by one process observing everything. The
    reported stream count is the streams that actually CONTRIBUTED a lane
    — a pre-sketch host's stream beside a sketch-carrying one must not
    read as two-host coverage."""
    from fedml_tpu.obs.sketch import Sketch

    lanes: dict = {}          # lane -> [(stream name, Sketch)]
    for name, snaps in streams.items():
        for lane, s in (snaps[-1].get("sketches") or {}).items():
            if not (isinstance(s, dict) and s.get("enc")):
                continue
            try:
                sk = Sketch.decode(s["enc"])
            except (ValueError, KeyError, TypeError):
                # one corrupted encoding must not kill the report — the
                # JSONL layer is torn-line tolerant, this layer matches it
                print(f"trace_report: skipping undecodable sketch "
                      f"'{lane}' in {name}", file=sys.stderr)
                continue
            lanes.setdefault(lane, []).append((name, sk))
    merged = {}
    contributed = set()
    for lane, entries in sorted(lanes.items()):
        # hosts launched with different --sketch_alpha produce unmergeable
        # universes: group per universe and fold the DETERMINISTIC winner
        # (most streams, then most samples, then finest alpha) — never an
        # accident of filename sort order — and only streams whose data is
        # actually IN the fold count toward the reported stream total
        groups: dict = {}
        for name, sk in entries:
            key = (sk.alpha, sk.min_value, sk.max_value)
            groups.setdefault(key, []).append((name, sk))
        win = max(groups, key=lambda k: (len(groups[k]),
                                         sum(s.n for _n, s in groups[k]),
                                         -k[0]))
        skipped = [n for k, v in groups.items() if k != win for n, _s in v]
        if skipped:
            print(f"trace_report: '{lane}' sketches from "
                  f"{sorted(skipped)} use a different universe (hosts ran "
                  "different --sketch_alpha?) — excluded from the merge",
                  file=sys.stderr)
        out = groups[win][0][1].copy()
        for _name, sk in groups[win][1:]:
            out.merge(sk)
        merged[lane] = out.summary()
        contributed.update(n for n, _s in groups[win])
    if not merged:
        return None
    return {"streams": len(contributed), "lanes": merged}


def client_profiles_section(snaps: list) -> dict:
    """The fedpulse join: per-client straggler ranking + fairness from the
    stream's LAST snapshot (profiles are cumulative), health across all."""
    last = snaps[-1]
    critical = sum(1 for s in snaps
                   for e in (s.get("health") or {}).get("events", ())
                   if e.get("severity") == "critical")
    return {
        "snapshots": len(snaps),
        "last_round": last.get("round"),
        "profile": last.get("profile") or {},
        "health_state": (last.get("health") or {}).get("state"),
        "critical_events": critical,
    }


def _worker_chain(round_span: dict, rank, span_by_sid, sends,
                  sends_by_parent, recvs):
    """One worker's causal chain for a round, in ms. Returns None when the
    linkage is incomplete (e.g. an untraced peer)."""
    # the worker round span nests under the recv span of the sync message
    parent = span_by_sid.get((rank, round_span.get("psid")))
    if parent is None or parent.get("name") != "recv":
        return None
    mid_down = _args(parent).get("mid")
    down_send = sends.get(mid_down)
    # the worker's upload: the send span PARENTED BY this round span
    up_send = sends_by_parent.get((rank, round_span.get("sid")))
    up_recv = recvs.get(_args(up_send).get("mid")) if up_send else None
    if down_send is None or up_recv is None:
        return None
    total = (up_recv["ts"] + up_recv.get("dur", 0)) - down_send["ts"]
    return {
        "total_ms": round(total / 1e3, 3),
        "wire_down_ms": round((parent["ts"] - down_send["ts"]) / 1e3, 3),
        "train_ms": round(round_span.get("dur", 0) / 1e3, 3),
        "wire_up_ms": round((up_recv["ts"] - up_send["ts"]) / 1e3, 3),
    }


def format_report(rep: dict) -> str:
    lines = []
    lines.append(f"fedtrace report: {rep['events']} events, "
                 f"{len(rep['ranks'])} rank(s) {rep['ranks']}, "
                 f"{rep['rounds']} round(s)")
    inc = rep.get("incident")
    if inc:
        row = (f"INCIDENT {inc.get('id')}: rule {inc.get('rule')!r} "
               f"at round {inc.get('round')} ({inc.get('kind')})")
        if inc.get("tenant"):
            row += f" tenant {inc['tenant']!r}"
        lines.append(row)
    lines.append("")
    lines.append("round timeline:")
    for e in rep["timeline"]:
        row = (f"  round {e['round']:>3}  start +{e['start_ms']:>9.1f} ms  "
               f"wall {e['wall_ms']:>9.1f} ms  ranks {e['ranks']}")
        if "overlap_frac" in e:
            row += f"  overlap {e['overlap_frac']:.2f}"
        lines.append(row)
        cp = e.get("critical_path")
        if cp and cp.get("kind") == "mesh":
            lines.append(
                f"        critical: device {cp['device_ms']:.1f} ms"
                f" [{cp.get('path')}]"
                f" + host {cp['host_ms']:.1f} ms")
        elif cp:
            lines.append(
                f"        critical: worker {cp['worker_rank']} "
                f"{cp['total_ms']:.1f} ms = down {cp['wire_down_ms']:.1f}"
                f" + train {cp['train_ms']:.1f}"
                f" + up {cp['wire_up_ms']:.1f}")
    if rep["straggler_ranking"]:
        lines.append("")
        lines.append("straggler ranking (mean causal-chain ms, worst first):")
        for s in rep["straggler_ranking"]:
            lines.append(f"  rank {s['rank']!s:>6}  "
                         f"{s['mean_chain_ms']:>9.1f} ms"
                         f"  over {s['rounds']} round(s)")
    cp = rep.get("client_profiles")
    if cp:
        prof = cp.get("profile") or {}
        lines.append("")
        lines.append(
            f"per-client profiles (fedpulse join, {cp['snapshots']} "
            f"snapshot(s) through round {cp['last_round']}):")
        part = prof.get("participation") or {}
        if prof.get("clients_seen"):
            lines.append(
                f"  {prof['clients_seen']} client(s) seen · participation "
                f"mean {part.get('mean', 0):g} / max {part.get('max', 0)} / "
                f"gini {part.get('gini', 0):g}")
        for s in prof.get("stragglers") or []:
            lines.append(f"  client #{s['client']:>8}  "
                         f"{s['ema_ms']:>9.1f} ms EMA"
                         f"  over {s['rounds']} round(s)")
        lines.append(f"  health: {cp.get('health_state') or 'n/a'}, "
                     f"{cp['critical_events']} critical event(s)")
    sk = rep.get("sketches")
    if sk:
        lines.append("")
        lines.append(f"distribution sketches (fedsketch, merged across "
                     f"{sk['streams']} pulse stream(s)):")
        for lane, s in sk["lanes"].items():
            lines.append(
                f"  {lane:<14} p50 {s.get('p50', 0):>10g}  "
                f"p90 {s.get('p90', 0):>10g}  p99 {s.get('p99', 0):>10g}  "
                f"(n={s['count']})")
    costsec = rep.get("cost")
    if costsec:
        lines.append("")
        lines.append("cost attribution (fedcost, static per-op roofline):")
        for pname, p in costsec["programs"].items():
            s = p.get("summary") or {}
            ceil = s.get("out_lane_ceiling")
            head = (f"  {pname}: "
                    f"{(s.get('gemm_flops_per_invocation') or 0) / 1e9:.3f} "
                    f"GFLOP/invocation over {s.get('gemm_ops', 0)} GEMM "
                    f"op(s)")
            if ceil is not None:
                head += f", out-lane ceiling {ceil * 100:.1f}%"
            if s.get("unknown_trip_counts"):
                head += " [trip count unknown for some loops]"
            lines.append(head)
            for o in (s.get("top_ops") or [])[:3]:
                lines.append(
                    f"      {o['kind']} x{o['count']}  "
                    f"M={o['m']} K={o['k']} N={o['n']}"
                    + (f" g={o['groups']}" if o.get("groups", 1) > 1 else "")
                    + f"  fill {o['out_lane_fill'] * 100:.1f}%"
                    f"  {o['flops'] * o['count'] / 1e9:.3f} GFLOP")
            ach = costsec["achieved"].get(pname)
            if ach:
                row = (f"      achieved: "
                       f"{ach['achieved_gflops_per_sec']:.2f} GFLOP/s over "
                       f"{ach['rounds']} round(s) [{ach['basis']}]")
                if ach.get("mfu_mac") is not None:
                    row += (f", mfu {ach['mfu_mac'] * 100:.2f}% = "
                            f"{ach.get('mfu_vs_ceiling', 0) * 100:.0f}% of "
                            f"the lane ceiling")
                lines.append(row)
    comp = rep.get("compile")
    if comp and (comp["counters"] or comp["spans"]):
        c = comp["counters"]
        lines.append("")
        lines.append(
            "compile accounting: "
            f"{c.get('misses', 0)} build(s) / {c.get('hits', 0)} cache "
            f"hit(s), build {c.get('build_ms', 0.0):.1f} ms, first-call "
            f"(trace+XLA) {c.get('first_call_ms', 0.0):.1f} ms")
        for name, row in comp["spans"].items():
            lines.append(f"  {name}: {row['count']} span(s), "
                         f"{row['ms']:.1f} ms")
    dm = rep.get("device_mem")
    if dm:
        lines.append("")
        lines.append(f"device memory (high-water over {dm['samples']} "
                     "round-boundary samples):")
        for rk, series in dm["high_water"].items():
            parts = ", ".join(f"{k}={v / 1e6:.1f} MB"
                              for k, v in series.items())
            lines.append(f"  rank {rk}: {parts}")
    wire = {k: v for k, v in rep["wire"].items() if v}
    if wire:
        lines.append("")
        lines.append("wire summary: " + ", ".join(
            f"{k}={v}" for k, v in sorted(wire.items())))
    lines.append("")
    if rep["anomalies"]:
        lines.append(f"ANOMALIES ({len(rep['anomalies'])}):")
        lines.extend(f"  - {a}" for a in rep["anomalies"])
    else:
        lines.append("no structural anomalies")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", nargs="?",
                    help="directory of trace-rank*.jsonl files")
    ap.add_argument("--incident", metavar="BUNDLE",
                    help="analyze a fedflight incident-<id>/ bundle instead "
                         "of a trace dir: the per-rank flight-ring dumps go "
                         "through the same merge + critical-path machinery")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    ap.add_argument("--perfetto", metavar="OUT",
                    help="also write the merged Chrome trace_event JSON here")
    ap.add_argument("--expect-ranks", type=int, default=0,
                    help="fail unless at least this many ranks are present")
    args = ap.parse_args(argv)
    if bool(args.trace_dir) == bool(args.incident):
        ap.error("exactly one of trace_dir or --incident is required")

    src = args.incident or args.trace_dir
    events = (load_incident_bundle(src) if args.incident
              else load_trace_dir(src))
    if not events:
        kind = "ring-*.jsonl" if args.incident else "trace-*.jsonl"
        print(f"no {kind} events under {src}", file=sys.stderr)
        return 2
    if not has_span_events(events):
        # a run can flush registry snapshots without ever opening a span
        # (e.g. counters-only instrumentation); there is no span graph to
        # analyze, and pretending the trace is "clean" would mask the gap
        print(f"no span events under {src} (only "
              "registry/counter snapshots); nothing to analyze",
              file=sys.stderr)
        return 2
    rep = analyze(events, expect_ranks=args.expect_ranks)
    if args.incident:
        # the bundle's manifest identifies WHAT this window led into; the
        # pulse tail inside the bundle feeds the same joins a trace dir's
        # pulse.jsonl would (the tail file uses the identical JSONL shape)
        man_path = os.path.join(src, "manifest.json")
        if os.path.exists(man_path):
            try:
                with open(man_path, encoding="utf-8") as f:
                    man = json.load(f)
                rep["incident"] = {k: man.get(k) for k in
                                   ("id", "rule", "round", "kind", "tenant")}
            except (OSError, ValueError):
                rep["anomalies"].append("unreadable manifest.json in bundle")
        else:
            rep["anomalies"].append(
                "incomplete bundle: no manifest.json (dump interrupted?)")
    # one parse pass over every pulse*.jsonl: the primary stream feeds the
    # client-profiles join, all streams feed the cross-host sketch fold
    streams = load_pulse_streams(src)
    if args.incident and not streams:
        tail = os.path.join(src, "pulse-tail.jsonl")
        if os.path.exists(tail):
            snaps, _off = read_snapshots(tail)
            if snaps:
                streams = {"pulse.jsonl": snaps}
    pulse = streams.get("pulse.jsonl")
    if pulse:
        # additive join: exit codes and the span-graph sections are
        # untouched — a pulse-less trace dir reports exactly as before
        rep["client_profiles"] = client_profiles_section(pulse)
    if streams:
        merged = sketch_section(streams)
        if merged:
            rep["sketches"] = merged
    if args.perfetto:
        write_chrome_trace(args.perfetto, events)
        rep["perfetto"] = args.perfetto
    print(json.dumps(rep, indent=2) if args.json else format_report(rep))
    return 1 if rep["anomalies"] else 0


if __name__ == "__main__":
    sys.exit(main())
