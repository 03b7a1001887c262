#!/usr/bin/env python
"""fedpost: postmortem analyzer for fedflight incident bundles.

Input: one ``incident-<id>/`` directory written by the flight recorder
(fedml_tpu/obs/flight.py). The bundle is self-contained — manifest,
per-rank full-rate flight-ring dumps, windowed round records, the pulse
tail and the structured watchdog view — so fedpost needs nothing from
the crashed run's environment but the directory itself.

The verdict it renders:

- **what fired** — rule, trigger kind, round, tenant and the watchdog's
  detail line, straight from the manifest + ``watchdog.json``;
- **counter deltas vs baseline** — the watchdog's first-round baseline
  against the wire/registry lanes at the incident, the "what changed"
  summary (``watchdog.json`` ``baseline_deltas``);
- **causal chain** — the per-rank ring dumps go through trace_report's
  merge + critical-path machinery (ONE implementation; fedpost imports
  it rather than re-deriving span causality), yielding the incident
  round's slowest broadcast->train->upload->aggregate chain and the
  straggler attribution across the window;
- **round window** — the retained rounds' loss / wall / health state
  and notable per-round counter-lane deltas (``rounds.jsonl``);
- **suspect clients** — when the run was lens-armed (``--lens on``) the
  round records carry the fedlens ``learning`` lane; fedpost merges the
  per-round suspect rankings across the window (each client keeps its
  worst drift/norm observation) and names the logical client ids most
  likely behind a learning-signal incident — from the bundle alone;
- **replay** — the exact command the manifest carries: the run is pure
  in (seed, chaos_seed, flags), so the command reproduces the incident.

``--markdown`` renders the same verdict as GitHub-flavored markdown for
issue trackers; the default is aligned plain text.

Exit codes: 0 — bundle complete, verdict rendered; 1 — malformed or
incomplete bundle (not a directory, missing/unreadable ``manifest.json``
— the manifest is written LAST and atomically, so its absence means the
dump was interrupted).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_TOOLS_DIR, ".."))
sys.path.insert(0, _TOOLS_DIR)   # trace_report (span machinery) lives beside us

from trace_report import analyze, has_span_events, load_incident_bundle  # noqa: E402


class BundleError(Exception):
    """The bundle cannot be analyzed (malformed or incomplete)."""


def load_bundle(path: str) -> dict:
    """Parse an incident bundle; raises :class:`BundleError` when it is
    not analyzable. The manifest gates everything: it is written last,
    atomically, so a directory without one is an interrupted dump."""
    if not os.path.isdir(path):
        raise BundleError(f"not a bundle directory: {path}")
    man_path = os.path.join(path, "manifest.json")
    if not os.path.exists(man_path):
        raise BundleError(
            "no manifest.json — the dump was interrupted before the "
            "completeness marker was written")
    try:
        with open(man_path, encoding="utf-8") as f:
            man = json.load(f)
    except (OSError, ValueError) as e:
        raise BundleError(f"unreadable manifest.json: {e}")
    if not isinstance(man, dict) or not man.get("id") or "rule" not in man:
        raise BundleError("manifest.json lacks the id/rule identity keys")

    def _opt_json(name):
        p = os.path.join(path, name)
        if not os.path.exists(p):
            return None
        try:
            with open(p, encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    rounds = []
    rp = os.path.join(path, "rounds.jsonl")
    if os.path.exists(rp):
        try:
            with open(rp, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue   # torn line: same tolerance as the stream
                    if isinstance(row, dict):
                        rounds.append(row)
        except OSError:
            pass
    return {
        "path": os.path.abspath(path),
        "manifest": man,
        "watchdog": _opt_json("watchdog.json"),
        "rounds": rounds,
        "events": load_incident_bundle(path),
    }


def build_verdict(b: dict) -> dict:
    """The structured verdict both renderers share."""
    man = b["manifest"]
    wd = b["watchdog"] or {}
    v = {
        "id": man.get("id"),
        "rule": man.get("rule"),
        "round": man.get("round"),
        "kind": man.get("kind"),
        "tenant": man.get("tenant"),
        "reason": man.get("reason") or wd.get("detail") or "",
        "state": wd.get("state"),
        "seed": man.get("seed"),
        "chaos_seed": man.get("chaos_seed"),
        "window": man.get("window"),
        "env": man.get("env") or {},
        "replay_cmd": man.get("replay_cmd"),
        "files": man.get("files") or [],
        "baseline_deltas": wd.get("baseline_deltas") or {},
        "rounds": b["rounds"],
    }
    v["suspects"] = collect_suspects(b["rounds"])
    if has_span_events(b["events"]):
        rep = analyze(b["events"])
        # the incident round's timeline entry when the rings kept it,
        # else the newest retained round — the window may have cut it
        entry = None
        for e in rep["timeline"]:
            if e["round"] == man.get("round"):
                entry = e
        if entry is None and rep["timeline"]:
            entry = rep["timeline"][-1]
        v["chain"] = {
            "events": rep["events"],
            "ranks": rep["ranks"],
            "rounds": rep["rounds"],
            "incident_entry": entry,
            "straggler_ranking": rep["straggler_ranking"],
        }
    else:
        v["chain"] = None
    return v


def collect_suspects(rounds: list) -> list:
    """Merge the fedlens suspect rankings across the retained window:
    each client keeps its WORST observation (highest drift, then highest
    norm — a client that looked fine for five rounds and anti-aligned on
    the sixth is ranked by the sixth), tagged with how many retained
    rounds ranked it. Deterministic: ties break on client id ascending.
    Empty on lens-off bundles — the section is absent and every pre-lens
    golden holds byte-identically."""
    worst: dict = {}
    seen: dict = {}
    for r in rounds:
        for s in (r.get("learning") or {}).get("suspects") or []:
            if not isinstance(s, dict) or "client" not in s:
                continue
            cid = int(s["client"])
            seen[cid] = seen.get(cid, 0) + 1
            key = (s["drift"] if isinstance(s.get("drift"), (int, float))
                   else float("-inf"), float(s.get("norm") or 0.0))
            if cid not in worst or key > worst[cid][0]:
                worst[cid] = (key, s)
    out = []
    for cid, (_, s) in worst.items():
        e = dict(s)
        e["client"] = cid
        e["rounds"] = seen[cid]
        out.append(e)
    out.sort(key=lambda e: (
        -(e["drift"] if isinstance(e.get("drift"), (int, float))
          else float("-inf")),
        -float(e.get("norm") or 0.0), e["client"]))
    return out


def _fmt_suspect(s: dict) -> str:
    row = f"client {s['client']!s:>5}  norm {s.get('norm', 0):g}"
    if s.get("drift") is not None:
        row += f"  drift {s['drift']:g}"
    if s.get("align") is not None:
        row += f"  align {s['align']:g}"
    if s.get("loss_delta") is not None:
        row += f"  dloss {s['loss_delta']:g}"
    return row + f"  in {s['rounds']} round(s)"


def _fmt_chain_entry(e: dict) -> list:
    lines = [f"round {e['round']}: wall {e['wall_ms']:.1f} ms "
             f"across ranks {e['ranks']}"]
    cp = e.get("critical_path")
    if cp and cp.get("kind") == "mesh":
        lines.append(f"critical: device {cp['device_ms']:.1f} ms"
                     f" + host {cp['host_ms']:.1f} ms")
    elif cp:
        lines.append(f"critical: worker {cp['worker_rank']} "
                     f"{cp['total_ms']:.1f} ms = down "
                     f"{cp['wire_down_ms']:.1f} + train {cp['train_ms']:.1f}"
                     f" + up {cp['wire_up_ms']:.1f}")
    return lines


def _round_rows(v: dict) -> list:
    rows = []
    for r in v["rounds"]:
        criticals = [e.get("rule") for e in (r.get("events") or [])
                     if e.get("severity") == "critical"]
        loss = r.get("loss")
        wall = r.get("round_ms")
        row = (f"round {r.get('round')!s:>4}  "
               f"loss {loss:.4f}  " if isinstance(loss, (int, float))
               else f"round {r.get('round')!s:>4}  loss n/a     ")
        if isinstance(wall, (int, float)):
            row += f"wall {wall:>8.1f} ms  "
        row += f"state {r.get('state') or 'n/a'}"
        if criticals:
            row += "  CRITICAL[" + ",".join(sorted(set(criticals))) + "]"
        rows.append(row)
    return rows


def _notable_deltas(v: dict, limit: int = 8) -> list:
    """Largest per-lane counter movements across the retained window —
    the wire/health lanes that moved most on the road to the incident."""
    totals: dict = {}
    for r in v["rounds"]:
        for ns, d in (r.get("lane_deltas") or {}).items():
            for k, dv in d.items():
                if isinstance(dv, (int, float)):
                    key = f"{ns}/{k}"
                    totals[key] = totals.get(key, 0) + dv
    ranked = sorted(totals.items(), key=lambda kv: (-abs(kv[1]), kv[0]))
    return [f"{k} {v:+g}" for k, v in ranked[:limit]]


def render_text(v: dict) -> str:
    lines = [f"fedpost verdict: incident {v['id']}"]
    lines.append(f"  rule      {v['rule']} ({v['kind']})"
                 + (f" tenant {v['tenant']!r}" if v.get("tenant") else ""))
    lines.append(f"  round     {v['round']}")
    if v.get("reason"):
        lines.append(f"  detail    {v['reason']}")
    if v.get("state"):
        lines.append(f"  state     {v['state']}")
    lines.append(f"  run       seed {v['seed']} / chaos_seed "
                 f"{v['chaos_seed']} / window {v['window']}")
    if v["baseline_deltas"]:
        lines.append("")
        lines.append("counter deltas vs run baseline (watchdog):")
        for k, d in sorted(v["baseline_deltas"].items()):
            lines.append(f"  {k:<24} {d:+g}")
    ch = v.get("chain")
    if ch:
        lines.append("")
        lines.append(f"causal chain ({ch['events']} flight-ring event(s), "
                     f"{len(ch['ranks'])} rank(s), {ch['rounds']} round(s) "
                     "retained):")
        if ch["incident_entry"]:
            lines.extend("  " + ln
                         for ln in _fmt_chain_entry(ch["incident_entry"]))
        for s in ch["straggler_ranking"]:
            lines.append(f"  rank {s['rank']!s:>6}  "
                         f"{s['mean_chain_ms']:>9.1f} ms mean chain"
                         f"  over {s['rounds']} round(s)")
    else:
        lines.append("")
        lines.append("causal chain: no span events in the flight rings "
                     "(tracing was off, or the window was empty)")
    if v["rounds"]:
        lines.append("")
        lines.append(f"round window ({len(v['rounds'])} retained round(s)):")
        lines.extend("  " + r for r in _round_rows(v))
        deltas = _notable_deltas(v)
        if deltas:
            lines.append("  notable lane deltas: " + ", ".join(deltas))
    if v.get("suspects"):
        lines.append("")
        lines.append("suspect clients (fedlens, worst over the window):")
        lines.extend("  " + _fmt_suspect(s) for s in v["suspects"][:8])
    lines.append("")
    lines.append("replay:")
    lines.append(f"  {v['replay_cmd'] or '(manifest carries no command)'}")
    return "\n".join(lines)


def render_markdown(v: dict) -> str:
    lines = [f"# Incident `{v['id']}`", ""]
    lines.append(f"**Rule:** `{v['rule']}` ({v['kind']})"
                 + (f" — tenant `{v['tenant']}`" if v.get("tenant") else ""))
    lines.append(f"**Round:** {v['round']}")
    if v.get("reason"):
        lines.append(f"**Detail:** {v['reason']}")
    if v.get("state"):
        lines.append(f"**Watchdog state:** {v['state']}")
    lines.append(f"**Run:** seed {v['seed']}, chaos_seed {v['chaos_seed']}, "
                 f"window {v['window']}")
    if v["baseline_deltas"]:
        lines += ["", "## Counter deltas vs baseline", "",
                  "| counter | delta |", "| --- | --- |"]
        for k, d in sorted(v["baseline_deltas"].items()):
            lines.append(f"| `{k}` | {d:+g} |")
    ch = v.get("chain")
    lines += ["", "## Causal chain", ""]
    if ch:
        lines.append(f"{ch['events']} flight-ring event(s) across "
                     f"{len(ch['ranks'])} rank(s), {ch['rounds']} round(s) "
                     "retained.")
        if ch["incident_entry"]:
            lines.append("")
            lines.extend(f"- {ln}"
                         for ln in _fmt_chain_entry(ch["incident_entry"]))
        if ch["straggler_ranking"]:
            lines += ["", "| rank | mean chain (ms) | rounds |",
                      "| --- | --- | --- |"]
            for s in ch["straggler_ranking"]:
                lines.append(f"| {s['rank']} | {s['mean_chain_ms']:.1f} | "
                             f"{s['rounds']} |")
    else:
        lines.append("No span events in the flight rings (tracing was off, "
                     "or the window was empty).")
    if v["rounds"]:
        lines += ["", "## Round window", "", "```"]
        lines.extend(_round_rows(v))
        lines.append("```")
        deltas = _notable_deltas(v)
        if deltas:
            lines.append("")
            lines.append("Notable lane deltas: "
                         + ", ".join(f"`{d}`" for d in deltas))
    if v.get("suspects"):
        lines += ["", "## Suspect clients (fedlens)", "",
                  "| client | norm | drift | align | dloss | rounds |",
                  "| --- | --- | --- | --- | --- | --- |"]
        for s in v["suspects"][:8]:
            def _c(k):
                return (f"{s[k]:g}" if isinstance(s.get(k), (int, float))
                        else "-")
            lines.append(f"| {s['client']} | {_c('norm')} | {_c('drift')} | "
                         f"{_c('align')} | {_c('loss_delta')} | "
                         f"{s['rounds']} |")
    lines += ["", "## Replay", "", "```sh",
              v["replay_cmd"] or "# manifest carries no command", "```"]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("bundle", help="incident-<id>/ bundle directory")
    ap.add_argument("--markdown", action="store_true",
                    help="render the verdict as GitHub-flavored markdown")
    args = ap.parse_args(argv)
    try:
        b = load_bundle(args.bundle)
    except BundleError as e:
        print(f"fedpost: malformed bundle: {e}", file=sys.stderr)
        return 1
    v = build_verdict(b)
    print(render_markdown(v) if args.markdown else render_text(v))
    return 0


if __name__ == "__main__":
    sys.exit(main())
