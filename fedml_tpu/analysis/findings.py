"""Finding record + the in-source suppression syntax.

A finding is (rule, path, line, message). Suppressions are trailing
``# fedlint: disable=<rule>[,<rule>]`` comments: they silence findings of the
named rules on their own physical line, and — when the comment is the whole
line — on the line directly below (so multi-line statements can carry the
comment above their first line). A suppression naming a rule that does not
exist is reported as a ``bad-suppression`` finding, which is itself
unsuppressable: a typo in a suppression must never silently widen the gate.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Set, Tuple

#: rule-id -> one-line description (the CLI's --list-rules output).
RULES: Dict[str, str] = {
    "traced-purity": (
        "no wall-clock, OS-entropy RNG, I/O, or self/global mutation "
        "reachable from a jit/pjit/shard_map/pmap traced root"
    ),
    "retrace-hazard": (
        "str/dict parameters entering a jit without static_argnums/"
        "static_argnames, or f-string construction inside a traced body"
    ),
    "seeded-rng": (
        "np.random.default_rng() must always take a seed expression; "
        "argless calls draw OS entropy and break run determinism"
    ),
    "protocol-exhaustiveness": (
        "every MSG_TYPE_* constant needs a registered receive handler or a "
        "SEND_ONLY_MSG_TYPES entry; registering an undefined type is an error"
    ),
    "config-flag-drift": (
        "every argparse --flag must be read somewhere in the package, and "
        "every config/args attribute read must name a defined flag or field"
    ),
    "trace-coverage": (
        "run_round overrides must route through the fedtrace "
        "span wrapper (override _run_round_inner, delegate to super(), or "
        "open the span) so no paradigm drops out of the round timeline"
    ),
    "unguarded-shared-write": (
        "a write to state shared across thread roots at a site that does "
        "not hold the lock guarding the majority of that field's accesses"
    ),
    "check-then-act": (
        "a read of a lock-guarded shared field outside its guard — the "
        "len-check-then-pop atomicity hole: the checked value can change "
        "before the act runs"
    ),
    "blocking-under-lock": (
        "sleep/join/Queue.put/send_message/future-result, or acquiring a "
        "different lock, while holding one — the stall/deadlock shape"
    ),
    "bad-suppression": (
        "a fedlint suppression comment names a rule that does not exist"
    ),
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_SUPPRESS_RE = re.compile(r"#\s*fedlint:\s*disable=([A-Za-z0-9_\-, ]+)")


def parse_suppressions(
    source: str, path: str
) -> Tuple[Dict[int, Set[str]], List[Finding]]:
    """Map line -> suppressed-rule set, plus bad-suppression findings.

    A whole-line comment also covers the next line, so long statements can
    be annotated above rather than by stretching their first line.
    """
    by_line: Dict[int, Set[str]] = {}
    bad: List[Finding] = []
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        unknown = sorted(r for r in rules if r not in RULES)
        for r in unknown:
            bad.append(
                Finding(
                    "bad-suppression", path, lineno,
                    f"suppression names unknown rule {r!r} "
                    f"(known: {', '.join(sorted(RULES))})",
                )
            )
        rules -= set(unknown)
        if not rules:
            continue
        by_line.setdefault(lineno, set()).update(rules)
        if text.lstrip().startswith("#"):  # standalone comment: covers below
            by_line.setdefault(lineno + 1, set()).update(rules)
    return by_line, bad


def apply_suppressions(
    findings: List[Finding], by_path: Dict[str, Dict[int, Set[str]]]
) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (kept, suppressed). bad-suppression never drops."""
    kept: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        lines = by_path.get(f.path, {})
        if f.rule != "bad-suppression" and f.rule in lines.get(f.line, ()):
            suppressed.append(f)
        else:
            kept.append(f)
    return kept, suppressed
