"""The timed loop, one for every cell.

Dispatch round r, then block on round r-1's loss: one round stays in
flight, so host dispatch (and the host data path behind it) overlaps device
compute as in a long ``train()`` with ``async_rounds``, and every round
still gets a completion time. The loop runs until ``seconds`` have passed
(or, in a traced run, ``max_rounds`` rounds are out), finishes the round in
flight, and the rate is taken over all the rounds and all the time.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional


def round_indices(rounds: dict, skip: int = 0) -> Iterator[int]:
    """The cell's round indices: ``first .. first+cycle-1`` replayed for
    ever, or, with ``cycle`` null, counting up from ``first + skip``."""
    first, cycle = int(rounds["first"]), rounds.get("cycle")
    if cycle:
        return itertools.cycle(range(first, first + int(cycle)))
    return itertools.count(first + skip)


@dataclass
class Window:
    """What one window saw, all on ``time.perf_counter``."""
    t0: float = 0.0
    t1: float = 0.0
    #: (round index, dispatch start, dispatch end, completion) per round
    rounds: list = field(default_factory=list)
    #: losses, in dispatch order (host floats)
    losses: list = field(default_factory=list)
    raised: int = 0

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0

    @property
    def attempted(self) -> int:
        return len(self.rounds) + self.raised

    @property
    def failed(self) -> int:
        return self.raised + sum(1 for v in self.losses if not math.isfinite(v))

    def gaps_ms(self) -> list:
        done = [r[3] for r in self.rounds]
        return [(b - a) * 1e3 for a, b in zip(done, done[1:])]


def run_window(run_round: Callable, indices: Iterator[int], seconds: float,
               *, max_rounds: Optional[int] = None,
               annotate: Optional[Callable] = None) -> Window:
    """Drive ``run_round(r)`` (which returns an un-synced device scalar)
    over ``indices``. ``annotate(name)`` gives a context manager that puts
    the host span into the profiler's trace (traced runs only)."""
    import jax

    span = annotate or (lambda _name: contextlib.nullcontext())
    w = Window()
    pending = None            # (round, dispatch start, dispatch end, loss)

    def finish(p):
        with span("bench/block_prev"):
            value = float(jax.block_until_ready(p[3]))
        w.rounds.append((p[0], p[1], p[2], time.perf_counter()))
        w.losses.append(value)

    w.t0 = time.perf_counter()
    for n, r in enumerate(indices):
        d0 = time.perf_counter()
        try:
            with span("bench/dispatch"):
                loss = run_round(r)
        except Exception:       # a round that raised is a failed round
            import traceback

            traceback.print_exc()
            w.raised += 1
            loss = None
        d1 = time.perf_counter()
        if pending is not None:
            finish(pending)
        pending = None if loss is None else (r, d0, d1, loss)
        out = n + 1
        if (time.perf_counter() - w.t0 >= seconds
                or (max_rounds is not None and out >= max_rounds)
                or w.raised > 3):
            break
    if pending is not None:
        finish(pending)
    w.t1 = time.perf_counter()
    return w


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule), plain Python."""
    s = sorted(values)
    if not s:
        raise ValueError("no samples")
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
