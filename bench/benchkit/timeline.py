"""End-to-end arithmetic on host-clock stamps.

A ``Record`` is one request as the client saw it: when it was sent, the
host time at which each of its output tokens came back (the return of the
``ServingEngine.step`` that produced it), and its prompt-prefill progress
at the window's two edges.  The window is ``(t0, t1]``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Record:
    rid: str
    client: Optional[int]          # None: a set-up warm-up request
    index: int
    prompt_len: int
    sent: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None   # host time the engine finished it
    next_sent: bool = False
    progress0: float = 0.0         # prompt tokens prefilled at t0
    progress1: float = 0.0         # ... at t1
    cursor0: Tuple[int, int] = (0, 0)   # prefill (layer, tokens in it)
    cursor1: Tuple[int, int] = (0, 0)   # at t0 and t1

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


def tokens_in(records: List[Record], t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.stamps if t0 < t <= t1)


def gaps_in(records: List[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive output tokens of one request whose
    later token came inside the window (the first gap may start before
    it)."""
    out = []
    for r in records:
        for a, b in zip(r.stamps, r.stamps[1:]):
            if t0 < b <= t1:
                out.append(b - a)
    return out


def ttfts(records: List[Record], t0: float, t1: float) -> List[float]:
    """Time to first token of every request outstanding in the window:
    sent before its end and without a first token before its start.  A
    request still waiting at the end counts at (t1 - sent)."""
    out = []
    for r in records:
        if r.client is None or r.sent > t1:
            continue
        first = r.first
        if first is not None and first <= t0:
            continue
        out.append((first if first is not None and first <= t1 else t1)
                   - r.sent)
    return out


def prompt_tokens_in(records: List[Record]) -> float:
    """Prompt tokens prefilled inside the window, partial prompts by their
    progress: each request's progress at t1 minus at t0."""
    return float(sum(r.progress1 - r.progress0 for r in records))


def percentile(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))
