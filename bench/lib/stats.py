"""Per-request records and what the window makes of them.

The percentile arithmetic is copied from the program's
``repro.traffic.metrics`` (linear interpolation).

Window rules (all times on the host's ``perf_counter``):

- tokens count where they were harvested inside [t0, t1);
- the gap between tokens of a request is its time from its first to its
  last harvest inside the window over the tokens harvested after the
  first, so a request that crosses an edge of the window still counts;
- a batch that crosses an edge of the window counts for the share of its
  run on the device that lies inside.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Record:
    uid: int
    prompt_len: int
    budget: int
    due: float                      # when the request was due to be sent
    harvests: list = dataclasses.field(default_factory=list)  # (t, n)
    finished: float | None = None
    tokens: np.ndarray | None = None
    reason: str = ""

    @property
    def first_token(self) -> float | None:
        return self.harvests[0][0] if self.harvests else None


class Log:
    """Records by uid, fed by the scheduler's per-token callback and by
    the requests it reports finished."""

    def __init__(self, clock):
        self.clock = clock
        self.records: dict[int, Record] = {}

    def add(self, uid, prompt_len, budget, due) -> Record:
        rec = Record(uid, prompt_len, budget, due)
        self.records[uid] = rec
        return rec

    def on_token(self, uid, toks, first):
        rec = self.records.get(uid)
        if rec is not None:
            rec.harvests.append((self.clock(), len(toks)))

    def on_finished(self, fin):
        rec = self.records.get(fin.uid)
        if rec is not None:
            rec.finished = self.clock()
            rec.tokens = np.asarray(fin.tokens)
            rec.reason = fin.reason


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile; nan on empty input."""
    xs = np.asarray(list(xs), np.float64)
    if xs.size == 0:
        return float("nan")
    return float(np.percentile(xs, q))


def tokens_in(records, t0: float, t1: float) -> int:
    return sum(n for r in records for t, n in r.harvests if t0 <= t < t1)


def token_gaps(records, t0: float, t1: float) -> list[float]:
    """Each request's mean gap between tokens inside [t0, t1); requests
    with fewer than two harvests there have none."""
    gaps = []
    for r in records:
        inside = [(t, n) for t, n in r.harvests if t0 <= t < t1]
        after = sum(n for _, n in inside[1:])
        if len(inside) >= 2 and after > 0:
            gaps.append((inside[-1][0] - inside[0][0]) / after)
    return gaps


def batch_work_in(batches, t0: float, t1: float) -> float:
    """Work of batches that the device runs one after another, credited to
    [t0, t1). ``batches`` are (sent, done, work) in the order sent; each
    runs from the later of its sending and the previous one's completion
    until its own, and one that crosses an edge of the window is credited
    in proportion to the part of that run inside it."""
    total, prev = 0.0, -float("inf")
    for sent, done, work in batches:
        start = max(sent, prev)
        prev = done
        inside = min(done, t1) - max(start, t0)
        if inside > 0:
            total += work * inside / (done - start)
    return total


def active_in(records, t0: float, t1: float) -> list:
    """Requests that received a token inside [t0, t1)."""
    return [r for r in records
            if any(t0 <= t < t1 for t, _ in r.harvests)]
