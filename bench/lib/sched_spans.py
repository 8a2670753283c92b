"""The continuous-batching scheduler's program spans, for the readers of
the "scheduler" layer.

The spans are the program's own (``repro.obs.trace``), read twice: from
the tracer's list (``ctx.spans``: ``t`` and ``dur`` in seconds on the
host's ``perf_counter``), where their list args are, and, as profiler
annotations of the same names, from the trace's host plane
(``ctx.trace.host``: ns on the device's clock). The scheduler's work
happens in three top-level spans: ``sched.admit`` (holding a
``sched.prefill`` per prefill group), ``sched.dispatch`` and
``sched.harvest`` (holding ``sched.sync``, the host's wait for a chunk's
tokens). A program without a span gives its readers nothing to read:
they return None.
"""
from __future__ import annotations

from . import xtrace

TOP = ("sched.admit", "sched.dispatch", "sched.harvest")
SYNC = "sched.sync"
PREFILL = "sched.prefill"


def named(spans, name: str) -> list:
    return [s for s in spans if s["name"] == name]


def overlap_ns(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def subtract(a, b) -> list:
    """Sorted disjoint intervals ``a`` less sorted disjoint ``b``."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def host_work(reduced) -> list | None:
    """The traced window's intervals (ns) in which the host is inside a
    top-level scheduler span and not waiting in ``sched.sync``; None where
    the window holds no scheduler span or no ``sched.sync``: the wait
    cannot be told from the work then."""
    t0, t1 = reduced.t0, reduced.t1
    top = xtrace.clip([(s, s + d) for name, s, d in reduced.host
                       if name in TOP], t0, t1)
    waits = xtrace.clip([(s, s + d) for name, s, d in reduced.host
                         if name == SYNC], t0, t1)
    if not top or not waits:
        return None
    return subtract(xtrace.union(top), xtrace.union(waits))
