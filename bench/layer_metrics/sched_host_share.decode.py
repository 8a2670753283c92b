"""Share of the traced window the scheduler kept the host busy: its
top-level ``sched.admit``, ``sched.dispatch`` and ``sched.harvest``
spans, less the ``sched.sync`` waits on the device inside the harvests
(the spans' profiler annotations, on the device's clock). Read under the
profiler, whose Python tracer slows every Python call: the untraced
share is lower."""
from bench.lib import sched_spans


def read(ctx):
    t = ctx.trace
    if t is None or t.t1 <= t.t0:
        return None
    work = sched_spans.host_work(t)
    if work is None:
        return None
    return 100.0 * sum(e - s for s, e in work) / (t.t1 - t.t0)
