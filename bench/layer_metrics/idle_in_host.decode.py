"""Share of the traced window in which the first device is idle while the
host does the scheduler's work: inside a top-level ``sched.*`` span of the
program and not in its ``sched.sync`` wait (the spans' profiler
annotations, on the device's clock). At most ``idle_share.decode``."""
from bench.lib import sched_spans


def read(ctx):
    t = ctx.trace
    if t is None or t.t1 <= t.t0 or not t.devices:
        return None
    work = sched_spans.host_work(t)
    if work is None:
        return None
    idle = [(s, s + d) for s, d in t.gaps]
    return 100.0 * sched_spans.overlap_ns(idle, work) / (t.t1 - t.t0)
