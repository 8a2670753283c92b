"""Median time a request admitted in the traced window waited in the
admission queue: the scheduler's clock at its admission less its arrival
(``waited_ms`` of the program's ``sched.prefill`` spans)."""
import statistics

from bench.lib import harness, sched_spans


def read(ctx):
    waits = [w for s in sched_spans.named(ctx.spans, sched_spans.PREFILL)
             for w in s["args"].get("waited_ms", ())]
    harness.log(f"queue_wait_p50_ms.decode samples={len(waits)}")
    if not waits:
        return None
    return statistics.median(waits)
