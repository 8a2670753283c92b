"""Share of the prompt positions prefilled in the traced window that were
padding: the padded ``bucket`` times the group's ``batch``, less the
prompts' ``lengths``, over the former (the program's ``sched.prefill``
spans)."""
from bench.lib import sched_spans


def read(ctx):
    groups = [s["args"] for s in sched_spans.named(ctx.spans,
                                                    sched_spans.PREFILL)
              if {"bucket", "batch", "lengths"} <= set(s["args"])]
    run = sum(g["bucket"] * g["batch"] for g in groups)
    if run <= 0:
        return None
    return 100.0 * (run - sum(sum(g["lengths"]) for g in groups)) / run
