"""Mean share of the scheduler's slots that hold a request, over its
decode-chunk dispatches in the traced window (the program's
``sched.dispatch`` spans and their ``active`` count)."""


def read(ctx):
    active = [s["args"]["active"] for s in ctx.spans
              if s["name"] == "sched.dispatch" and "active" in s["args"]]
    if not active or not ctx.facts.get("slots"):
        return None
    return 100.0 * sum(active) / len(active) / ctx.facts["slots"]
