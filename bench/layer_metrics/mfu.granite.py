"""Operations the tokens served in the traced window required over the
chip's peak, across the window: per position fed (prompt positions and
decode steps) the packed projections' kept entries and the SSM updates,
attention over each position's live context, and the tied head once per
token served."""
from bench.lib import cost_hybrid, readers


def read(ctx):
    f = ctx.facts
    if "out_tokens" not in f or "attn_context" not in f:
        return None
    cfg = ctx.cfg
    fed = f["out_tokens"] - f["first_tokens"] + f["prompt_tokens"]
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    flops = (fed * (cost_hybrid.token_flops(cfg) - head)
             + f["out_tokens"] * head
             + cost_hybrid.attention_flops(cfg, f["attn_context"]))
    return readers.mfu(ctx, flops)
