"""The fused BRDS-LSTM step kernel's share of its roofline, prefill and decode calls together."""
from bench.lib import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "rb_step")
