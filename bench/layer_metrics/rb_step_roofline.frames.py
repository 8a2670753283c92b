"""The fused BRDS-LSTM step kernel's share of its roofline in the batched prefill."""
from bench.lib import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "rb_step")
