"""The Mamba-2 state update's share of its roofline: the least time the
state's bytes and operations need (``cost_hybrid.ssm_need_s``) for the
decode steps (at the slot batch) and the prefill positions (one update
per position at the prefill batch) the traced window dispatched, over the
summed device time of the ``mamba2_ssm_step`` kernel's ops."""
import re

from bench.lib import cost_hybrid

KERNEL = re.compile(r"^%?mamba2_ssm_step(\.\d+)? "
                    r"|jit\(mamba2_ssm_step\)/pallas_call")


def read(ctx):
    t, f = ctx.trace, ctx.facts
    if t is None or ctx.peaks is None or "decode_steps" not in f:
        return None
    took = t.op_seconds(lambda label: bool(KERNEL.search(label)))
    if took <= 0:
        return None
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["flops"]
    B = f["batch"]["prefill"]
    need = f["decode_steps"] * cost_hybrid.ssm_need_s(
        ctx.cfg, f["batch"]["decode"], bw, peak)
    for bucket, calls in f["prefill_requests"].items():
        need += int(bucket) * calls / B * cost_hybrid.ssm_need_s(
            ctx.cfg, B, bw, peak)
    return 100.0 * need / took
