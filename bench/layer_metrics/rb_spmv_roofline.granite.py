"""The packed projections' share of their roofline: the least time their
bytes and operations need (``cost_hybrid.packed_need_s``) for the decode
steps (at the slot batch) and the prefill rows the traced window
dispatched, over the summed device time of the ``rb_spmv`` kernel's ops
(the Pallas gather, named in its ``pallas_call``)."""
import re

from bench.lib import cost_hybrid

KERNEL = re.compile(r"^%?rb_spmv(\.\d+)? |jit\(rb_spmv\)/pallas_call")


def read(ctx):
    t, f = ctx.trace, ctx.facts
    if t is None or ctx.peaks is None or "decode_steps" not in f:
        return None
    took = t.op_seconds(lambda label: bool(KERNEL.search(label)))
    if took <= 0:
        return None
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["flops"]
    need = f["decode_steps"] * cost_hybrid.packed_need_s(
        ctx.cfg, f["batch"]["decode"], bw, peak)
    for bucket, calls in f["prefill_requests"].items():
        n = int(bucket) * f["batch"]["prefill"]
        need += calls / f["batch"]["prefill"] * cost_hybrid.packed_need_s(
            ctx.cfg, n, bw, peak)
    return 100.0 * need / took
