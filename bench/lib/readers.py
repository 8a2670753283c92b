"""Shared arithmetic of the per-layer readers in ``bench/layer_metrics``.

Each reader returns None where its traced run gave it nothing to read;
none returns 0 for a share of a peak or of a roofline.
"""
from __future__ import annotations

import re

from . import cost, registry


def names() -> dict:
    """Kernel and program names to match in the trace (data, kept beside
    the readers)."""
    return registry.load_json(registry.BENCH / "layer_metrics"
                              / "names.json")


def matcher(patterns):
    rx = re.compile("|".join(patterns))
    return lambda name: bool(rx.search(name))


def module_class(module: str):
    """Which of the named program classes a module (compiled program)
    belongs to, or None."""
    for cls, pats in names()["modules"].items():
        if matcher(pats)(module):
            return cls
    return None


def idle_share(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def kernel_roofline(ctx, kernel: str):
    """Least time the chip could take for the kernel's calls in the
    window (the larger of bytes over HBM bandwidth and operations over
    peak, per call, at the call's batch) over their device time. Each
    call is counted at the mean of the model's layers, which are alike in
    both configurations here."""
    t, batch = ctx.trace, ctx.facts.get("batch", {})
    if t is None or ctx.peaks is None:
        return None
    match = matcher(names()["kernels"][kernel])
    classes = {}
    dims = cost.layer_dims(ctx.cfg)
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["flops"]
    need = took = 0.0
    for op in t.ops:
        if op.module not in classes:
            classes[op.module] = module_class(op.module)
        B = batch.get(classes[op.module])
        if B is None or not match(op.label):
            continue
        need += sum(max(cost.step_call_bytes(d, B) / bw,
                        cost.step_call_flops(d, B) / peak)
                    for d in dims) / len(dims)
        took += op.dur * 1e-9
    return 100.0 * need / took if took > 0 else None


def mfu(ctx, flops: float):
    """Operations required over the chip's peak across the traced part of
    the window, its length on the host's clock."""
    window = ctx.facts.get("window_s", 0.0)
    if ctx.peaks is None or flops <= 0 or window <= 0:
        return None
    return 100.0 * flops / (window * ctx.peaks["flops"])


def lm_flops(ctx):
    """Operations the tokens served in the traced window required: a
    prompt step or a decode step through the LSTM layers per token fed,
    and the head once per token served."""
    f = ctx.facts
    if "out_tokens" not in f:
        return 0
    steps = f["out_tokens"] - f["first_tokens"] + f["prompt_tokens"]
    return (steps * cost.lstm_flops_per_step(ctx.cfg)
            + f["out_tokens"] * cost.head_flops(ctx.cfg))


def frame_flops(ctx):
    """Operations the frames run in the traced window required (a batch
    crossing its edge counts pro rata): the LSTM layers per frame, the
    head once per utterance (its last frame, which is all the entry
    computes)."""
    f = ctx.facts
    if "frames" not in f:
        return 0
    return (f["frames"] * cost.lstm_flops_per_step(ctx.cfg)
            + f["utterances"] * cost.head_flops(ctx.cfg))
