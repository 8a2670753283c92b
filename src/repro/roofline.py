"""Roofline analysis from compiled HLO artifacts.

`compiled.cost_analysis()` counts each while-loop body ONCE (trip counts are
not statically multiplied), so a scanned-layers model under-reports by ~L×.
This module parses the post-optimization HLO text instead and:

  1. splits it into computations,
  2. recovers while-loop trip counts from loop-condition constants
     (scan lowers to `compare(counter, constant(N)), direction=LT`),
  3. builds a call graph (while body/cond, call, fusion, conditional) with
     multiplicative loop multiplicity,
  4. sums dot/convolution FLOPs and collective bytes × multiplicity.

Collective byte → wire-time conversion uses ring formulas:
  all-reduce      2·size·(n-1)/n
  all-gather      size·(n-1)/n      (size = full gathered output)
  reduce-scatter  size·(n-1)/n      (size = full input)
  all-to-all      size·(n-1)/n
  collective-permute  size
All divided by n_links·link_bw when converted to seconds (per-chip view).

The three roofline terms (per step, per chip):
  compute    = FLOPs_total   / (chips × peak_flops)
  memory     = HBM bytes     / (chips × hbm_bw)     [analytic traffic model]
  collective = Σ wire bytes  / (chips × ici_bw)
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any

import numpy as np

from . import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def shape_bytes(s: str) -> int:
    """Bytes of one HLO shape string (sums tuple elements)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_elems(s: str) -> int:
    m = _SHAPE_RE.search(s)
    if not m:
        return 0
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _shape_dims(s: str) -> list[int]:
    m = _SHAPE_RE.search(s)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class Computation:
    name: str
    lines: list
    # op name -> full shape string (output)
    shapes: dict


_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)(.*)$")


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        if line.endswith("{") and ("->" in line or line.startswith("ENTRY")):
            m = _COMP_HEAD.match(line.strip())
            if m:
                cur = Computation(m.group(1), [], {})
                comps[cur.name] = cur
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        cur.lines.append(line)
        m = _OP_DEF.match(line)
        if m:
            cur.shapes[m.group(1)] = m.group(2)
    return comps


_CALLED = re.compile(r"(?:body|condition|to_apply|calls|branch_computations)="
                     r"\{?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_WHILE = re.compile(r"=\s*\S+\s+while\(.*body=%?([\w\.\-]+).*")
_CONST = re.compile(r"%?([\w\.\-]+)\s*=\s*s32\[\]\s+constant\((\d+)\)")
_COMPARE = re.compile(r"compare\(([^)]*)\),?.*direction=(\w+)")
_KNOWN_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def while_trip_count(cond: Computation) -> int:
    """Recover scan trip count from the loop condition computation."""
    consts = dict(_CONST.findall("\n".join(cond.lines)))
    for line in cond.lines:
        m = _COMPARE.search(line)
        if not m:
            continue
        ops = [o.strip().lstrip("%") for o in m.group(1).split(",")]
        for o in ops:
            if o in consts:
                return int(consts[o])
    # fall back: any s32 constant in the condition
    if consts:
        return max(int(v) for v in consts.values())
    return 1


def multiplicities(comps: dict[str, Computation],
                   entry: str) -> dict[str, float]:
    """Execution count per computation, loop-aware."""
    mult: dict[str, float] = {c: 0.0 for c in comps}
    if entry not in comps:
        return mult
    mult[entry] = 1.0
    # topological-ish fixed point (call graphs are acyclic in HLO)
    for _ in range(64):
        changed = False
        for name, comp in comps.items():
            base = mult.get(name, 0.0)
            if base == 0.0:
                continue
            for line in comp.lines:
                wm = re.search(r"while\(", line)
                body = re.search(r"body=%?([\w\.\-]+)", line)
                cond = re.search(r"condition=%?([\w\.\-]+)", line)
                if wm and body and cond:
                    ktc = _KNOWN_TRIP.search(line)
                    if ktc:
                        trips = int(ktc.group(1))
                    elif cond.group(1) in comps:
                        trips = while_trip_count(comps[cond.group(1)])
                    else:
                        trips = 1
                    for tgt, k in ((body.group(1), trips),
                                   (cond.group(1), trips + 1)):
                        if tgt in comps:
                            newv = base * k
                            if mult[tgt] < newv:
                                mult[tgt] = newv
                                changed = True
                    continue
                for m in _CALLED.finditer(line):
                    for tgt in re.split(r",\s*", m.group(1)):
                        tgt = tgt.lstrip("%")
                        if tgt in comps and mult[tgt] < base:
                            mult[tgt] = base
                            changed = True
        if not changed:
            break
    return mult


# ------------------------------------------------------------- collectives

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_RG_SETS = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_RG_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str, default: int) -> int:
    m = _RG_SETS.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _RG_IOTA.search(line)
    if m:
        return int(m.group(2))
    return default


def collective_stats(comps, mult, n_devices: int) -> dict:
    """Sum payload and ring-wire bytes per collective kind (whole program,
    loop-aware). Wire bytes follow the ring formulas in the module doc."""
    out = {k: {"payload": 0.0, "wire": 0.0, "count": 0.0}
           for k in _COLL_KINDS}
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for line in comp.lines:
            mo = _OP_DEF.match(line)
            if not mo:
                continue
            kind = mo.group(3)
            if kind.endswith("-start"):
                kind = kind[:-6]
            if kind not in _COLL_KINDS:
                continue
            size = shape_bytes(mo.group(2))
            # XLA's CPU float-normalization pass promotes bf16 reductions
            # to f32 ("...clone_promoted"); a TPU build reduces native bf16.
            # Count promoted reduces at their true (half) wire size.
            if "promoted" in line:
                size //= 2
            n = _group_size(line, n_devices)
            if kind == "all-reduce":
                wire = 2 * size * (n - 1) / max(n, 1)
            elif kind == "collective-permute":
                wire = size
            else:
                wire = size * (n - 1) / max(n, 1)
            out[kind]["payload"] += m * size
            out[kind]["wire"] += m * wire
            out[kind]["count"] += m
    return out


# ------------------------------------------------------------------ flops

_DOT_OPERANDS = re.compile(r"dot\(([^)]*)\)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

# Quantized dots must not be costed at the bf16 peak. Two signals mark a
# dot as integer arithmetic: narrow-int operands (pre-optimization HLO /
# TPU builds keep s8 operands into the MXU) or an integer OUTPUT dtype —
# XLA's CPU backend normalizes s8-operand dots to convert→s32-dot, which
# erases the operand signal but keeps the s32 accumulator type; float
# models never emit integer-output dots, so the union is a sound
# classifier either way.
_INT8_DTYPES = {"s8", "u8", "s4", "u4"}
_INT_DOT_OUT = {"s8", "u8", "s16", "u16", "s32", "u32"}


def _lhs_shape_str(line, comp) -> str:
    """The lhs operand's shape string of a dot line ('' if unknown)."""
    ops = _DOT_OPERANDS.search(line)
    if not ops:
        return ""
    # Operands separate on ", " — shape dim commas ("f32[8,16]")
    # have no space, so a plain str.split(",") truncates the lhs
    # shape and drops contraction dims.
    lhs = ops.group(1).split(", ")[0].strip()
    # Post-opt HLO writes operands as "<shape> %name"; read the
    # inline shape, falling back to the defining op for bare
    # "%name" operands.
    if _SHAPE_RE.search(lhs):
        return lhs
    lhs_name = lhs.split()[-1].lstrip("%")
    return comp.shapes.get(lhs_name, "")


def _is_int_dot(line, out_shape: str, comp) -> bool:
    """Integer-arithmetic (quantized) dot: narrow-int lhs operand or an
    integer output/accumulator dtype."""
    sm = _SHAPE_RE.search(out_shape)
    if sm and sm.group(1) in _INT_DOT_OUT:
        return True
    lm = _SHAPE_RE.search(_lhs_shape_str(line, comp))
    return bool(lm and lm.group(1) in _INT8_DTYPES)


def dot_flops(comps, mult, int_only: bool = False) -> float:
    """Loop-aware dot FLOPs. ``int_only`` restricts to integer-arithmetic
    (quantized) dots — see ``_is_int_dot``; False counts every dot."""
    total = 0.0
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for line in comp.lines:
            mo = _OP_DEF.match(line)
            if not mo or mo.group(3) != "dot":
                continue
            if int_only and not _is_int_dot(line, mo.group(2), comp):
                continue
            lhs = _lhs_shape_str(line, comp)
            out_elems = shape_elems(mo.group(2))
            cm = _CONTRACT.search(line)
            contract = 1
            if cm and cm.group(1):
                dims = _shape_dims(lhs)
                for idx in cm.group(1).split(","):
                    i = int(idx)
                    if i < len(dims):
                        contract *= dims[i]
            total += m * 2.0 * out_elems * contract
    return total


def int8_dot_flops(comps, mult) -> float:
    """The integer-dot subset of ``dot_flops``, costed at
    the int8 peak by the roofline terms. (int16 fixed-point dots
    are approximated at the same rate — the quantized path's dominant
    deployment is int8.)"""
    return dot_flops(comps, mult, int_only=True)


# ------------------------------------------------------------- top level

@dataclasses.dataclass
class RooflineReport:
    flops_hlo: float            # loop-aware parsed dot flops (whole program)
    flops_cost_analysis: float  # XLA cost_analysis (body-once undercount)
    collectives: dict           # per-kind payload/wire bytes
    collective_wire_bytes: float
    n_devices: int
    flops_int8: float = 0.0     # int8-operand subset of flops_hlo

    def terms(self, hbm_bytes_per_chip: float, chips: int) -> dict:
        # post-SPMD HLO shapes are PER-DEVICE, so parsed flops / wire bytes
        # are already per-chip quantities. int8 dots run at the int8 MXU
        # peak (2x bf16) — costing a quantized model at the bf16 rate would
        # overstate its compute time. Ring wire bytes may all cross one
        # link, so the collective term uses the per-link bandwidth.
        pk = hw.peaks(hw.TARGET_KIND)
        compute_s = ((self.flops_hlo - self.flops_int8) / pk.bf16_flops
                     + self.flops_int8 / pk.int8_ops)
        memory_s = hbm_bytes_per_chip / pk.hbm_bytes_per_s
        coll_s = self.collective_wire_bytes / pk.ici_link_bytes_per_s
        dom = max(compute_s, memory_s, coll_s)
        which = ("compute" if dom == compute_s else
                 "memory" if dom == memory_s else "collective")
        return dict(compute_s=compute_s, memory_s=memory_s,
                    collective_s=coll_s, bound=which,
                    step_s=dom)


def analyze_hlo(text: str, n_devices: int,
                cost_analysis: dict | None = None) -> RooflineReport:
    comps = parse_hlo(text)
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HEAD.match(line.strip())
            if m:
                entry = m.group(1)
                break
    if entry is None:
        entry = next(iter(comps), "")
    mult = multiplicities(comps, entry)
    colls = collective_stats(comps, mult, n_devices)
    wire = sum(v["wire"] for v in colls.values())
    return RooflineReport(
        flops_hlo=dot_flops(comps, mult),
        flops_cost_analysis=(cost_analysis or {}).get("flops", 0.0),
        collectives=colls,
        collective_wire_bytes=wire,
        n_devices=n_devices,
        flops_int8=int8_dot_flops(comps, mult),
    )


# ---------------------------------------------------- analytic flops model

def model_flops(arch, shape) -> dict:
    """MODEL_FLOPS: 6·N·D for training (2·N·D inference) + attention terms.
    N = active params (MoE: routed active only), D = tokens processed."""
    from .configs.base import ArchConfig, ShapeConfig
    from .models import build_model
    m = build_model(arch)
    n_total = m.param_count()
    # active params: replace expert count by experts_per_token
    if arch.moe:
        act = arch.with_(num_experts=arch.experts_per_token)
        n_active = build_model(act).param_count()
    else:
        n_active = n_total
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = B * S
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = B * S
        factor = 2.0
    else:  # decode: one token per sequence
        tokens = B * 1
        factor = 2.0
    core = factor * n_active * tokens
    # attention score/value flops (not in 6ND): 2·2·B·S·ctx·H·Dh per layer
    attn_layers = sum(1 for k in arch.block_pattern if k.startswith("attn"))
    n_attn = (arch.num_layers * attn_layers / max(len(arch.block_pattern), 1)
              if not arch.encdec else arch.num_layers + (arch.enc_layers or 0))
    Dh, Hq = arch.head_dim, arch.num_heads
    if shape.kind == "decode":
        ctx = S
        attn = 2 * 2 * B * 1 * ctx * Hq * Dh * n_attn * (factor / 2.0)
    else:
        ctx = S / 2  # causal average
        attn = 2 * 2 * B * S * ctx * Hq * Dh * n_attn * (factor / 2.0)
    if arch.window:
        attn = min(attn, 2 * 2 * B * (S if shape.kind != "decode" else 1)
                   * arch.window * Hq * Dh * n_attn * (factor / 2.0))
    return dict(total=core + attn, core=core, attention=attn,
                n_params=n_total, n_active=n_active)


def analytic_hbm_bytes(arch, shape, chips: int, opt: bool = True) -> dict:
    """Per-chip HBM traffic per step (documented formula, DESIGN.md §6).

    train: weights read 2× (fwd+bwd) + grads written + Adam m,v read+write
           (fp32) + remat block-input activations written+read.
    prefill: weights 1× + kv cache write + activations stream.
    decode: weights 1× + KV cache read at current length + state r/w.
    kv_quant: int8 cache + per-(pos,head) f32 scale (1 + 4/head_dim B/elem).
    """
    from .models import build_model
    m = build_model(arch)
    n = m.param_count()
    B, S = shape.global_batch, shape.seq_len
    bytes_w = 2  # bf16 weights
    kv_bytes = (1.0 + 4.0 / arch.head_dim) if arch.kv_quant else bytes_w
    d = arch.d_model
    L = arch.num_layers + (arch.enc_layers if arch.encdec else 0)
    if shape.kind == "train":
        weights = n * bytes_w * 2                  # fwd + bwd read
        grads = n * 4
        optim = n * 4 * 4 if opt else 0            # m,v read+write fp32
        acts = L * B * S * d * bytes_w * 2          # remat block inputs w+r
        total = weights + grads + optim + acts
    elif shape.kind == "prefill":
        weights = n * bytes_w
        kv = (L * B * S * arch.num_kv_heads * arch.head_dim * 2 * kv_bytes
              if not _attn_free(arch) else 0)
        acts = L * B * S * d * bytes_w
        total = weights + kv + acts
    else:
        weights = n * bytes_w
        kv = (L * B * S * arch.num_kv_heads * arch.head_dim * 2 * kv_bytes
              if not _attn_free(arch) else
              B * arch.num_heads * arch.head_dim ** 2 * 4 * 2)
        if arch.window and not _attn_free(arch):
            kv = min(kv, L * B * arch.window * arch.num_kv_heads
                     * arch.head_dim * 2 * kv_bytes)
        total = weights + kv
    return dict(total_per_chip=total / chips, weights=weights / chips,
                global_total=total)


def _attn_free(arch) -> bool:
    return all(not k.startswith("attn") for k in arch.block_pattern) \
        and not arch.encdec
