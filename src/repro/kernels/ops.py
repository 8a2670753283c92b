"""jit'd public wrappers around the Pallas kernels.

Backend selection goes through ``repro.sparse.backend``: "pallas" runs the
kernels, "ref" the pure-jnp reference formulations (the dry-run path
lowers these; XLA fuses them), "auto"/None the configured default. The old
per-call ``use_kernel=`` boolean is accepted as a deprecated alias.

Whether a kernel runs compiled or in Pallas interpret mode is decided in
ONE place, ``interpret_mode``: compiled on a TPU default backend, always;
interpreted elsewhere (the CPU test runs). The kernels themselves take
``interpret`` as a required argument, so no call can fall back silently.

Row padding to kernel-block multiples is handled here, with a fast path
for structs pre-padded by ``core.packing.pad_packed`` (the model/serving
layer pads once at pack time so no per-token copy of the weight stream
happens inside the jitted step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from . import fused_step as _fused
from .rb_spmv import rb_spmv as _rb_spmv_kernel, rb_dual_spmv as _rb_dual_kernel
from .delta_rb_spmv import (delta_rb_spmv as _delta_rb_spmv_kernel,
                            delta_rb_dual_spmv as _delta_rb_dual_kernel)
from .rb_spmv_q8 import (rb_spmv_q8 as _rb_spmv_q8_kernel,
                         rb_dual_parts_q8 as _rb_dual_parts_q8_kernel)
from .lstm_gates import lstm_gates as _lstm_gates_kernel
from .flash_attention import flash_attention as _flash_kernel
from .decode_attention import decode_attention as _decode_kernel
from .mamba2_ssm import mamba2_ssm_step as _mamba2_ssm_kernel
from .rb_spmv import lane_pad
from .. import hw as _hw
from ..core.packing import RowBalancedSparse, block_rows_for
from ..quant.scheme import quantize as _quantize
from ..sparse import backend as _backend


def interpret_mode() -> bool:
    """Pallas interpret mode iff the default backend is not a TPU: the TPU
    always runs the compiled kernels."""
    return jax.default_backend() != "tpu"


def model_backend() -> str | None:
    """The backend a model's own kernels take: the configured default
    where the Pallas kernels compile (a TPU), the ``ref`` twins where they
    would only be interpreted (the CPU runs)."""
    return "ref" if interpret_mode() else None


def _resolve(backend: str | None, use_kernel: bool | None) -> str:
    """→ concrete "pallas" | "ref" (use_kernel= is the deprecated alias)."""
    if use_kernel is not None:
        return _backend.from_use_kernel(use_kernel, stacklevel=4)
    return _backend.resolve(backend)


def _pad_rows(arr, mult):
    r = arr.shape[0]
    pad = (-r) % mult
    if pad:
        arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
    return arr, pad


def _prep_rows(s, block_rows):
    """→ (values, deltas, scales | None, eff_block, padded_rows).

    The padded row count is a pure function of (logical rows, block):
    ``Rp = R + (-R) % block_rows_for(R, block_rows)`` — so the two structs
    of a dual
    call always agree. Fast path: the struct was pre-padded to exactly
    that count by ``core.packing.pad_packed`` (or needs no padding) and
    its arrays are consumed as-is, no per-call copy. Otherwise fall back
    to slicing to logical rows and padding here.
    """
    R = s.rows
    eff = block_rows_for(R, block_rows)
    Rp = R + (-R) % eff
    scales = getattr(s, "scales", None)
    if s.values.shape[0] == Rp:
        return s.values, s.deltas, scales, eff, Rp
    s = s.logical()
    vals, _ = _pad_rows(s.values, eff)
    deltas, _ = _pad_rows(s.deltas, eff)
    scales = getattr(s, "scales", None)
    if scales is not None and Rp > R:
        scales = jnp.pad(scales, (0, Rp - R))
    return vals, deltas, scales, eff, Rp


def _fit(vec, n):
    """Pad (with zeros) or slice ``vec``'s last axis to length ``n`` —
    bias/partial-sum vectors ride whichever padding the struct carries."""
    have = vec.shape[-1]
    if have == n:
        return vec
    if have > n:
        return vec[..., :n]
    widths = ((0, 0),) * (vec.ndim - 1) + ((0, n - have),)
    return jnp.pad(vec, widths)


# ---------------------------------------------------------------- rb_spmv

def rb_spmv(s: RowBalancedSparse, x: jnp.ndarray, *, block_rows: int = 256,
            backend: str | None = None,
            use_kernel: bool | None = None) -> jnp.ndarray:
    """Packed row-balanced SpMV; x (B, ncols) → (B, rows)."""
    if _resolve(backend, use_kernel) == "ref":
        return _ref.rb_spmv_ref(s, x)
    R = s.rows
    vals, deltas, _, eff, Rp = _prep_rows(s, block_rows)
    y = _rb_spmv_kernel(vals, deltas, x, block_rows=eff,
                        interpret=interpret_mode())
    return y[:, :R] if Rp > R else y


def project_block_rows(K: int) -> int:
    """Kernel row block of a packed projection with K kept columns per
    row: 256, or 128 from K = 2048 up, where a 256-row block's columns,
    values and gathered chunks no longer fit the kernel's VMEM."""
    return 128 if lane_pad(K) >= 2048 else 256


def project_rows(ncols: int) -> int:
    """Activation rows per packed-projection kernel call: 32 up to 4096
    source columns, 8 beyond, where the replicated source chunks of more
    rows overflow the kernel's VMEM."""
    return 32 if lane_pad(ncols) <= 4096 else 8


# Device time of the packed gather per kept entry per activation row, by
# device kind: the fused LSTM step at B=32 in ``ptb_decode`` (2.57 ms a
# call over 6.75 M kept entries × 32 rows; PERF_LEDGER.jsonl, PR 14), the
# kernel's best rate on the chip. A kind with no rate serves packed.
GATHER_S_PER_ENTRY_ROW = {"TPU v5 lite": 1.2e-11}


def device_kind() -> str:
    """``device_kind`` of the default backend's first device."""
    return jax.devices()[0].device_kind


def serve_dense(rows: int, ncols: int, K: int, n: int, kind: str,
                itemsize: int = 4) -> bool:
    """Whether a packed projection of ``rows`` × ``ncols`` keeping ``K``
    columns a row serves faster as its pruned dense matrix at ``n``
    activation rows on a ``kind`` device: true where the gather's work,
    ``n · rows · K`` entry-rows at ``GATHER_S_PER_ENTRY_ROW[kind]``, takes
    at least as long as streaming the dense bytes at the chip's HBM rate
    (``hw.PEAKS``). The gather's work grows with ``n``, the dense bytes do
    not. A kind with no measured rate keeps the leaf packed."""
    g = GATHER_S_PER_ENTRY_ROW.get(kind)
    if g is None:
        return False
    return (n * rows * K * g
            >= rows * ncols * itemsize / _hw.peaks(kind).hbm_bytes_per_s)


def rb_project(x, s: RowBalancedSparse, *, backend: str | None = None):
    """x (..., ncols) through packed weights → (..., rows): every
    projection of a packed model. The leading axes (batch, or batch ×
    positions at prefill) are flattened into activation rows and run
    through ``rb_spmv`` — the Pallas ``gather_dot`` on a TPU — in calls of
    at most ``project_rows`` rows (a ``lax.map`` over row tiles), at the
    row block ``project_block_rows`` that ``pad_packed`` gave the struct.
    ``backend=None`` is ``model_backend()``: the ref twin on the CPU."""
    lead, X = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, X)
    if backend is None:
        backend = model_backend()
    if _resolve(backend, None) == "ref":
        y = _ref.rb_spmv_ref(s, x2)
    else:
        n, t = x2.shape[0], project_rows(s.ncols)
        call = functools.partial(rb_spmv, s,
                                 block_rows=project_block_rows(s.K),
                                 backend=backend)
        if n <= t:
            y = call(x2)
        else:
            xs = jnp.pad(x2, ((0, (-n) % t), (0, 0))).reshape(-1, t, X)
            y = jax.lax.map(call, xs).reshape(-1, s.rows)[:n]
    return y.reshape(*lead, s.rows)


def rb_dual_spmv(sx: RowBalancedSparse, x, sh: RowBalancedSparse, h, bias,
                 *, block_rows: int = 256, backend: str | None = None,
                 use_kernel: bool | None = None):
    """z = Sx@x + Sh@h + bias — the fused dual-ratio gate preactivation."""
    if _resolve(backend, use_kernel) == "ref":
        return _ref.rb_dual_spmv_ref(sx, x, sh, h, bias)
    R = sx.rows
    vx, dx, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dh, _, _, _ = _prep_rows(sh, block_rows)
    z = _rb_dual_kernel(vx, dx, x, vh, dh, h, _fit(bias, Rp),
                        block_rows=eff, interpret=interpret_mode())
    return z[:, :R] if Rp > R else z


def delta_rb_spmv(s: RowBalancedSparse, d, fired, *, block_rows: int = 256,
                  backend: str | None = None):
    """Temporal-delta SpMV: y[b, r] = Σ_k vals[r, k] · fired[b, c] · d[b, c].

    ``d`` (B, ncols) raw activation deltas, ``fired`` (B, ncols) bool/0-1
    threshold mask. Returns (B, rows)."""
    fired = fired.astype(jnp.float32)
    if _resolve(backend, None) == "ref":
        return _ref.delta_rb_spmv_ref(s, d, fired)
    R = s.rows
    vals, deltas, _, eff, Rp = _prep_rows(s, block_rows)
    y = _delta_rb_spmv_kernel(vals, deltas, d, fired, block_rows=eff,
                              interpret=interpret_mode())
    return y[:, :R] if Rp > R else y


def delta_rb_dual_spmv(sx: RowBalancedSparse, dx, fx,
                       sh: RowBalancedSparse, dh, fh, m, *,
                       block_rows: int = 256, backend: str | None = None):
    """m' = m + Sx@(fx·dx) + Sh@(fh·dh) — the fused temporal-delta gate
    accumulation (partial-sum memory update)."""
    fx = fx.astype(jnp.float32)
    fh = fh.astype(jnp.float32)
    if _resolve(backend, None) == "ref":
        return _ref.delta_rb_dual_spmv_ref(sx, dx, fx, sh, dh, fh, m)
    R = sx.rows
    vx, dxi, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dhi, _, _, _ = _prep_rows(sh, block_rows)
    z = _delta_rb_dual_kernel(vx, dxi, dx, fx, vh, dhi, dh, fh, _fit(m, Rp),
                              block_rows=eff, interpret=interpret_mode())
    return z[:, :R] if Rp > R else z


# --------------------------------------------------------------- quantized

def _quant_act(x, packed, act_scale):
    """→ (codes, scale): quantize one activation batch for a q8 matvec.

    ``act_scale`` None → the packing's scheme decides: fixed-point uses
    its constant 2^-N; scaled schemes fall back to a dynamic per-call
    max-abs (the calibrated static scales arrive through the model)."""
    scheme = packed.scheme
    sa = scheme.act_scale(act_scale)
    if sa is None:
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        sa = jnp.maximum(amax / scheme.qmax, 1e-12)
    return _quantize(x, sa, scheme), sa


def rb_spmv_q8(s, x, *, act_scale=None, block_rows: int = 256,
               backend: str | None = None):
    """Quantized packed SpMV: int codes × int activation codes, int32
    accumulate, per-row dequant. ``s``: RowBalancedSparseQ8; x (B, ncols)
    float activations (quantized here, so pallas and ref consume the SAME
    codes). Returns (B, rows) float32."""
    qx, sa = _quant_act(x, s, act_scale)
    if _resolve(backend, None) == "ref":
        return _ref.rb_spmv_q8_ref(s, qx, sa)
    R = s.rows
    vals, deltas, scales, eff, Rp = _prep_rows(s, block_rows)
    comb = (scales * sa).astype(jnp.float32)
    y = _rb_spmv_q8_kernel(vals, deltas, comb, qx, block_rows=eff,
                           interpret=interpret_mode())
    return y[:, :R] if Rp > R else y


def _prep_parts_q8(sx, sax, sh, sah, block_rows):
    """Prep both q8 families: padded arrays + combined (row × act) dequant
    scales (padded scales are zero → padded rows dequantize to exact 0)."""
    vx, dxi, scx, eff, Rp = _prep_rows(sx, block_rows)
    vh, dhi, sch, _, _ = _prep_rows(sh, block_rows)
    cx = (scx * sax).astype(jnp.float32)
    ch = (sch * sah).astype(jnp.float32)
    return vx, dxi, cx, vh, dhi, ch, eff, Rp


def _dual_parts_q8(sx, qx, sax, sh, qh, sah, block_rows):
    """Run the two-family q8 kernel → (zx, zh) dequantized partial sums,
    both (B, rows) f32."""
    R = sx.rows
    vx, dxi, cx, vh, dhi, ch, eff, Rp = _prep_parts_q8(sx, sax, sh, sah,
                                                       block_rows)
    zx, zh = _rb_dual_parts_q8_kernel(vx, dxi, cx, qx, vh, dhi, ch, qh,
                                      block_rows=eff,
                                      interpret=interpret_mode())
    return (zx[:, :R], zh[:, :R]) if Rp > R else (zx, zh)


def rb_dual_spmv_q8(sx, x, sh, h, bias, *, act_scale_x=None,
                    act_scale_h=None, block_rows: int = 256,
                    backend: str | None = None):
    """z = dq(Sx@qx) + dq(Sh@qh) + bias — the quantized dual-ratio gate
    preactivation (each family dequantized by its own row × act scales).
    Returns (B, rows) float32."""
    qx, sax = _quant_act(x, sx, act_scale_x)
    qh, sah = _quant_act(h, sh, act_scale_h)
    if _resolve(backend, None) == "ref":
        return _ref.rb_dual_spmv_q8_ref(sx, qx, sax, sh, qh, sah, bias)
    zx, zh = _dual_parts_q8(sx, qx, sax, sh, qh, sah, block_rows)
    return zx + zh + bias[:zx.shape[-1]].astype(jnp.float32)[None, :]


def delta_rb_dual_spmv_q8(sx, dx, fx, sh, dh, fh, m, *, act_scale_x=None,
                          act_scale_h=None, block_rows: int = 256,
                          backend: str | None = None):
    """m' = m + dq(Sx@q(fx·dx)) + dq(Sh@q(fh·dh)) — the quantized fused
    temporal-delta gate accumulation. Deltas are masked BEFORE quantizing,
    so unfired columns carry exact 0 codes into the int32 accumulation;
    ``m`` stays the fp32 partial-sum memory. Returns (B, rows) float32."""
    dxm = jnp.where(fx.astype(bool), dx, 0).astype(dx.dtype)
    dhm = jnp.where(fh.astype(bool), dh, 0).astype(dh.dtype)
    qdx, sax = _quant_act(dxm, sx, act_scale_x)
    qdh, sah = _quant_act(dhm, sh, act_scale_h)
    if _resolve(backend, None) == "ref":
        return _ref.delta_rb_dual_spmv_q8_ref(sx, qdx, sax, sh, qdh, sah, m)
    zx, zh = _dual_parts_q8(sx, qdx, sax, sh, qdh, sah, block_rows)
    return m.astype(jnp.float32) + zx + zh


def brds_lstm_step_q8(sx, x, sh, h_prev, bias, c_prev, *, act_scale_x=None,
                      act_scale_h=None, pwl: bool = False,
                      block_rows: int = 256, backend: str | None = None):
    """One quantized BRDS-LSTM inference step: the q8 dual-ratio SpMV
    (int32 accumulate + per-row dequant) feeding the Function module.
    Returns (c, h)."""
    z = rb_dual_spmv_q8(sx, x, sh, h_prev, bias, act_scale_x=act_scale_x,
                        act_scale_h=act_scale_h, block_rows=block_rows,
                        backend=backend)
    H = z.shape[-1] // 4
    return lstm_gates(z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:], c_prev, pwl=pwl, backend=backend)


def brds_delta_lstm_step_q8(sx, dx, fx, sh, dh, fh, m_prev, bias, c_prev,
                            *, act_scale_x=None, act_scale_h=None,
                            pwl: bool = False, block_rows: int = 256,
                            backend: str | None = None):
    """One quantized temporally-sparse BRDS-LSTM step: fired-column
    quantized products advance the fp32 partial-sum memory, bias applies
    on top, the Function module closes the cell. Returns (c, h, m)."""
    m = delta_rb_dual_spmv_q8(sx, dx, fx, sh, dh, fh, m_prev,
                              act_scale_x=act_scale_x,
                              act_scale_h=act_scale_h,
                              block_rows=block_rows, backend=backend)
    z = m + bias[:m.shape[-1]].astype(jnp.float32)[None, :]
    H = z.shape[-1] // 4
    c, h = lstm_gates(z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:], c_prev, pwl=pwl, backend=backend)
    return c, h, m


def brds_delta_lstm_step(sx: RowBalancedSparse, dx, fx,
                         sh: RowBalancedSparse, dh, fh, m_prev, bias, c_prev,
                         *, pwl: bool = False, block_rows: int = 256,
                         backend: str | None = None):
    """One temporally-sparse BRDS-LSTM inference step.

    The Spartus composition of the accelerator datapath: the fused delta
    dual-SpMV advances the partial-sum memory ``m`` with only the fired
    columns' products, the bias is applied on top, and the Function module
    (lstm_gates) produces the new cell state. Returns (c, h, m)."""
    m = delta_rb_dual_spmv(sx, dx, fx, sh, dh, fh, m_prev,
                           block_rows=block_rows, backend=backend)
    z = m.astype(jnp.float32) + bias[:m.shape[-1]].astype(jnp.float32)[None, :]
    H = z.shape[-1] // 4
    c, h = lstm_gates(z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:], c_prev, pwl=pwl, backend=backend)
    return c, h, m


def brds_lstm_step(sx: RowBalancedSparse, x, sh: RowBalancedSparse, h_prev,
                   bias, c_prev, *, pwl: bool = False,
                   block_rows: int = 256, backend: str | None = None):
    """One BRDS-LSTM inference step — the accelerator datapath as one op:
    the fused dual-ratio SpMV (the paper's Gate module) feeding the LSTM
    nonlinearities (the Function module). x (B, X), h/c (B, H) with
    sx/sh packed over the 4H gate rows. Returns (c, h).

    This is the decode hot loop: the serving runtime scans it once per
    generated token with the (c, h) cache donated. Chained form — two
    kernel launches (SpMV, gates) with z through HBM between them; see
    ``fused_brds_lstm_step`` for the single-launch fusion."""
    z = rb_dual_spmv(sx, x, sh, h_prev, bias, block_rows=block_rows,
                     backend=backend)
    H = z.shape[-1] // 4
    return lstm_gates(z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:], c_prev, pwl=pwl, backend=backend)


# ------------------------------------------------------------- fused step

def fused_brds_lstm_step(sx: RowBalancedSparse, x, sh: RowBalancedSparse,
                         h_prev, bias, c_prev, *, pwl: bool = False,
                         block_rows: int = 256, backend: str | None = None):
    """``brds_lstm_step`` in ONE kernel launch: the Gate stage's z blocks
    land in VMEM scratch and the Function stage closes the cell from
    there — no HBM round-trip for z/c/h between the two. Bitwise-identical
    to the chained path (same block shapes → same reductions). Returns
    (c, h)."""
    if _resolve(backend, None) == "ref":
        z = _ref.rb_dual_spmv_ref(sx, x, sh, h_prev, bias)
        H = z.shape[-1] // 4
        return _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                  z[:, 2 * H:3 * H], z[:, 3 * H:],
                                  c_prev, pwl=pwl)
    vx, dx, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dh, _, _, _ = _prep_rows(sh, block_rows)
    return _fused.fused_brds_lstm_step(vx, dx, x, vh, dh, h_prev,
                                       _fit(bias, Rp), c_prev, pwl=pwl,
                                       block_rows=eff,
                                       interpret=interpret_mode())


def fused_brds_delta_lstm_step(sx: RowBalancedSparse, dx, fx,
                               sh: RowBalancedSparse, dh, fh, m_prev, bias,
                               c_prev, *, pwl: bool = False,
                               block_rows: int = 256,
                               backend: str | None = None):
    """``brds_delta_lstm_step`` in ONE launch: fired-column masking, the
    partial-sum memory update, bias and the cell — m and z VMEM-resident
    between the stages. Returns (c, h, m)."""
    fx = fx.astype(jnp.float32)
    fh = fh.astype(jnp.float32)
    if _resolve(backend, None) == "ref":
        m = _ref.delta_rb_dual_spmv_ref(sx, dx, fx, sh, dh, fh, m_prev)
        z = (m.astype(jnp.float32)
             + bias[:m.shape[-1]].astype(jnp.float32)[None, :])
        H = z.shape[-1] // 4
        c, h = _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                  z[:, 2 * H:3 * H], z[:, 3 * H:],
                                  c_prev, pwl=pwl)
        return c, h, m
    R = sx.rows
    vx, dxi, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dhi, _, _, _ = _prep_rows(sh, block_rows)
    c, h, m = _fused.fused_brds_delta_lstm_step(
        vx, dxi, dx, fx, vh, dhi, dh, fh, _fit(m_prev, Rp), _fit(bias, Rp),
        c_prev, pwl=pwl, block_rows=eff, interpret=interpret_mode())
    return c, h, m[:, :R] if Rp > R else m


def fused_brds_lstm_step_q8(sx, x, sh, h_prev, bias, c_prev, *,
                            act_scale_x=None, act_scale_h=None,
                            pwl: bool = False, block_rows: int = 256,
                            backend: str | None = None):
    """``brds_lstm_step_q8`` in ONE launch: int32 accumulate + per-row
    dequant feeding the gate nonlinearities in-register. Returns (c, h)."""
    qx, sax = _quant_act(x, sx, act_scale_x)
    qh, sah = _quant_act(h_prev, sh, act_scale_h)
    if _resolve(backend, None) == "ref":
        z = _ref.rb_dual_spmv_q8_ref(sx, qx, sax, sh, qh, sah, bias)
        H = z.shape[-1] // 4
        return _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                  z[:, 2 * H:3 * H], z[:, 3 * H:],
                                  c_prev, pwl=pwl)
    vx, dxi, cx, vh, dhi, ch, eff, Rp = _prep_parts_q8(sx, sax, sh, sah,
                                                       block_rows)
    return _fused.fused_brds_lstm_step_q8(vx, dxi, cx, qx, vh, dhi, ch, qh,
                                          _fit(bias, Rp), c_prev, pwl=pwl,
                                          block_rows=eff,
                                          interpret=interpret_mode())


def fused_brds_delta_lstm_step_q8(sx, dx, fx, sh, dh, fh, m_prev, bias,
                                  c_prev, *, act_scale_x=None,
                                  act_scale_h=None, pwl: bool = False,
                                  block_rows: int = 256,
                                  backend: str | None = None):
    """``brds_delta_lstm_step_q8`` in ONE launch: masked-delta int codes
    advance the fp32 partial-sum memory, bias applies on top, the cell
    closes — all VMEM-resident. Returns (c, h, m)."""
    dxm = jnp.where(fx.astype(bool), dx, 0).astype(dx.dtype)
    dhm = jnp.where(fh.astype(bool), dh, 0).astype(dh.dtype)
    qdx, sax = _quant_act(dxm, sx, act_scale_x)
    qdh, sah = _quant_act(dhm, sh, act_scale_h)
    if _resolve(backend, None) == "ref":
        m = _ref.delta_rb_dual_spmv_q8_ref(sx, qdx, sax, sh, qdh, sah,
                                           m_prev)
        z = m + bias[:m.shape[-1]].astype(jnp.float32)[None, :]
        H = z.shape[-1] // 4
        c, h = _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                  z[:, 2 * H:3 * H], z[:, 3 * H:],
                                  c_prev, pwl=pwl)
        return c, h, m
    R = sx.rows
    vx, dxi, cx, vh, dhi, ch, eff, Rp = _prep_parts_q8(sx, sax, sh, sah,
                                                       block_rows)
    c, h, m = _fused.fused_brds_delta_lstm_step_q8(
        vx, dxi, cx, qdx, vh, dhi, ch, qdh, _fit(m_prev, Rp),
        _fit(bias, Rp), c_prev, pwl=pwl, block_rows=eff,
        interpret=interpret_mode())
    return c, h, m[:, :R] if Rp > R else m


# ------------------------------------------------------- multi-token scan

def fused_brds_lstm_scan(sx: RowBalancedSparse, xs, sh: RowBalancedSparse,
                         h0, bias, c0, *, pwl: bool = False,
                         block_rows: int = 256,
                         backend: str | None = None):
    """T decode steps in ONE kernel launch. c/h stay in VMEM scratch
    across tokens; only the packed weight blocks are re-read from HBM per
    step (and can stay resident when they fit VMEM — see
    ``benchmarks/decode_throughput.py``'s crossover report). Trajectory
    is bitwise the T-times-repeated ``fused_brds_lstm_step``.

    xs (T, B, X); h0/c0 (B, H). Returns (hs (T, B, H), c_T)."""
    if _resolve(backend, None) == "ref":
        # python loop, NOT lax.scan: a traced scan body compiles into one
        # XLA computation whose fused mul+adds can contract (FMA) and
        # drift off the eagerly-dispatched per-step oracle
        c, h, hs = c0, h0, []
        for t in range(xs.shape[0]):
            z = _ref.rb_dual_spmv_ref(sx, xs[t], sh, h, bias)
            H = z.shape[-1] // 4
            c, h = _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                      z[:, 2 * H:3 * H], z[:, 3 * H:],
                                      c, pwl=pwl)
            hs.append(h)
        return jnp.stack(hs), c
    vx, dx, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dh, _, _, _ = _prep_rows(sh, block_rows)
    return _fused.fused_brds_lstm_scan(vx, dx, xs, vh, dh, h0,
                                       _fit(bias, Rp), c0, pwl=pwl,
                                       block_rows=eff,
                                       interpret=interpret_mode())


def fused_brds_delta_lstm_scan(sx: RowBalancedSparse, xs,
                               sh: RowBalancedSparse, h0, c0, x_ref0,
                               h_ref0, m0, bias, *, theta_x: float,
                               theta_h: float, pwl: bool = False,
                               block_rows: int = 256,
                               backend: str | None = None):
    """T temporally-sparse decode steps in ONE launch: thresholding,
    reference tracking, the partial-sum memory AND the cell all advance
    in VMEM scratch. Uncapped thresholds only (occupancy caps need
    ``top_k`` — callers fall back to per-step launches when one is set).

    xs (T, B, X); x_ref0/h_ref0 reference states; m0 (B, 4H) fp32 partial
    sums. Returns (hs, c_T, x_ref_T, h_ref_T, m_T)."""
    from ..sparse.temporal import delta_threshold
    if _resolve(backend, None) == "ref":
        # python loop, NOT lax.scan — see fused_brds_lstm_scan
        c, h, xr, hr, m = c0, h0, x_ref0, h_ref0, m0
        hs = []
        for t in range(xs.shape[0]):
            d_x, f_x, xr = delta_threshold(xs[t], xr, theta_x)
            d_h, f_h, hr = delta_threshold(h, hr, theta_h)
            m = _ref.delta_rb_dual_spmv_ref(
                sx, d_x, f_x.astype(jnp.float32), sh, d_h,
                f_h.astype(jnp.float32), m)
            z = (m.astype(jnp.float32)
                 + bias[:m.shape[-1]].astype(jnp.float32)[None, :])
            H = z.shape[-1] // 4
            c, h = _ref.lstm_cell_ref(z[:, :H], z[:, H:2 * H],
                                      z[:, 2 * H:3 * H], z[:, 3 * H:],
                                      c, pwl=pwl)
            hs.append(h)
        return jnp.stack(hs), c, xr, hr, m
    R = sx.rows
    vx, dxi, _, eff, Rp = _prep_rows(sx, block_rows)
    vh, dhi, _, _, _ = _prep_rows(sh, block_rows)
    hs, c, xr, hr, m = _fused.fused_brds_delta_lstm_scan(
        vx, dxi, xs, vh, dhi, h0, c0, x_ref0, h_ref0, _fit(m0, Rp),
        _fit(bias, Rp), theta_x=float(theta_x), theta_h=float(theta_h),
        pwl=pwl, block_rows=eff, interpret=interpret_mode())
    return hs, c, xr, hr, m[:, :R] if Rp > R else m


# ---------------------------------------------------------------- lstm cell

def lstm_gates(zf, zi, zg, zo, c_prev, *, pwl: bool = False,
               backend: str | None = None, use_kernel: bool | None = None):
    if _resolve(backend, use_kernel) == "ref":
        return _ref.lstm_cell_ref(zf, zi, zg, zo, c_prev, pwl=pwl)
    B, H = zf.shape
    if H <= 128:
        # one block spanning the whole (small) hidden axis
        return _lstm_gates_kernel(zf, zi, zg, zo, c_prev, pwl=pwl, block=H,
                                  interpret=interpret_mode())
    # lane-aligned blocks: odd hidden sizes pad to the next 128-multiple
    # and slice (the _pad_rows convention) instead of one giant block = H
    Hp = -(-H // 128) * 128
    block = next(b for b in (512, 256, 128) if Hp % b == 0)
    if Hp == H:
        return _lstm_gates_kernel(zf, zi, zg, zo, c_prev, pwl=pwl,
                                  block=block, interpret=interpret_mode())
    w = ((0, 0), (0, Hp - H))
    c, h = _lstm_gates_kernel(
        jnp.pad(zf, w), jnp.pad(zi, w), jnp.pad(zg, w), jnp.pad(zo, w),
        jnp.pad(c_prev, w), pwl=pwl, block=block, interpret=interpret_mode())
    return c[:, :H], h[:, :H]


# ---------------------------------------------------------------- attention

def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = 256, block_kv: int = 256,
                    backend: str | None = None,
                    use_kernel: bool | None = None):
    if _resolve(backend, use_kernel) == "ref":
        return _ref.mha_ref(q, k, v, causal=causal, window=window)
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    bq = max(g for g in (block_q, 128, 64, 32, 16, 8, 1) if Sq % g == 0)
    bk = max(g for g in (block_kv, 128, 64, 32, 16, 8, 1) if Sk % g == 0)
    return _flash_kernel(q, k, v, causal=causal, window=window, block_q=bq,
                         block_kv=bk, interpret=interpret_mode())


def decode_attention(q, k, v, lengths, *, block_kv: int = 512,
                     backend: str | None = None,
                     use_kernel: bool | None = None):
    if _resolve(backend, use_kernel) == "ref":
        return _ref.decode_attention_ref(q, k, v, lengths)
    S = k.shape[2]
    bk = max(g for g in (block_kv, 256, 128, 64, 32, 16, 8, 1) if S % g == 0)
    return _decode_kernel(q, k, v, lengths, block_kv=bk,
                          interpret=interpret_mode())


# ------------------------------------------------------------------ mamba-2

def mamba2_ssm_step(state, x, dt, A, Bm, Cm, D, *,
                    backend: str | None = None):
    """One Mamba-2 decode step over every slot and head: S ← exp(dt A) S
    + (dt x) ⊗ B, y = S C + D x. state (B, H, P, N) float32, updated in
    place by the kernel; x (B, H, P); dt (B, H); A, D (H,); Bm, Cm (B, G,
    N). Returns (new state, y (B, H, P))."""
    if _resolve(backend, None) == "ref":
        return _ref.mamba2_ssm_step_ref(state, x, dt, A, Bm, Cm, D)
    return _mamba2_ssm_kernel(state, x, dt, A, Bm, Cm, D,
                              interpret=interpret_mode())
