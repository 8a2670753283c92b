"""Pallas TPU kernels: the WHOLE BRDS-LSTM decode step in one launch.

The paper's accelerator wins by computation overlapping: the Gate module's
MxV output streams through a Buffer straight into the Function module
(σ/tanh/⊙) without ever leaving the chip. Our chained decode path instead
launches 2–3 separate kernels per token (rb_dual_spmv → lstm_gates, plus
the delta partial-sum and q8 dequant variants) with HBM round-trips for
z, c, h and m between them. These kernels are the TPU analogue of the
paper's pipelined datapath — one ``pallas_call`` per layer step:

- the Gate stage runs the SAME per-row-block math as the chained kernels
  (``rb_spmv._rb_dual_kernel`` / ``delta_rb_spmv._delta_rb_dual_kernel`` /
  ``rb_spmv_q8._rb_dual_parts_q8_kernel``), writing each z block into a
  VMEM scratch instead of an HBM output;
- on the last row block the Function stage (``lstm_gates``'s cell math,
  including the PWL LUT mode) closes the cell from the VMEM-resident z —
  c and h never round-trip through HBM between the two stages.

Keeping the Gate stage's block shapes and op order IDENTICAL to the
chained kernels is what makes the fusion bitwise: the per-row K reduction
sees the same (B, block_rows, K) tiles, and the cell is elementwise (shape
changes cannot move a ulp). The ``kernels.ops`` wrappers assert this
parity bar in tests across packed / Θ=0 / Θ>0 delta / calibrated q8.

The multi-token SCAN variants go one step further (Spartus's degree of
fusion): grid (T, row-blocks) iterates T decode steps inside ONE launch,
holding c/h (and x_ref/h_ref/m for the delta path) in VMEM scratch across
steps and re-reading only the packed weight blocks from HBM. At high
sparsity + int8 the packed weights can fit VMEM outright — then even the
weight stream stays on-chip across tokens and decode approaches the
dispatch floor (the crossover `benchmarks/decode_throughput.py` measures).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lstm_gates import _LUT, _T, _pwl
from .rb_spmv import (DEF_BLOCK_ROWS, dual_gate, dual_scratch, lane_pad,
                      rows_spec, src_spec)
from .rb_spmv_q8 import dual_parts_q8


# ------------------------------------------------------------ shared stages
# The Gate stage is ``rb_spmv.dual_gate`` (float and masked-delta families)
# or ``rb_spmv_q8.dual_parts_q8`` (int codes) — the very functions the
# chained kernels call, so the per-row reduction is the same code.
#
# The q8 partial sums zx/zh MUST be stored to separate scratch buffers
# before being added (mirroring rb_spmv_q8.py's two-output no-FMA-
# contraction contract): any emitted fusion containing dequant-mul → add
# lets XLA contract them into an FMA and drift a bit off the chained path.
# A store's value is the bare multiply — exactly rounded — and adds on
# scratch reads have no multiply operand left to contract.

def _function_stage(lut_ref, z, c_prev, p_scr, H, pwl):
    """The Function module on a VMEM-resident z — the exact elementwise
    math of ``lstm_gates._lstm_gates_kernel`` (elementwise ops cannot
    drift across block shapes). z: (B, ≥4H); p_scr: (2, B, H) f32 VMEM
    scratch staging the cell's two products (see below);
    returns (c, h) float32."""
    f32 = jnp.float32
    zf = z[:, :H].astype(f32)
    zi = z[:, H:2 * H].astype(f32)
    zg = z[:, 2 * H:3 * H].astype(f32)
    zo = z[:, 3 * H:4 * H].astype(f32)
    if pwl:
        lut = lut_ref[...]
        lo, hi, n_seg = _T["lo"], _T["hi"], _T["n_seg"]
        sig = lambda v: _pwl(v, lut[0], lut[1], lo, hi, n_seg, 0.0, 1.0)
        th = lambda v: _pwl(v, lut[2], lut[3], lo, hi, n_seg, -1.0, 1.0)
    else:
        sig = jax.nn.sigmoid
        th = jnp.tanh
    f, i, g, o = sig(zf), sig(zi), th(zg), sig(zo)
    # c = f*c_prev + i*g with both products staged through VMEM scratch —
    # a stored product is exactly rounded and multi-use, so the compiler
    # cannot contract it into the add (fmuladd). The chained
    # ``lstm_gates`` kernel stages its cell identically, which is what
    # keeps step, scan and chained trajectories bitwise-identical: an
    # unstaged product's rounding depends on the surrounding kernel body.
    p_scr[0] = f * c_prev.astype(f32)
    p_scr[1] = i * g
    c = p_scr[0] + p_scr[1]
    h = o * th(c)
    return c, h


def _lut():
    return jnp.asarray(_LUT)


def _lut_spec():
    """Constant-index BlockSpec for the PWL LUT, for a grid of any rank."""
    return pl.BlockSpec(_LUT.shape, lambda *_: (0, 0))


# ------------------------------------------------------------- fused step

def _fused_step_kernel(lut_ref, x_ref, h_ref, c_ref, vx_ref, dx_ref, vh_ref,
                       dh_ref, b_ref, c_out_ref, h_out_ref, z_scr, p_scr,
                       *gscr, block_rows, nblk, H, pwl, Kx, Kh):
    i = pl.program_id(0)
    accx, acch = dual_gate(x_ref[...], h_ref[...], vx_ref, dx_ref, vh_ref,
                           dh_ref, gscr, Kx=Kx, Kh=Kh)
    z = accx + acch + b_ref[...].astype(jnp.float32)
    # the chained path writes z in x.dtype and re-reads it f32; replicate
    # the round-trip in VMEM so the fused trajectory stays bitwise
    z_scr[:, pl.dslice(i * block_rows, block_rows)] = z.astype(z_scr.dtype)

    @pl.when(i == nblk - 1)
    def _close_cell():
        c, h = _function_stage(lut_ref, z_scr[...], c_ref[...], p_scr, H,
                               pwl)
        c_out_ref[...] = c.astype(c_out_ref.dtype)
        h_out_ref[...] = h.astype(h_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pwl", "block_rows", "interpret"))
def fused_brds_lstm_step(vals_x, deltas_x, x, vals_h, deltas_h, h, bias,
                         c_prev, *, pwl: bool = False,
                         block_rows: int = DEF_BLOCK_ROWS,
                         interpret: bool):
    """One BRDS-LSTM decode step in ONE launch: dual-ratio SpMV + bias +
    gate nonlinearities + cell update, z/c/h VMEM-resident between the
    Gate and Function stages.

    vals/deltas: (R, K*) packed over the 4H gate rows (R a block_rows
    multiple — the ops wrapper pre-pads); x (B, X), h/c (B, H),
    bias (R,). Returns (c, h) in c_prev.dtype.
    """
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    B, X = x.shape
    H = h.shape[1]
    assert vals_h.shape[0] == R and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    bspec = pl.BlockSpec((1, block_rows), lambda i: (0, i))
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))
    c, h_out = pl.pallas_call(
        functools.partial(_fused_step_kernel, block_rows=block_rows,
                          nblk=nblk, H=H, pwl=pwl, Kx=Kx, Kh=Kh),
        grid=(nblk,),
        in_specs=[_lut_spec(), src_spec(B, X), src_spec(B, H), full((B, H)),
                  rows_spec(block_rows, Kx), rows_spec(block_rows, Kx),
                  rows_spec(block_rows, Kh), rows_spec(block_rows, Kh),
                  bspec],
        out_specs=[full((B, H)), full((B, H))],
        out_shape=[jax.ShapeDtypeStruct((B, H), c_prev.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((B, R), x.dtype),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh)],
        interpret=interpret,
        name="fused_brds_lstm_step",
    )(_lut(), x, h, c_prev, vals_x, deltas_x, vals_h, deltas_h,
      bias.reshape(1, R))
    return c, h_out


# ------------------------------------------------------- fused delta step

def _fused_delta_step_kernel(lut_ref, dx_ref, fx_ref, dh_ref, fh_ref, c_ref,
                             vx_ref, ix_ref, vh_ref, ih_ref, m_ref, b_ref,
                             c_out_ref, h_out_ref, m_out_ref, z_scr, p_scr,
                             *gscr, block_rows, nblk, H, pwl, Kx, Kh):
    i = pl.program_id(0)
    dxm = dx_ref[...].astype(jnp.float32) * fx_ref[...]
    dhm = dh_ref[...].astype(jnp.float32) * fh_ref[...]
    accx, acch = dual_gate(dxm, dhm, vx_ref, ix_ref, vh_ref, ih_ref, gscr,
                           Kx=Kx, Kh=Kh)
    m = m_ref[...].astype(jnp.float32) + accx + acch
    m_out_ref[...] = m.astype(m_out_ref.dtype)
    z_scr[:, pl.dslice(i * block_rows, block_rows)] = m

    @pl.when(i == nblk - 1)
    def _close_cell():
        z = z_scr[...] + b_ref[...].astype(jnp.float32)[0][None, :]
        c, h = _function_stage(lut_ref, z, c_ref[...], p_scr, H, pwl)
        c_out_ref[...] = c.astype(c_out_ref.dtype)
        h_out_ref[...] = h.astype(h_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pwl", "block_rows", "interpret"))
def fused_brds_delta_lstm_step(vals_x, deltas_x, dx, fx, vals_h, deltas_h,
                               dh, fh, m, bias, c_prev, *, pwl: bool = False,
                               block_rows: int = DEF_BLOCK_ROWS,
                               interpret: bool):
    """One temporally-sparse BRDS-LSTM step in ONE launch: fired-column
    masking + partial-sum memory update + bias + cell, m and z staying in
    VMEM between the Gate and Function stages.

    dx (B, X) / dh (B, H) raw deltas with f32 fired masks fx/fh;
    m (B, R) fp32 partial-sum memory (R block-padded by the wrapper).
    Returns (c, h, m')."""
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    B, X = dx.shape
    H = dh.shape[1]
    assert vals_h.shape[0] == R and m.shape == (B, R) and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))
    mblk = pl.BlockSpec((B, block_rows), lambda i: (0, i))
    c, h, m_out = pl.pallas_call(
        functools.partial(_fused_delta_step_kernel, block_rows=block_rows,
                          nblk=nblk, H=H, pwl=pwl, Kx=Kx, Kh=Kh),
        grid=(nblk,),
        in_specs=[_lut_spec(), src_spec(B, X), src_spec(B, X),
                  src_spec(B, H), src_spec(B, H), full((B, H)),
                  rows_spec(block_rows, Kx), rows_spec(block_rows, Kx),
                  rows_spec(block_rows, Kh), rows_spec(block_rows, Kh), mblk,
                  full((1, R))],
        out_specs=[full((B, H)), full((B, H)), mblk],
        out_shape=[jax.ShapeDtypeStruct((B, H), c_prev.dtype),
                   jax.ShapeDtypeStruct((B, H), c_prev.dtype),
                   jax.ShapeDtypeStruct((B, R), m.dtype)],
        scratch_shapes=[pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh)],
        interpret=interpret,
        name="fused_brds_delta_lstm_step",
    )(_lut(), dx, fx, dh, fh, c_prev, vals_x, deltas_x, vals_h, deltas_h,
      m, bias.reshape(1, R))
    return c, h, m_out


# --------------------------------------------------------- fused q8 steps

def _fused_step_q8_kernel(lut_ref, qx_ref, qh_ref, c_ref, vx_ref, ix_ref,
                          sx_ref, vh_ref, ih_ref, sh_ref, b_ref, c_out_ref,
                          h_out_ref, zx_scr, zh_scr, p_scr, *gscr,
                          block_rows, nblk, H, pwl, Kx, Kh):
    i = pl.program_id(0)
    zx, zh = dual_parts_q8(qx_ref[...], qh_ref[...], vx_ref, ix_ref, sx_ref,
                           vh_ref, ih_ref, sh_ref, gscr, Kx=Kx, Kh=Kh)
    sl = pl.dslice(i * block_rows, block_rows)
    zx_scr[:, sl] = zx
    zh_scr[:, sl] = zh

    @pl.when(i == nblk - 1)
    def _close_cell():
        z = (zx_scr[...] + zh_scr[...]
             + b_ref[...].astype(jnp.float32)[0][None, :])
        c, h = _function_stage(lut_ref, z, c_ref[...], p_scr, H, pwl)
        c_out_ref[...] = c.astype(c_out_ref.dtype)
        h_out_ref[...] = h.astype(h_out_ref.dtype)


def _q8_specs(B, X, H, R, block_rows, Kx, Kh):
    """in_specs of the q8 fused steps' shared leading operands: LUT, the
    two code vectors, c, and both families' (values, deltas, scales)."""
    sblk = pl.BlockSpec((1, block_rows), lambda i: (0, i))
    return [_lut_spec(), src_spec(B, X), src_spec(B, H),
            pl.BlockSpec((B, H), lambda i: (0, 0)),
            rows_spec(block_rows, Kx), rows_spec(block_rows, Kx), sblk,
            rows_spec(block_rows, Kh), rows_spec(block_rows, Kh), sblk]


@functools.partial(jax.jit,
                   static_argnames=("pwl", "block_rows", "interpret"))
def fused_brds_lstm_step_q8(vals_x, deltas_x, scales_x, qx, vals_h, deltas_h,
                            scales_h, qh, bias, c_prev, *, pwl: bool = False,
                            block_rows: int = DEF_BLOCK_ROWS,
                            interpret: bool):
    """One QUANTIZED BRDS-LSTM step in ONE launch: int32 accumulate +
    per-row dequant feeding the gate nonlinearities in VMEM.

    vals: (R, K*) int codes; scales: (R,) f32 combined row×act dequant;
    qx (B, X) / qh (B, H) int activation codes (the ops wrapper quantizes,
    so pallas and ref consume the SAME codes). Returns (c, h)."""
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    B, X = qx.shape
    H = qh.shape[1]
    assert vals_h.shape[0] == R and bias.shape == (R,)
    assert scales_x.shape == (R,) and scales_h.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))
    c, h = pl.pallas_call(
        functools.partial(_fused_step_q8_kernel, block_rows=block_rows,
                          nblk=nblk, H=H, pwl=pwl, Kx=Kx, Kh=Kh),
        grid=(nblk,),
        in_specs=[*_q8_specs(B, X, H, R, block_rows, Kx, Kh), full((1, R))],
        out_specs=[full((B, H)), full((B, H))],
        out_shape=[jax.ShapeDtypeStruct((B, H), c_prev.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh,
                                      jnp.int32)],
        interpret=interpret,
        name="fused_brds_lstm_step_q8",
    )(_lut(), qx, qh, c_prev, vals_x, deltas_x, scales_x.reshape(1, R),
      vals_h, deltas_h, scales_h.reshape(1, R), bias.reshape(1, R))
    return c, h


def _fused_delta_step_q8_kernel(lut_ref, qdx_ref, qdh_ref, c_ref, vx_ref,
                                ix_ref, sx_ref, vh_ref, ih_ref, sh_ref,
                                m_ref, b_ref, c_out_ref, h_out_ref,
                                m_out_ref, zx_scr, zh_scr, p_scr, *gscr,
                                block_rows, nblk, H, pwl, Kx, Kh):
    i = pl.program_id(0)
    zx, zh = dual_parts_q8(qdx_ref[...], qdh_ref[...], vx_ref, ix_ref,
                           sx_ref, vh_ref, ih_ref, sh_ref, gscr, Kx=Kx,
                           Kh=Kh)
    sl = pl.dslice(i * block_rows, block_rows)
    zx_scr[:, sl] = zx
    zh_scr[:, sl] = zh

    @pl.when(i == nblk - 1)
    def _close_cell():
        m = m_ref[...].astype(jnp.float32) + zx_scr[...] + zh_scr[...]
        m_out_ref[...] = m.astype(m_out_ref.dtype)
        z = m + b_ref[...].astype(jnp.float32)[0][None, :]
        c, h = _function_stage(lut_ref, z, c_ref[...], p_scr, H, pwl)
        c_out_ref[...] = c.astype(c_out_ref.dtype)
        h_out_ref[...] = h.astype(h_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pwl", "block_rows", "interpret"))
def fused_brds_delta_lstm_step_q8(vals_x, deltas_x, scales_x, qdx, vals_h,
                                  deltas_h, scales_h, qdh, m, bias, c_prev,
                                  *, pwl: bool = False,
                                  block_rows: int = DEF_BLOCK_ROWS,
                                  interpret: bool):
    """One QUANTIZED temporally-sparse step in ONE launch: masked-delta
    int codes advance the fp32 partial-sum memory, bias applies on top,
    the Function stage closes the cell — all VMEM-resident.

    qdx/qdh are int codes of the MASKED deltas (exact 0 where unfired).
    Returns (c, h, m')."""
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    B, X = qdx.shape
    H = qdh.shape[1]
    assert vals_h.shape[0] == R and m.shape == (B, R) and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0, 0))
    c, h, m_out = pl.pallas_call(
        functools.partial(_fused_delta_step_q8_kernel,
                          block_rows=block_rows, nblk=nblk, H=H, pwl=pwl,
                          Kx=Kx, Kh=Kh),
        grid=(nblk,),
        in_specs=[*_q8_specs(B, X, H, R, block_rows, Kx, Kh), full((B, R)),
                  full((1, R))],
        out_specs=[full((B, H)), full((B, H)), full((B, R))],
        out_shape=[jax.ShapeDtypeStruct((B, H), c_prev.dtype),
                   jax.ShapeDtypeStruct((B, H), c_prev.dtype),
                   jax.ShapeDtypeStruct((B, R), m.dtype)],
        scratch_shapes=[pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh,
                                      jnp.int32)],
        interpret=interpret,
        name="fused_brds_delta_lstm_step_q8",
    )(_lut(), qdx, qdh, c_prev, vals_x, deltas_x, scales_x.reshape(1, R),
      vals_h, deltas_h, scales_h.reshape(1, R), m, bias.reshape(1, R))
    return c, h, m_out


# ---------------------------------------------------- multi-token scan
# The scans keep h (and the delta path's reference/masked-delta vectors)
# in lane-padded VMEM scratch: the Gate stage gathers from whole 128-lane
# chunks, and lanes past H/X are never selected, so they need no init.

def _store_lanes(ref, v):
    """Write a (B, n) value into the first n lanes of a lane-padded
    (B, ≥n) scratch."""
    if ref.shape[1] == v.shape[1]:
        ref[...] = v
    else:
        ref[:, :v.shape[1]] = v


def _fused_scan_kernel(lut_ref, xs_ref, h0_ref, c0_ref, vx_ref, dx_ref,
                       vh_ref, dh_ref, b_ref, hs_ref, c_out_ref, z_scr,
                       h_scr, c_scr, p_scr, *gscr, block_rows, nblk, H, pwl,
                       Kx, Kh):
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(t == 0, j == 0))
    def _load_state():
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...]

    accx, acch = dual_gate(xs_ref[...][0], h_scr[...], vx_ref, dx_ref,
                           vh_ref, dh_ref, gscr, Kx=Kx, Kh=Kh)
    z = accx + acch + b_ref[...].astype(jnp.float32)
    z_scr[:, pl.dslice(j * block_rows, block_rows)] = z.astype(z_scr.dtype)

    @pl.when(j == nblk - 1)
    def _close_cell():
        c, h = _function_stage(lut_ref, z_scr[...], c_scr[...], p_scr, H,
                               pwl)
        c_scr[...] = c.astype(c_scr.dtype)
        _store_lanes(h_scr, h.astype(h_scr.dtype))
        hs_ref[...] = h.astype(hs_ref.dtype)[None]
        c_out_ref[...] = c.astype(c_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pwl", "block_rows", "interpret"))
def fused_brds_lstm_scan(vals_x, deltas_x, xs, vals_h, deltas_h, h0, bias,
                         c0, *, pwl: bool = False,
                         block_rows: int = DEF_BLOCK_ROWS,
                         interpret: bool):
    """T BRDS-LSTM decode steps inside ONE kernel launch.

    Grid (T, row-blocks): c and h live in VMEM scratch across steps, so
    between tokens only the packed weight blocks are re-read from HBM
    (and when they fit VMEM the hardware can keep them resident — the
    paper's computation overlapping taken to its limit). Each step's math
    is the fused single-step kernel's, so the trajectory is bitwise the
    T-times-repeated ``fused_brds_lstm_step``.

    xs: (T, B, X); h0/c0: (B, H). Returns (hs (T, B, H), c_T)."""
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    T, B, X = xs.shape
    H = h0.shape[1]
    assert vals_h.shape[0] == R and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    full = lambda shp: pl.BlockSpec(shp, lambda t, j: (0, 0))
    rblk = lambda K: rows_spec(block_rows, K, lambda t, j: (j, 0))
    hs, c = pl.pallas_call(
        functools.partial(_fused_scan_kernel, block_rows=block_rows,
                          nblk=nblk, H=H, pwl=pwl, Kx=Kx, Kh=Kh),
        grid=(T, nblk),
        in_specs=[_lut_spec(),
                  pl.BlockSpec((1, B, lane_pad(X)), lambda t, j: (t, 0, 0)),
                  src_spec(B, H), full((B, H)), rblk(Kx), rblk(Kx),
                  rblk(Kh), rblk(Kh),
                  pl.BlockSpec((1, block_rows), lambda t, j: (0, j))],
        out_specs=[pl.BlockSpec((1, B, H), lambda t, j: (t, 0, 0)),
                   full((B, H))],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), h0.dtype),
                   jax.ShapeDtypeStruct((B, H), c0.dtype)],
        scratch_shapes=[pltpu.VMEM((B, R), xs.dtype),
                        pltpu.VMEM((B, lane_pad(H)), h0.dtype),
                        pltpu.VMEM((B, H), c0.dtype),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh)],
        interpret=interpret,
        name="fused_brds_lstm_scan",
    )(_lut(), xs, h0, c0, vals_x, deltas_x, vals_h, deltas_h,
      bias.reshape(1, R))
    return hs, c


def _fused_delta_scan_kernel(lut_ref, xs_ref, h0_ref, c0_ref, xr0_ref,
                             hr0_ref, m0_ref, vx_ref, ix_ref, vh_ref, ih_ref,
                             b_ref, hs_ref, c_out_ref, xr_out_ref,
                             hr_out_ref, m_out_ref, h_scr, c_scr, xr_scr,
                             hr_scr, dxm_scr, dhm_scr, m_scr, p_scr, *gscr,
                             block_rows, nblk, H, pwl, theta_x, theta_h,
                             Kx, Kh):
    t, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(jnp.logical_and(t == 0, j == 0))
    def _load_state():
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...]
        xr_scr[...] = xr0_ref[...]
        hr_scr[...] = hr0_ref[...]
        m_scr[...] = m0_ref[...].astype(f32)

    @pl.when(j == 0)
    def _threshold():
        # in-kernel delta_threshold (repro.sparse.temporal), uncapped:
        # same elementwise ops as the host-side version, on VMEM state
        x = xs_ref[...][0]
        d = (x - xr_scr[...]).astype(x.dtype)
        fired = jnp.abs(d) > theta_x
        xr_scr[...] = jnp.where(fired, x, xr_scr[...])
        dxm_scr[...] = d.astype(f32) * fired.astype(f32)
        hv = h_scr[...]
        dh = (hv - hr_scr[...]).astype(hv.dtype)
        fired_h = jnp.abs(dh) > theta_h
        hr_scr[...] = jnp.where(fired_h, hv, hr_scr[...])
        dhm_scr[...] = dh.astype(f32) * fired_h.astype(f32)

    accx, acch = dual_gate(dxm_scr[...], dhm_scr[...], vx_ref, ix_ref,
                           vh_ref, ih_ref, gscr, Kx=Kx, Kh=Kh)
    sl = pl.dslice(j * block_rows, block_rows)
    m_scr[:, sl] = m_scr[:, sl].astype(f32) + accx + acch

    @pl.when(j == nblk - 1)
    def _close_cell():
        z = m_scr[...] + b_ref[...].astype(f32)[0][None, :]
        c, h = _function_stage(lut_ref, z, c_scr[...], p_scr, H, pwl)
        c_scr[...] = c.astype(c_scr.dtype)
        _store_lanes(h_scr, h.astype(h_scr.dtype))
        hs_ref[...] = h.astype(hs_ref.dtype)[None]
        c_out_ref[...] = c.astype(c_out_ref.dtype)
        xr_out_ref[...] = xr_scr[...]
        hr_out_ref[...] = hr_scr[...]
        m_out_ref[...] = m_scr[...].astype(m_out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("theta_x", "theta_h", "pwl",
                                    "block_rows", "interpret"))
def fused_brds_delta_lstm_scan(vals_x, deltas_x, xs, vals_h, deltas_h, h0,
                               c0, x_ref0, h_ref0, m0, bias, *,
                               theta_x: float, theta_h: float,
                               pwl: bool = False,
                               block_rows: int = DEF_BLOCK_ROWS,
                               interpret: bool):
    """T temporally-sparse decode steps inside ONE kernel launch: the
    delta thresholding, reference-state tracking, partial-sum memory AND
    the cell all advance in VMEM scratch; only packed weight blocks are
    re-read from HBM between tokens. Uncapped thresholds only (the
    occupancy cap's top_k runs host-side — the ops wrapper falls back to
    per-step launches when a cap is set).

    xs (T, B, X); x_ref0 (B, X) / h_ref0 (B, H) reference states;
    m0 (B, R) fp32 partial sums. Returns (hs, c_T, x_ref_T, h_ref_T, m_T).
    """
    R, Kx = vals_x.shape
    Kh = vals_h.shape[1]
    T, B, X = xs.shape
    H = h0.shape[1]
    assert vals_h.shape[0] == R and m0.shape == (B, R) and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    nblk = R // block_rows
    Xp, Hp = lane_pad(X), lane_pad(H)
    full = lambda shp: pl.BlockSpec(shp, lambda t, j: (0, 0))
    rblk = lambda K: rows_spec(block_rows, K, lambda t, j: (j, 0))
    hs, c, xr, hr, m = pl.pallas_call(
        functools.partial(_fused_delta_scan_kernel, block_rows=block_rows,
                          nblk=nblk, H=H, pwl=pwl, theta_x=theta_x,
                          theta_h=theta_h, Kx=Kx, Kh=Kh),
        grid=(T, nblk),
        in_specs=[_lut_spec(),
                  pl.BlockSpec((1, B, Xp), lambda t, j: (t, 0, 0)),
                  full((B, Hp)), full((B, H)), full((B, Xp)), full((B, Hp)),
                  full((B, R)), rblk(Kx), rblk(Kx), rblk(Kh), rblk(Kh),
                  full((1, R))],
        out_specs=[pl.BlockSpec((1, B, H), lambda t, j: (t, 0, 0)),
                   full((B, H)), full((B, Xp)), full((B, Hp)),
                   full((B, R))],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), h0.dtype),
                   jax.ShapeDtypeStruct((B, H), c0.dtype),
                   jax.ShapeDtypeStruct((B, X), x_ref0.dtype),
                   jax.ShapeDtypeStruct((B, H), h_ref0.dtype),
                   jax.ShapeDtypeStruct((B, R), m0.dtype)],
        scratch_shapes=[pltpu.VMEM((B, Hp), h0.dtype),
                        pltpu.VMEM((B, H), c0.dtype),
                        pltpu.VMEM((B, Xp), x_ref0.dtype),
                        pltpu.VMEM((B, Hp), h_ref0.dtype),
                        pltpu.VMEM((B, Xp), jnp.float32),
                        pltpu.VMEM((B, Hp), jnp.float32),
                        pltpu.VMEM((B, R), jnp.float32),
                        pltpu.VMEM((2, B, H), jnp.float32),
                        *dual_scratch(B, block_rows, X, Kx, H, Kh)],
        interpret=interpret,
        name="fused_brds_delta_lstm_scan",
    )(_lut(), xs, h0, c0, x_ref0, h_ref0, m0, vals_x, deltas_x, vals_h,
      deltas_h, bias.reshape(1, R))
    return hs, c, xr, hr, m
