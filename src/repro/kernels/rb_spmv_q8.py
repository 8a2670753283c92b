"""Pallas TPU kernels: QUANTIZED packed row-balanced SpMV.

The arithmetic-fidelity half of the BRDS datapath: the FPGA evaluates its
pruned LSTMs in fixed point (ESE ships 12-bit sparse weights, Spartus a
fixed-point spatio-temporal sparse LSTM), and on the TPU the same move
pays twice —

- the decode hot path is MEMORY bound, so int8 codes stream 4× fewer
  weight bytes HBM→VMEM than f32 (2× for an int16-stored qM.N), on top of
  the 1/(1-sparsity) packing gain;
- int8 × int8 products accumulate in int32 on the MXU at twice the bf16
  rate (``hw.PEAKS``).

Kernel structure mirrors the float kernels (rb_spmv / delta_rb_spmv) so
every invariant survives quantization: identical per-row work (row
balance), delta-encoded columns rebuilt in VMEM by the shared prefix sum
(relative addressing — quantization never moves a column), and the dual
variants advancing both weight families in the same grid step (Large/
Small mult-array lockstep). New here is the epilogue: the int32
accumulator is dequantized by ONE multiply per row — the per-row weight
scale pre-combined with the static activation scale — landing in the
existing fp32 partial-sum memory.

The wrappers (kernels.ops) quantize the activations; the kernels consume
integer codes only, so pallas↔ref parity is EXACT (integer accumulation
has no float re-association to disagree about).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .rb_spmv import (DEF_BLOCK_ROWS, acc_scratch, dual_gate, dual_scratch,
                      family_scratch, gather_dot, rows_spec, src_spec)


def _rb_spmv_q8_kernel(qx_ref, vals_ref, deltas_ref, scales_ref, out_ref,
                       fam_scr, acc_scr, *, K):
    """Grid step: one block of rows. qx (B, Xp) int codes; vals/deltas
    (bR, Kp); scales (1, bR) combined row·act dequant; out (B, bR) f32."""
    acc = gather_dot(qx_ref[...], vals_ref, deltas_ref, fam_scr, acc_scr,
                     K=K, acc_dtype=jnp.int32)
    out_ref[...] = acc.astype(jnp.float32) * scales_ref[...][0][None, :]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rb_spmv_q8(values, deltas, scales, qx, *,
               block_rows: int = DEF_BLOCK_ROWS, interpret: bool):
    """y[b, r] = scales[r] · Σ_k values[r, k] · qx[b, cols[r, k]].

    values: (R, K) int codes; deltas: (R, K) int8/16/32; scales: (R,)
    f32 combined (per-row weight scale × activation scale); qx: (B, X)
    int activation codes. Products accumulate in int32; the per-row
    dequant is the only float op. Returns (B, R) float32.
    """
    R, K = values.shape
    B, X = qx.shape
    assert scales.shape == (R,), (scales.shape, R)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_rb_spmv_q8_kernel, K=K),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), rows_spec(block_rows, K),
                  rows_spec(block_rows, K),
                  pl.BlockSpec((1, block_rows), lambda i: (0, i))],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
        scratch_shapes=[family_scratch(B, block_rows, K, X, jnp.int32),
                        acc_scratch(B, block_rows, jnp.int32)],
        interpret=interpret,
        name="rb_spmv_q8",
    )(qx, values, deltas, scales.reshape(1, R))


def dual_parts_q8(qx, qh, vx_ref, ix_ref, sx_ref, vh_ref, ih_ref, sh_ref,
                  scr, *, Kx, Kh):
    """One row block of the quantized dual MxV → (zx, zh): int32
    accumulation, then ONE dequant multiply per family and row."""
    accx, acch = dual_gate(qx, qh, vx_ref, ix_ref, vh_ref, ih_ref, scr,
                           Kx=Kx, Kh=Kh, acc_dtype=jnp.int32)
    zx = accx.astype(jnp.float32) * sx_ref[...][0][None, :]
    zh = acch.astype(jnp.float32) * sh_ref[...][0][None, :]
    return zx, zh


def _rb_dual_parts_q8_kernel(qx_ref, qh_ref, vx_ref, ix_ref, sx_ref,
                             vh_ref, ih_ref, sh_ref, zx_ref, zh_ref, *scr,
                             Kx, Kh):
    """One row block of the dual-family quantized MxV: both packed
    families advance in the same step (Large/Small MA lockstep), each
    int32 accumulator dequantizes with its own per-row scales.

    The kernel emits the TWO dequantized partial sums (zx, zh) instead of
    their total: the epilogue is then multiply-only, so XLA cannot
    FMA-contract a dequant multiply into an add and drift a last bit away
    from the reference twins — the wrapper performs the (shared, exact-
    order) adds. Integer work stays fully in-kernel."""
    zx_ref[...], zh_ref[...] = dual_parts_q8(
        qx_ref[...], qh_ref[...], vx_ref, ix_ref, sx_ref, vh_ref, ih_ref,
        sh_ref, scr, Kx=Kx, Kh=Kh)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rb_dual_parts_q8(vals_x, deltas_x, scales_x, qx, vals_h, deltas_h,
                     scales_h, qh, *, block_rows: int = DEF_BLOCK_ROWS,
                     interpret: bool):
    """(zx, zh) = (dq(Sx @ qx), dq(Sh @ qh)) — the quantized dual-ratio
    MxV pair underlying both the gate preactivation
    (``ops.rb_dual_spmv_q8``: zx + zh + bias) and the temporal partial-sum
    update (``ops.delta_rb_dual_spmv_q8``: m + zx + zh).

    scales_*: (R,) f32 combined (row × activation) dequant scales;
    qx (B, X) / qh (B, H) int codes. Returns two (B, R) float32 arrays.
    """
    R, Kx = vals_x.shape
    _, Kh = vals_h.shape
    B, X = qx.shape
    H = qh.shape[1]
    assert vals_h.shape[0] == R
    assert scales_x.shape == (R,) and scales_h.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    sblk = pl.BlockSpec((1, block_rows), lambda i: (0, i))
    oblk = pl.BlockSpec((B, block_rows), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_rb_dual_parts_q8_kernel, Kx=Kx, Kh=Kh),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), src_spec(B, H),
                  rows_spec(block_rows, Kx), rows_spec(block_rows, Kx), sblk,
                  rows_spec(block_rows, Kh), rows_spec(block_rows, Kh), sblk],
        out_specs=[oblk, oblk],
        out_shape=[jax.ShapeDtypeStruct((B, R), jnp.float32)] * 2,
        scratch_shapes=dual_scratch(B, block_rows, X, Kx, H, Kh,
                                    jnp.int32),
        interpret=interpret,
        name="rb_dual_parts_q8",
    )(qx, qh, vals_x, deltas_x, scales_x.reshape(1, R), vals_h, deltas_h,
      scales_h.reshape(1, R))
