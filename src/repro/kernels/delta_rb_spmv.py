"""Pallas TPU kernel: temporal-delta SpMV over packed row-balanced weights.

This is the Spartus [Gao et al., 2021] composition on top of the BRDS
Gate-module MxV: the activation vector is a *delta* against a reference
state, thresholded on the host side into a fired-column mask, and the
kernel accumulates only (surviving row, changed column) products into the
partial-sum memory ``m``:

    m'[b, r] = m[b, r] + Σ_k vals[r, k] · fired[b, c] · d[b, c],
               c = cols[r, k]

- the weight side stays the paper's row-balanced packing (exactly K
  non-zeros per row, values + narrow delta-encoded column indices), so
  every grid step still does identical work per row — the balanced-PE
  invariant survives the temporal composition;
- the activation side masks the delta vector by its fired mask in VMEM
  and gathers the product lane-locally (``rb_spmv.gather_dot``); a
  column that did not cross the threshold Θ contributes an exact 0.0 to the accumulation — the product a real delta accelerator
  would never issue.  The occupancy (fired fraction) is the effective-ops
  metric `benchmarks/fig_delta_occupancy.py` sweeps;
- the dual variant processes the W_x and W_h packed families in the SAME
  grid step (the Large/Small mult-array lockstep of rb_dual_spmv) and
  fuses the partial-sum update, so one kernel launch advances the whole
  temporal gate preactivation.

Used on the memory-bound decode path: weight bytes already shrink by
(1 - weight sparsity); firing columns shrink the *compute* by the delta
occupancy — the two ratios multiply into the effective-ops reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .rb_spmv import (DEF_BLOCK_ROWS, acc_scratch, dual_gate, dual_scratch,
                      family_scratch, gather_dot, rows_spec, src_spec)


def _delta_rb_spmv_kernel(d_ref, f_ref, vals_ref, deltas_ref, out_ref,
                          fam_scr, acc_scr, *, K):
    """Grid step: one block of rows. d/f (B, Xp); vals/deltas (bR, Kp);
    out_ref (B, bR)."""
    dm = d_ref[...].astype(jnp.float32) * f_ref[...]               # (B, Xp)
    acc = gather_dot(dm, vals_ref, deltas_ref, fam_scr, acc_scr, K=K,
                     acc_dtype=jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def delta_rb_spmv(values, deltas, d, fired, *,
                  block_rows: int = DEF_BLOCK_ROWS, interpret: bool):
    """y[b, r] = Σ_k values[r, k] · fired[b, c] · d[b, c], c = cols[r, k].

    values: (R, K) float; deltas: (R, K) int8/16/32; d: (B, X) raw
    activation deltas; fired: (B, X) float32 0/1 threshold-crossing mask.
    Returns (B, R) in d.dtype. R must be a multiple of block_rows (the ops
    wrapper pads).
    """
    R, K = values.shape
    B, X = d.shape
    assert fired.shape == (B, X), (fired.shape, d.shape)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_delta_rb_spmv_kernel, K=K),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), src_spec(B, X), rows_spec(block_rows, K),
                  rows_spec(block_rows, K)],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, R), d.dtype),
        scratch_shapes=[family_scratch(B, block_rows, K, X, jnp.float32),
                        acc_scratch(B, block_rows, jnp.float32)],
        interpret=interpret,
        name="delta_rb_spmv",
    )(d, fired, values, deltas)


def _delta_rb_dual_kernel(dx_ref, fx_ref, dh_ref, fh_ref, vx_ref, ix_ref,
                          vh_ref, ih_ref, m_ref, out_ref, *scr, Kx, Kh):
    """One row block of m' = m + Sx@(fx·dx) + Sh@(fh·dh). Both packed
    families advance in the same step (Large/Small MA lockstep)."""
    dx = dx_ref[...].astype(jnp.float32) * fx_ref[...]
    dh = dh_ref[...].astype(jnp.float32) * fh_ref[...]
    accx, acch = dual_gate(dx, dh, vx_ref, ix_ref, vh_ref, ih_ref, scr,
                           Kx=Kx, Kh=Kh)
    m = m_ref[...].astype(jnp.float32) + accx + acch
    out_ref[...] = m.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def delta_rb_dual_spmv(vals_x, deltas_x, dx, fx, vals_h, deltas_h, dh, fh,
                       m, *, block_rows: int = DEF_BLOCK_ROWS,
                       interpret: bool):
    """m' = m + Sx @ (fx·dx) + Sh @ (fh·dh) for packed row-balanced
    Sx (R, Kx), Sh (R, Kh).

    dx: (B, X), dh: (B, H) raw deltas; fx/fh their float32 fired masks;
    m: (B, R) partial-sum memory. Returns (B, R) in m.dtype."""
    R, Kx = vals_x.shape
    _, Kh = vals_h.shape
    B, X = dx.shape
    H = dh.shape[1]
    assert vals_h.shape[0] == R and m.shape == (B, R)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_delta_rb_dual_kernel, Kx=Kx, Kh=Kh),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), src_spec(B, X), src_spec(B, H),
                  src_spec(B, H),
                  rows_spec(block_rows, Kx), rows_spec(block_rows, Kx),
                  rows_spec(block_rows, Kh), rows_spec(block_rows, Kh),
                  pl.BlockSpec((B, block_rows), lambda i: (0, i))],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, R), m.dtype),
        scratch_shapes=dual_scratch(B, block_rows, X, Kx, H, Kh),
        interpret=interpret,
        name="delta_rb_dual_spmv",
    )(dx, fx, dh, fh, vals_x, deltas_x, vals_h, deltas_h, m)
