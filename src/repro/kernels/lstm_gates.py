"""Pallas TPU kernel: fused LSTM cell elementwise update (paper's Function +
Buffer modules).

On the FPGA, the Gate module's MxV output streams through a Buffer into the
Function module (σ/tanh/⊙) so activation traffic never leaves the chip.
The TPU analogue: one kernel consumes the four gate preactivations and
c_{t-1} tile-by-tile from VMEM and emits (c_t, h_t) — no HBM round-trip for
the intermediate gate activations, double-buffered DMAs across grid steps.

Supports the paper's piecewise-linear activation mode (16-segment LUT,
out = a·x + b per segment) as a static option, matching the fixed-point
datapath study. The LUT coefficients ride in as a (4, n_seg) kernel input
(the BRAM LUT analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import pwl_tables

DEF_BLOCK = 512
_T = pwl_tables()
# rows: a_sig, b_sig, a_tanh, b_tanh
_LUT = np.stack([_T["sig"][0], _T["sig"][1], _T["tanh"][0], _T["tanh"][1]])


def _pwl(x, a, b, lo, hi, n_seg, sat_lo, sat_hi):
    xc = jnp.clip(x, lo, hi - 1e-6)
    idx = jnp.clip(jnp.floor((xc - lo) / (hi - lo) * n_seg).astype(jnp.int32),
                   0, n_seg - 1)
    y = a[idx] * xc + b[idx]
    return jnp.where(x < lo, sat_lo, jnp.where(x >= hi, sat_hi, y))


def _lstm_gates_kernel(lut_ref, zf_ref, zi_ref, zg_ref, zo_ref, c_ref,
                       c_out_ref, h_out_ref, p_scr, *, pwl: bool):
    f32 = jnp.float32
    zf, zi = zf_ref[...].astype(f32), zi_ref[...].astype(f32)
    zg, zo = zg_ref[...].astype(f32), zo_ref[...].astype(f32)
    c_prev = c_ref[...].astype(f32)
    if pwl:
        lut = lut_ref[...]
        lo, hi, n_seg = _T["lo"], _T["hi"], _T["n_seg"]
        sig = lambda v: _pwl(v, lut[0], lut[1], lo, hi, n_seg, 0.0, 1.0)
        th = lambda v: _pwl(v, lut[2], lut[3], lo, hi, n_seg, -1.0, 1.0)
    else:
        sig = jax.nn.sigmoid
        th = jnp.tanh
    f, i, g, o = sig(zf), sig(zi), th(zg), sig(zo)
    # c = f*c_prev + i*g, with each product staged through VMEM scratch:
    # a stored product is exactly rounded and multi-use, so the compiler
    # cannot contract it into the add (fmuladd) — the cell rounds the
    # same way in every kernel that inlines this math (the fused
    # single-step and multi-token-scan kernels replicate it bitwise)
    p_scr[0] = f * c_prev
    p_scr[1] = i * g
    c = p_scr[0] + p_scr[1]
    h = o * th(c)
    c_out_ref[...] = c.astype(c_out_ref.dtype)
    h_out_ref[...] = h.astype(h_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pwl", "block", "interpret"))
def lstm_gates(zf, zi, zg, zo, c_prev, *, pwl: bool = False,
               block: int = DEF_BLOCK, interpret: bool):
    """Fused elementwise LSTM cell. All inputs (B, H); returns (c_t, h_t)."""
    B, H = zf.shape
    block = min(block, H)
    assert H % block == 0, (H, block)
    grid = (H // block,)
    spec = pl.BlockSpec((B, block), lambda i: (0, i))
    lut = jnp.asarray(_LUT)
    lut_spec = pl.BlockSpec(lut.shape, lambda i: (0, 0))
    c, h = pl.pallas_call(
        functools.partial(_lstm_gates_kernel, pwl=pwl),
        grid=grid,
        in_specs=[lut_spec] + [spec] * 5,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((B, H), c_prev.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((2, B, block), jnp.float32)],
        interpret=interpret,
        name="lstm_gates",
    )(lut, zf, zi, zg, zo, c_prev)
    return c, h
