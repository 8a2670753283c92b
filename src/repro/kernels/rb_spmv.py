"""Pallas TPU kernel: packed row-balanced sparse matrix × dense vector(s).

This is the BRDS accelerator's Gate-module MxV, adapted to TPU:

- every row has exactly K non-zeros → every grid step does identical work
  (the paper's row-balanced PE utilization argument, restated for VMEM
  tiles);
- only (R, K) values + narrow delta indices stream HBM→VMEM (the relative-
  addressing memory saving);
- the dual-ratio variant processes the W_x and W_h packed matrices in the
  SAME grid step so both families advance in lockstep — the Large/Small
  mult-array co-scheduling, with per-step work automatically proportional
  to K_x : K_h exactly like R_L : R_S sizing.

Inside a row block the packed stream is decoded in VMEM (``gather_dot``):
absolute columns come from a log-step prefix sum of the relative deltas
along lanes (``pltpu.roll`` + select — Mosaic has no cumsum), and the
activation gather is LANE-LOCAL: the TPU gathers only
within one 128-lane vreg, so x is split into 128-lane chunks, each chunk
is gathered by ``col % 128`` and kept where ``col // 128`` names it. The
gathered tile is multiplied by the values and accumulated per 128-lane
K-chunk, then reduced across lanes once per row block. Lanes past K (the
block is rounded up to a lane multiple) carry zero deltas and zero values,
so they add exact zeros.

Used on the memory-bound decode path, where bytes (not FLOPs) dominate:
effective-throughput gain ≈ 1/(1-sparsity), the paper's headline metric.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEF_BLOCK_ROWS = 256
LANES = 128          # vreg lane width: the TPU gathers within one vreg only
LANE_BITS = 7        # log2(LANES)
SUBLANES = 8         # rows per gather tile (one 32-bit vreg is 8 x 128)


def lane_pad(n: int) -> int:
    """``n`` rounded up to a lane multiple — the VMEM width of a packed or
    activation block (blocks past the array edge are partial; the kernels
    never read their padding lanes)."""
    return -(-n // LANES) * LANES


def _columns(deltas, K):
    """(bR, Kp) int32 absolute columns from the block's relative deltas.

    A Hillis–Steele prefix sum along lanes: log2(Kp) rounds of roll +
    masked add. Lanes ≥ K (block padding) are zeroed first, so they repeat
    the row's last column — an in-range index whose value is zeroed."""
    d = deltas.astype(jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, d.shape, 1)
    cols = jnp.where(lane < K, d, 0)
    shift = 1
    while shift < d.shape[1]:
        cols = cols + jnp.where(lane >= shift,
                                pltpu.roll(cols, shift, 1), 0)
        shift *= 2
    return cols


def family_scratch(block_rows: int, K: int, acc_dtype):
    """VMEM scratch one packed family decodes into: columns + values."""
    Kp = lane_pad(K)
    return [pltpu.VMEM((block_rows, Kp), jnp.int32),
            pltpu.VMEM((block_rows, Kp), acc_dtype)]


def acc_scratch(B: int, block_rows: int, acc_dtype):
    """Per-lane partial sums of up to one sublane group of batch rows."""
    return pltpu.VMEM((min(B, SUBLANES), block_rows, LANES), acc_dtype)


def gather_dot(src, vals_ref, deltas_ref, cols_scr, vals_scr, acc_scr, *,
               K: int, acc_dtype):
    """acc[b, r] = Σ_k vals[r, k] · src[b, cols[r, k]] for one row block.

    ``src`` (B, ≥lane_pad(X)) activations as a value (its lanes past X are
    never selected); ``vals_ref``/``deltas_ref`` the (bR, Kp) packed block;
    ``cols_scr``/``vals_scr`` (bR, Kp) 32-bit decode scratch; ``acc_scr``
    from ``acc_scratch``. Float families accumulate in f32, integer codes
    in int32 (exact). Returns (B, bR) ``acc_dtype``.

    The per-row arithmetic — chunked lane products summed over K-chunks,
    then one lane reduction — depends on neither the block's row count nor
    its position, which is what keeps every kernel sharing this function
    bitwise-consistent with the others."""
    cols_scr[...] = _columns(deltas_ref[...], K)
    lane = lax.broadcasted_iota(jnp.int32, vals_scr.shape, 1)
    vals_scr[...] = jnp.where(lane < K, vals_ref[...].astype(acc_dtype), 0)
    bR, Kp = cols_scr.shape
    B = src.shape[0]
    G = acc_scr.shape[0]
    nx = src.shape[1] // LANES
    # integer codes (|q| ≤ 32767) are exact in f32: gathering every family
    # in f32 keeps one chunk layout that Mosaic accepts at every batch
    src = src.astype(jnp.float32)
    rows = []
    for b0 in range(0, B, G):
        g_rows = range(b0, min(b0 + G, B))
        # one batch row's 128-lane chunk, replicated over 8 sublanes. The
        # zero add gives the sliced row a fresh (1, 128) layout: Mosaic
        # cannot broadcast a row sliced out of a block of fewer than 8
        # rows ("Invalid input layout"), which every B < 8 would hit
        zero = jnp.zeros((1, LANES), jnp.float32)
        chunks = [[jnp.broadcast_to(
                       src[b:b + 1, c * LANES:(c + 1) * LANES] + zero,
                       (SUBLANES, LANES)) for c in range(nx)]
                  for b in g_rows]

        def tile(t, carry, chunks=chunks, n=len(g_rows)):
            r0 = pl.multiple_of(t * SUBLANES, SUBLANES)
            accs = [jnp.zeros((SUBLANES, LANES), acc_dtype)] * n
            for kc in range(Kp // LANES):
                sl = (pl.ds(r0, SUBLANES), pl.ds(kc * LANES, LANES))
                idx, v = cols_scr[sl], vals_scr[sl]
                lo, hi = idx & (LANES - 1), idx >> LANE_BITS
                gs = [jnp.zeros((SUBLANES, LANES), jnp.float32)] * n
                for c in range(nx):
                    hit = hi == c
                    gs = [jnp.where(hit, jnp.take_along_axis(
                              chunks[j][c], lo, axis=1,
                              mode="promise_in_bounds"), gs[j])
                          for j in range(n)]
                accs = [accs[j] + gs[j].astype(acc_dtype) * v
                        for j in range(n)]
            for j in range(n):
                acc_scr[j, pl.ds(r0, SUBLANES), :] = accs[j]
            return carry

        lax.fori_loop(0, bR // SUBLANES, tile, 0)
        rows += [jnp.sum(acc_scr[j], axis=1) for j in range(len(g_rows))]
    return jnp.stack(rows, axis=0)


def src_spec(B: int, X: int, index_map=lambda *_: (0, 0)):
    """BlockSpec for a (B, X) activation the kernels gather from: one
    lane-padded block (partial past X)."""
    return pl.BlockSpec((B, lane_pad(X)), index_map)


def rows_spec(block_rows: int, K: int, index_map=lambda i: (i, 0)):
    """BlockSpec for a (R, K) packed array: one row block, lane-padded."""
    return pl.BlockSpec((block_rows, lane_pad(K)), index_map)


def _rb_spmv_kernel(x_ref, vals_ref, deltas_ref, out_ref, cols_scr, vals_scr,
                    acc_scr, *, K):
    """Grid step: one block of rows. x_ref (B, Xp); vals/deltas (bR, Kp);
    out_ref (B, bR)."""
    acc = gather_dot(x_ref[...], vals_ref, deltas_ref, cols_scr, vals_scr,
                     acc_scr, K=K, acc_dtype=jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rb_spmv(values, deltas, x, *, block_rows: int = DEF_BLOCK_ROWS,
            interpret: bool):
    """y[b, r] = Σ_k values[r, k] · x[b, cols[r, k]].

    values: (R, K) float; deltas: (R, K) int8/16/32; x: (B, X).
    Returns (B, R) in x.dtype. R must be a multiple of block_rows (the ops
    wrapper pads).
    """
    R, K = values.shape
    B, X = x.shape
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_rb_spmv_kernel, K=K),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), rows_spec(block_rows, K),
                  rows_spec(block_rows, K)],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, R), x.dtype),
        scratch_shapes=[*family_scratch(block_rows, K, jnp.float32),
                        acc_scratch(B, block_rows, jnp.float32)],
        interpret=interpret,
        name="rb_spmv",
    )(x, values, deltas)


def dual_gate(x, h, vx_ref, dx_ref, vh_ref, dh_ref, scr, *, Kx, Kh,
              acc_dtype=jnp.float32):
    """Both packed families of one row block (Large/Small MA lockstep):
    → (Sx@x, Sh@h) partial sums, (B, bR) each. ``scr`` is the scratch
    list ``dual_scratch`` declared."""
    cx, vx, ch, vh, acc = scr
    accx = gather_dot(x, vx_ref, dx_ref, cx, vx, acc, K=Kx,
                      acc_dtype=acc_dtype)
    acch = gather_dot(h, vh_ref, dh_ref, ch, vh, acc, K=Kh,
                      acc_dtype=acc_dtype)
    return accx, acch


def dual_scratch(B: int, block_rows: int, Kx: int, Kh: int,
                 acc_dtype=jnp.float32):
    return [*family_scratch(block_rows, Kx, acc_dtype),
            *family_scratch(block_rows, Kh, acc_dtype),
            acc_scratch(B, block_rows, acc_dtype)]


def _rb_dual_kernel(x_ref, h_ref, vx_ref, dx_ref, vh_ref, dh_ref, b_ref,
                    out_ref, *scr, Kx, Kh):
    """One row block of z = Sx@x + Sh@h + bias. Both packed families are
    consumed in the same step (Large/Small MA lockstep)."""
    accx, acch = dual_gate(x_ref[...], h_ref[...], vx_ref, dx_ref, vh_ref,
                           dh_ref, scr, Kx=Kx, Kh=Kh)
    z = accx + acch + b_ref[...].astype(jnp.float32)
    out_ref[...] = z.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def rb_dual_spmv(vals_x, deltas_x, x, vals_h, deltas_h, h, bias, *,
                 block_rows: int = DEF_BLOCK_ROWS, interpret: bool):
    """z = Sx @ x + Sh @ h + bias for packed row-balanced Sx (R,Kx), Sh (R,Kh).

    x: (B, X), h: (B, H), bias: (R,). Returns (B, R)."""
    R, Kx = vals_x.shape
    _, Kh = vals_h.shape
    B, X = x.shape
    H = h.shape[1]
    assert vals_h.shape[0] == R and bias.shape == (R,)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_rb_dual_kernel, Kx=Kx, Kh=Kh),
        grid=(R // block_rows,),
        in_specs=[src_spec(B, X), src_spec(B, H),
                  rows_spec(block_rows, Kx), rows_spec(block_rows, Kx),
                  rows_spec(block_rows, Kh), rows_spec(block_rows, Kh),
                  pl.BlockSpec((1, block_rows), lambda i: (0, i))],
        out_specs=pl.BlockSpec((B, block_rows), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, R), x.dtype),
        scratch_shapes=dual_scratch(B, block_rows, Kx, Kh),
        interpret=interpret,
        name="rb_dual_spmv",
    )(x, h, vals_x, deltas_x, vals_h, deltas_h, bias.reshape(1, R))
