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
activation gather is LANE-LOCAL: the TPU gathers only within one 128-lane
vreg, so x is split into 128-lane chunks, each chunk is gathered by
``col % 128`` and kept where ``col // 128`` names it. Per 8-row tile and
128-lane K-chunk only a window of ``window_chunks`` chunks from the tile's
own smallest ``col // 128`` is visited where every K-chunk of the tile
fits it (ascending columns keep the window narrow), and where one does
not, each K-chunk's own chunks from its smallest ``col // 128`` to its
largest. Each lane's chunk is visited and exactly one chunk selects
the lane, so every lane gathers the value a visit of all chunks would,
and the result is bitwise that visit's. The gathered tile is
multiplied by the values and accumulated per 128-lane K-chunk, then
reduced across lanes once per row block. Lanes past K (the block is
rounded up to a lane multiple) carry zero deltas and zero values, so they
add exact zeros.

Used on the memory-bound decode path, where bytes (not FLOPs) dominate:
effective-throughput gain ≈ 1/(1-sparsity), the paper's headline metric.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEF_BLOCK_ROWS = 256
LANES = 128          # vreg lane width: the TPU gathers within one vreg only
LANE_BITS = 7        # log2(LANES)
SUBLANES = 8         # rows per gather tile (one 32-bit vreg is 8 x 128)
BATCH_GROUP = 16     # batch rows gathered together per tile visit


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


def window_chunks(nx: int, K: int) -> int:
    """How many source chunks ``gather_dot`` visits per 8-row tile and
    128-lane K-chunk of a family with K non-zeros per row over ``nx``
    128-lane source chunks. 128 ascending columns at density K / (128·nx)
    span about 128·nx/K columns, that many chunks, plus one where they
    straddle a chunk edge and one for the spread across the tile's rows.
    A tile with a wider window visits all ``nx``. Shape alone sets it."""
    return min(nx, nx * LANES // K + 2)


def family_scratch(B: int, block_rows: int, K: int, X: int, acc_dtype):
    """Scratch one packed family decodes into, as one tuple (the kernel
    receives it as a tuple of refs): columns + values, and — where the
    window (``window_chunks``) is narrower than the source — the batch
    group's replicated source chunks, indexed by a dynamic chunk, and each
    tile's first and last chunk per K-chunk and fit flag, reduced in VMEM
    and copied to SMEM (with the copy's semaphore)."""
    Kp, nx = lane_pad(K), lane_pad(X) // LANES
    scr = [pltpu.VMEM((block_rows, Kp), jnp.int32),
           pltpu.VMEM((block_rows, Kp), acc_dtype)]
    if window_chunks(nx, K) < nx:
        spans = (block_rows // SUBLANES, lane_pad(2 * (Kp // LANES) + 1))
        scr += [pltpu.VMEM((nx, min(B, BATCH_GROUP), SUBLANES, LANES),
                           jnp.float32),
                pltpu.VMEM(spans, jnp.int32), pltpu.SMEM(spans, jnp.int32),
                pltpu.SemaphoreType.DMA]
    return tuple(scr)


def acc_scratch(B: int, block_rows: int, acc_dtype):
    """Per-lane partial sums of up to one group of batch rows."""
    return pltpu.VMEM((min(B, BATCH_GROUP), block_rows, LANES), acc_dtype)


def _windows(cols_scr, spans_v, spans, sem, U):
    """Per 8-row tile: column kc of ``spans`` the smallest ``col >> 7`` in
    K-chunk kc, column nkc 1 where every K-chunk's chunks fit ``U``,
    column nkc + 1 + kc the largest ``col >> 7`` in K-chunk kc.
    Reduced on vectors (in f32, exact for chunk numbers), then one copy to
    SMEM, where the tile loop reads them as scalars."""
    bR, Kp = cols_scr.shape
    nt, nkc = bR // SUBLANES, Kp // LANES
    lane = lax.broadcasted_iota(jnp.int32, spans_v.shape, 1)
    out = jnp.zeros(spans_v.shape, jnp.int32)
    widest = jnp.zeros((nt, 1), jnp.int32)
    for kc in range(nkc):
        hi = cols_scr[:, pl.ds(kc * LANES, LANES)] >> LANE_BITS
        hi = hi.astype(jnp.float32).reshape(nt, SUBLANES, LANES)
        first = jnp.min(jnp.min(hi, axis=1), axis=1, keepdims=True)
        last = jnp.max(jnp.max(hi, axis=1), axis=1, keepdims=True)
        first, last = first.astype(jnp.int32), last.astype(jnp.int32)
        out = jnp.where(lane == kc, first, out)
        out = jnp.where(lane == nkc + 1 + kc, last, out)
        widest = jnp.maximum(widest, last - first + 1)
    spans_v[...] = jnp.where(lane == nkc, (widest <= U).astype(jnp.int32),
                             out)
    copy = pltpu.make_async_copy(spans_v, spans, sem)
    copy.start()
    copy.wait()


_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _lane_gather(chunk, lo):
    """out[r, l] = chunk[r, lo[r, l, 0]]: the gather
    ``jnp.take_along_axis(chunk, lo[..., 0], axis=1)`` binds (Mosaic's
    in-vreg dynamic gather), without its wrapper's reshape and nested jit
    — a kernel trace makes thousands of these."""
    return lax.gather(chunk, lo, _LANE_GATHER, slice_sizes=(1, 1),
                      mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def gather_dot(src, vals_ref, deltas_ref, fam_scr, acc_scr, *, K: int,
               acc_dtype):
    """acc[b, r] = Σ_k vals[r, k] · src[b, cols[r, k]] for one row block.

    ``src`` (B, lane_pad(X)) activations as a value (its lanes past X are
    never selected); ``vals_ref``/``deltas_ref`` the (bR, Kp) packed block;
    ``fam_scr`` the refs ``family_scratch`` declared; ``acc_scr`` from
    ``acc_scratch``. Float families accumulate in f32, integer codes in
    int32 (exact). Returns (B, bR) ``acc_dtype``.

    Each lane gathers from the one 128-lane source chunk its column's
    ``col >> 7`` names. Per 8-row tile and 128-lane K-chunk the chunk
    visits start at the tile's own smallest ``col >> 7`` and run
    ``U = window_chunks(nx, K)`` chunks (clamped to the last), where every
    K-chunk of the tile fits U; a tile that does not fit visits each
    K-chunk's chunks from its smallest ``col >> 7`` to its largest, and a
    family with U = nx all ``nx`` chunks. Every lane's chunk is visited,
    exactly one chunk selects each lane (a clamped repeat gathers the same
    value again), so each lane gathers what a visit of all chunks would
    and the products and sums that follow are unchanged: the result is
    bitwise the full visit's, for any column order. A window's visits are
    unrolled: a loop with a dynamic trip count per K-chunk would split the
    tile's gathers into blocks the compiler cannot overlap. A tile that
    does not fit loops over each K-chunk's own chunks, one visit per
    iteration: ascending columns take that path where rows are long
    against the window's slack (the 8192-wide MLP down projection of a
    hybrid LM), and its code is kept small.

    The per-row arithmetic — chunked lane products summed over K-chunks,
    then one lane reduction — depends on neither the block's row count nor
    its position, which is what keeps every kernel sharing this function
    bitwise-consistent with the others."""
    cols_scr, vals_scr, *win_scr = fam_scr
    cols_scr[...] = _columns(deltas_ref[...], K)
    lane = lax.broadcasted_iota(jnp.int32, vals_scr.shape, 1)
    vals_scr[...] = jnp.where(lane < K, vals_ref[...].astype(acc_dtype), 0)
    bR, Kp = cols_scr.shape
    nkc = Kp // LANES
    B = src.shape[0]
    G = acc_scr.shape[0]
    nx = src.shape[1] // LANES
    U = window_chunks(nx, K)
    assert bool(win_scr) == (U < nx), (nx, U, len(fam_scr))
    if win_scr:
        src_scr, spans_v, spans, sem = win_scr
        _windows(cols_scr, spans_v, spans, sem, U)
    # integer codes (|q| ≤ 32767) are exact in f32: gathering every family
    # in f32 keeps one chunk layout that Mosaic accepts at every batch
    src = src.astype(jnp.float32)
    rows = []
    for b0 in range(0, B, G):
        n = min(G, B - b0)
        # one batch row's 128-lane chunk, replicated over 8 sublanes. The
        # zero add gives the sliced row a fresh (1, 128) layout: Mosaic
        # cannot broadcast a row sliced out of a block of fewer than 8
        # rows ("Invalid input layout"), which every B < 8 would hit
        zero = jnp.zeros((1, LANES), jnp.float32)
        chunks = [[lax.broadcast_in_dim(
                       lax.add(lax.slice(src, (b, c * LANES),
                                         (b + 1, (c + 1) * LANES)), zero),
                       (SUBLANES, LANES), (0, 1))
                   for b in range(b0, b0 + n)] for c in range(nx)]
        if win_scr:
            for c in range(nx):
                for j in range(n):
                    src_scr[c, j] = chunks[c][j]

        def tile(t, carry, chunks=chunks, n=n):
            r0 = pl.multiple_of(t * SUBLANES, SUBLANES)

            def visit(fits):
                # lax, not jnp, in this unrolled body: each jnp operator
                # call traces a nested jit, and the kernel makes thousands
                accs = [jnp.zeros((SUBLANES, LANES), acc_dtype)] * n
                for kc in range(nkc):
                    sl = (pl.ds(r0, SUBLANES), pl.ds(kc * LANES, LANES))
                    idx, v = cols_scr[sl], vals_scr[sl]
                    hi = idx >> LANE_BITS
                    lo = (idx & (LANES - 1)).reshape(SUBLANES, LANES, 1)

                    def window(start, gs, width=U, hi=hi, lo=lo):
                        """``width`` chunk visits from ``start``, clamped
                        to the last chunk; each keeps the lanes it names."""
                        for i in range(width):
                            if win_scr:
                                c = lax.min(lax.add(start, np.int32(i)),
                                            np.int32(nx - 1))
                                blk = src_scr[c]
                                row = lambda j, blk=blk: lax.index_in_dim(
                                    blk, j, keepdims=False)
                            else:
                                c = np.int32(i)
                                row = lambda j, c=i: chunks[c][j]
                            hit = lax.eq(hi, lax.broadcast(c, hi.shape))
                            gs = [lax.select(hit, _lane_gather(row(j), lo),
                                             gs[j]) for j in range(n)]
                        return gs

                    gs = [jnp.zeros((SUBLANES, LANES), jnp.float32)] * n
                    if not win_scr:
                        gs = window(0, gs)
                    elif fits:
                        gs = window(spans[t, kc], gs)
                    else:   # the K-chunk's own chunks, one at a time
                        gs = lax.fori_loop(
                            spans[t, kc], spans[t, nkc + 1 + kc] + 1,
                            lambda c, gs: window(c, gs, 1), gs)
                    if acc_dtype != jnp.float32:
                        gs = [lax.convert_element_type(g, acc_dtype)
                              for g in gs]
                    accs = [lax.add(accs[j], lax.mul(gs[j], v))
                            for j in range(n)]
                for j in range(n):
                    acc_scr[j, pl.ds(r0, SUBLANES), :] = accs[j]

            if win_scr:
                fits = spans[t, nkc] == 1
                pl.when(fits)(lambda: visit(True))
                pl.when(jnp.logical_not(fits))(lambda: visit(False))
            else:
                visit(True)
            return carry

        lax.fori_loop(0, bR // SUBLANES, tile, 0)
        rows += [jnp.sum(acc_scr[j], axis=1) for j in range(n)]
    return jnp.stack(rows, axis=0)


def gather_visit_share(deltas, ncols: int) -> float:
    """Share of (8-row tile, 128-lane K-chunk, source chunk) triples that
    ``gather_dot`` visits for a packed family, out of the ``nx`` chunks per
    (tile, K-chunk) of a visit of every chunk: ``window_chunks`` per
    K-chunk of a tile whose K-chunks all fit that window, and for any
    other tile each K-chunk's chunks from its first to its last. A
    property of the packed weights, computed on the host from
    ``deltas`` ((..., R, K), leading axes stacked layers) and the logical
    column count ``ncols``. Rows pad to a tile multiple with zero rows
    (column 0), as in the kernel; lanes past K repeat the row's last
    column, which lies in the last K-chunk already, so they change no
    window."""
    d = np.asarray(deltas)
    d = d.reshape(-1, *d.shape[-2:])
    L, R, K = d.shape
    nx = lane_pad(ncols) // LANES
    U = window_chunks(nx, K)
    if U == nx:
        return 1.0
    # every partial sum is a column, which the deltas' own dtype holds
    hi = np.cumsum(d, axis=-1, dtype=d.dtype) >> LANE_BITS
    if R % SUBLANES:
        hi = np.pad(hi, ((0, 0), (0, -R % SUBLANES), (0, 0)))
    hi = hi.reshape(L, -1, SUBLANES, K)               # (L, tiles, 8, K)
    starts = np.arange(0, K, LANES)
    last = np.maximum.reduceat(hi.max(axis=2), starts, axis=-1)
    first = np.minimum.reduceat(hi.min(axis=2), starts, axis=-1)
    width = last - first + 1                          # (L, tiles, K-chunks)
    fits = width.max(axis=-1, keepdims=True) <= U
    return float(np.where(fits, U, width).mean() / nx)


def src_spec(B: int, X: int, index_map=lambda *_: (0, 0)):
    """BlockSpec for a (B, X) activation the kernels gather from: one
    lane-padded block (partial past X)."""
    return pl.BlockSpec((B, lane_pad(X)), index_map)


def rows_spec(block_rows: int, K: int, index_map=lambda i: (i, 0)):
    """BlockSpec for a (R, K) packed array: one row block, lane-padded."""
    return pl.BlockSpec((block_rows, lane_pad(K)), index_map)


def _rb_spmv_kernel(x_ref, vals_ref, deltas_ref, out_ref, fam_scr, acc_scr,
                    *, K):
    """Grid step: one block of rows. x_ref (B, Xp); vals/deltas (bR, Kp);
    out_ref (B, bR)."""
    acc = gather_dot(x_ref[...], vals_ref, deltas_ref, fam_scr, acc_scr,
                     K=K, acc_dtype=jnp.float32)
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
        scratch_shapes=[family_scratch(B, block_rows, K, X, jnp.float32),
                        acc_scratch(B, block_rows, jnp.float32)],
        interpret=interpret,
        name="rb_spmv",
    )(x, values, deltas)


def dual_gate(x, h, vx_ref, dx_ref, vh_ref, dh_ref, scr, *, Kx, Kh,
              acc_dtype=jnp.float32):
    """Both packed families of one row block (Large/Small MA lockstep):
    → (Sx@x, Sh@h) partial sums, (B, bR) each. ``scr`` is the scratch
    list ``dual_scratch`` declared."""
    fx, fh, acc = scr
    accx = gather_dot(x, vx_ref, dx_ref, fx, acc, K=Kx, acc_dtype=acc_dtype)
    acch = gather_dot(h, vh_ref, dh_ref, fh, acc, K=Kh, acc_dtype=acc_dtype)
    return accx, acch


def dual_scratch(B: int, block_rows: int, X: int, Kx: int, H: int, Kh: int,
                 acc_dtype=jnp.float32):
    return [family_scratch(B, block_rows, Kx, X, acc_dtype),
            family_scratch(B, block_rows, Kh, H, acc_dtype),
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
        scratch_shapes=dual_scratch(B, block_rows, X, Kx, H, Kh),
        interpret=interpret,
        name="rb_dual_spmv",
    )(x, h, vals_x, deltas_x, vals_h, deltas_h, bias.reshape(1, R))
