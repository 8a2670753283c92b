"""Packed storage for row-balanced sparse matrices.

The accelerator stores only the non-zeros: each row of a row-balanced sparse
matrix has exactly K non-zeros, so values pack densely into a (rows, K)
array. Column positions are stored with the paper's *relative addressing*
(EIE-style [22]): the delta between consecutive non-zero column indices in a
row, which fits a narrow integer type. The kernel reconstructs absolute
columns with a prefix sum in VMEM — index HBM traffic shrinks 2–4×
vs int32 absolute indices.

This is a pytree, so it flows through jit/pjit/scan and can be sharded.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .sparsity import row_balanced_mask, keep_count

__all__ = ["RowBalancedSparse", "pack", "unpack", "pack_from_dense",
           "pad_packed", "block_rows_for"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowBalancedSparse:
    """Packed row-balanced sparse matrix of logical shape (rows, ncols).

    values:  (rows, K)  non-zero values, row-major by ascending column
    deltas:  (rows, K)  delta-encoded column indices (delta_dtype);
                        col[r, 0] = deltas[r, 0]; col[r, j] = col[r, j-1] + deltas[r, j]
    ncols:   static logical column count
    pad:     static count of zero rows appended by ``pad_packed`` so the
             row axis is a kernel-block multiple; ``rows`` stays logical
    block_rows: static block size the padding targeted (None = unpadded)
    """

    values: jnp.ndarray
    deltas: jnp.ndarray
    ncols: int = dataclasses.field(metadata=dict(static=True))
    pad: int = dataclasses.field(default=0, metadata=dict(static=True))
    block_rows: int | None = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def rows(self) -> int:
        return self.values.shape[0] - self.pad

    def logical(self) -> "RowBalancedSparse":
        """Padding-free view (slices off ``pad_packed``'s zero rows)."""
        if not self.pad:
            return self
        r = self.rows
        return dataclasses.replace(self, values=self.values[:r],
                                   deltas=self.deltas[:r], pad=0,
                                   block_rows=None)

    @property
    def K(self) -> int:
        return self.values.shape[1]

    @property
    def sparsity(self) -> float:
        return 1.0 - self.K / self.ncols

    def col_indices(self) -> jnp.ndarray:
        """Absolute column indices (rows, K), int32."""
        return jnp.cumsum(self.deltas.astype(jnp.int32), axis=1)

    def memory_bytes(self) -> dict:
        """Storage accounting for the Table-1 analogue benchmark (logical
        rows only — ``pad_packed``'s zero rows are a layout artifact)."""
        n = self.rows * self.K
        v = n * self.values.dtype.itemsize
        i = n * self.deltas.dtype.itemsize
        dense = self.rows * self.ncols * self.values.dtype.itemsize
        return dict(values=v, indices=i, total=v + i, dense_equiv=dense,
                    ratio=(v + i) / dense)


def _delta_dtype(ncols: int, k: int) -> np.dtype:
    """Narrowest signed int that can hold the worst-case column delta.

    The first delta is an absolute column (up to ncols-1); subsequent deltas
    are gaps (≥1). Worst case is ncols-1 in both conventions.
    """
    if ncols - 1 <= 127:
        return np.dtype(np.int8)
    if ncols - 1 <= 32767:
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def pack(w: jnp.ndarray, mask: jnp.ndarray) -> RowBalancedSparse:
    """Pack a dense matrix + row-balanced mask into packed form.

    Every row of ``mask`` must have the same popcount K (row-balanced
    invariant); this is asserted on concrete inputs.
    """
    rows, ncols = w.shape
    counts = np.asarray(jnp.sum(mask, axis=1))
    k = int(counts[0])
    if not (counts == k).all():
        raise ValueError("mask is not row-balanced: per-row nnz " f"{np.unique(counts)}")
    # Per row: the column indices where mask is True, ascending. Masked-out
    # positions sort to the end (key = ncols), and exactly K survive.
    colgrid = jnp.broadcast_to(jnp.arange(ncols), (rows, ncols))
    key = jnp.where(mask, colgrid, ncols)
    order = jnp.argsort(key, axis=1)[:, :k]            # (rows, K) ascending cols
    vals = jnp.take_along_axis(w, order, axis=1)
    cols = order.astype(jnp.int32)
    deltas = jnp.diff(cols, axis=1, prepend=jnp.zeros((rows, 1), jnp.int32))
    dd = _delta_dtype(ncols, k)
    return RowBalancedSparse(values=vals, deltas=deltas.astype(dd), ncols=ncols)


def pack_from_dense(w: jnp.ndarray, sparsity: float) -> RowBalancedSparse:
    """Row-balanced prune + pack in one step."""
    return pack(w, row_balanced_mask(w, sparsity))


def unpack(s: RowBalancedSparse) -> jnp.ndarray:
    """Reconstruct the dense (rows, ncols) matrix (zeros where pruned)."""
    s = s.logical()
    cols = s.col_indices()
    rows = s.rows
    out = jnp.zeros((rows, s.ncols), s.values.dtype)
    rowgrid = jnp.broadcast_to(jnp.arange(rows)[:, None], cols.shape)
    return out.at[rowgrid, cols].set(s.values)


def block_rows_for(rows: int, block_rows: int = 256) -> int:
    """The kernel row block for a matrix of ``rows`` rows: ``block_rows``,
    or — for a smaller matrix — its rows rounded up to a sublane multiple
    of 8 (the kernels gather over 8-row tiles)."""
    if not rows:
        return block_rows
    return min(block_rows, -(-rows // 8) * 8)


def pad_packed(s, block_rows: int = 256):
    """Pre-pad a packed struct's row axis to a kernel-block multiple.

    The kernel wrappers (``kernels.ops``) need the row count to be a
    multiple of their grid block; historically they re-padded
    values/deltas inside every jitted step call — a per-token copy of the
    whole weight stream on the decode hot path. Padding once at
    pack/prepare time (zero rows appended, ``pad``/``block_rows`` recorded
    on the struct) lets the wrappers consume the arrays as-is.

    Accepts :class:`RowBalancedSparse` and its quantized twin
    (``repro.quant.RowBalancedSparseQ8`` — its per-row ``scales`` pad
    along too). Padded rows are all-zero: their cumsum'd columns gather
    x[:, 0] against zero values/scales, contributing exact zeros that the
    wrappers slice away. No-op when the rows already divide ``block_rows``
    or the struct is already padded for it.
    """
    r = s.rows
    eff = block_rows_for(r, block_rows)
    pad = (-r) % eff
    if s.pad == pad and (s.block_rows in (None, eff) if pad == 0
                         else s.block_rows == eff):
        return dataclasses.replace(s, block_rows=eff)
    s = s.logical()
    widths = ((0, pad), (0, 0))
    kw = dict(values=jnp.pad(s.values, widths),
              deltas=jnp.pad(s.deltas, widths),
              pad=pad, block_rows=eff)
    if hasattr(s, "scales"):
        kw["scales"] = jnp.pad(s.scales, (0, pad))
    return dataclasses.replace(s, **kw)
