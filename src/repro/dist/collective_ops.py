"""shard_map-wrapped packed-sparse kernels + the sharded LSTM decode steps.

Every wrapper here follows the same collective inventory, the device
analogue of the paper's PE datapath:

* weights enter **row-sharded** over the mesh's ``model`` axis (the
  gate-aligned layout of :mod:`repro.dist.partition`) — each shard runs
  the ordinary packed kernels (``repro.kernels.ops``) over its own rows,
  and because every row carries exactly NZ survivors, the shards finish
  in lockstep: row balance *is* the device load balance;
* activations (``x``, ``h``) enter **replicated** — the broadcast the
  paper feeds its PEs;
* the **only per-step collective** is the small all-gather of the hidden
  state ``h`` (B × H/n per shard) right after the local cell update,
  feeding the next step's (and next layer's) W_h/W_x columns. ``c``,
  the partial-sum memory ``m``, and the gate preactivations never cross
  shard boundaries.

Θ-thresholding for the delta path runs on the *gathered* (replicated)
reference state, so fired-column sets agree across shards by
construction — no collective needed to reconcile them.

Batch shards over the mesh's ``data`` axis whenever it divides B (the
continuous-batching scheduler's batch=1 prefills fall back to replicated
batch); everything below is batch-elementwise, so data parallelism
composes transparently with the model-axis row sharding.

``check_vma=False`` throughout: the Pallas backend's ``pallas_call`` (and
``jax.lax.top_k`` inside the occupancy cap) defeat shard_map's static
replication checker; replication of the h all-gather output holds by
construction.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.packing import RowBalancedSparse
from ..kernels import ops as K
from ..quant import RowBalancedSparseQ8
from ..sparse.temporal import delta_threshold
from .partition import data_axis_size, model_axis_size

__all__ = ["batch_axis", "gather_hidden", "sharded_rb_dual_spmv",
           "sharded_delta_rb_dual_spmv", "sharded_rb_dual_spmv_q8",
           "dist_lstm_step", "dist_delta_lstm_step"]


def batch_axis(mesh: Mesh, batch: int):
    """``"data"`` when the data axis exists and divides ``batch``, else
    None (replicated batch — e.g. the scheduler's batch=1 prefills)."""
    d = data_axis_size(mesh)
    return "data" if d > 1 and batch % d == 0 else None


def gather_hidden(h_loc, axis: str = "model"):
    """All-gather a (B, H/n) hidden shard into the replicated (B, H)
    broadcast — THE per-step collective of the sharded decode path.
    Shards concatenate in mesh-axis order, restoring the original hidden
    order (call only inside a shard_map region)."""
    return jax.lax.all_gather(h_loc, axis, axis=h_loc.ndim - 1, tiled=True)


def _packed_spec(packed, row_axis: str = "model"):
    """shard_map PartitionSpec tree for one packed matrix (row-sharded)."""
    if isinstance(packed, RowBalancedSparseQ8):
        return dataclasses.replace(packed, values=P(row_axis, None),
                                   deltas=P(row_axis, None),
                                   scales=P(row_axis))
    return dataclasses.replace(packed, values=P(row_axis, None),
                               deltas=P(row_axis, None))


def _check_rows(mesh: Mesh, *packed):
    n = model_axis_size(mesh)
    for s in packed:
        if s.rows % n:
            raise ValueError(f"packed rows={s.rows} not divisible by the "
                             f"model axis ({n})")


# ------------------------------------------------- sharded kernel wrappers
# Row-sharded twins of the kernels.ops entry points: bitwise-identical
# results (each output row is computed by exactly one shard with the same
# per-row arithmetic), with the work split 1/n per device. These take the
# UNPERMUTED row order — output rows reassemble contiguously — and exist
# for kernel-level parity tests and as the building blocks the step
# functions below inline.

def sharded_rb_dual_spmv(mesh: Mesh, sx: RowBalancedSparse, x,
                         sh: RowBalancedSparse, h, bias, *,
                         backend: str | None = None):
    """z = Sx@x + Sh@h + bias with the 4H rows sharded over ``model``.

    x/h replicated (the PE activation broadcast); returns the full
    (B, 4H) preactivation, each shard having computed its own rows."""
    _check_rows(mesh, sx, sh)
    b = batch_axis(mesh, x.shape[0])

    def f(sx_, x_, sh_, h_, b_):
        return K.rb_dual_spmv(sx_, x_, sh_, h_, b_, backend=backend)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(_packed_spec(sx), P(b, None), _packed_spec(sh),
                  P(b, None), P("model")),
        out_specs=P(b, "model"), check_vma=False)(sx, x, sh, h, bias)


def sharded_delta_rb_dual_spmv(mesh: Mesh, sx: RowBalancedSparse, dx, fx,
                               sh: RowBalancedSparse, dh, fh, m, *,
                               backend: str | None = None):
    """m' = m + Sx@(fx·dx) + Sh@(fh·dh) — the fused temporal-delta
    partial-sum update with rows (and ``m``) sharded over ``model``;
    deltas and fired masks replicated."""
    _check_rows(mesh, sx, sh)
    b = batch_axis(mesh, dx.shape[0])

    def f(sx_, dx_, fx_, sh_, dh_, fh_, m_):
        return K.delta_rb_dual_spmv(sx_, dx_, fx_, sh_, dh_, fh_, m_,
                                    backend=backend)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(_packed_spec(sx), P(b, None), P(b, None), _packed_spec(sh),
                  P(b, None), P(b, None), P(b, "model")),
        out_specs=P(b, "model"), check_vma=False)(sx, dx, fx, sh, dh, fh, m)


def sharded_rb_dual_spmv_q8(mesh: Mesh, sx: RowBalancedSparseQ8, x,
                            sh: RowBalancedSparseQ8, h, bias, *,
                            act_scale_x=None, act_scale_h=None,
                            backend: str | None = None):
    """Quantized dual-ratio preactivation, rows + per-row scales sharded.

    Activation quantization happens per shard on the replicated x/h —
    identical codes everywhere (the dynamic max-abs fallback reduces over
    the same replicated tensor on every shard)."""
    _check_rows(mesh, sx, sh)
    b = batch_axis(mesh, x.shape[0])

    def f(sx_, x_, sh_, h_, b_):
        return K.rb_dual_spmv_q8(sx_, x_, sh_, h_, b_,
                                 act_scale_x=act_scale_x,
                                 act_scale_h=act_scale_h, backend=backend)

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(_packed_spec(sx), P(b, None), _packed_spec(sh),
                  P(b, None), P("model")),
        out_specs=P(b, "model"), check_vma=False)(sx, x, sh, h, bias)


# ----------------------------------------------------- sharded decode steps
# The multi-layer LSTM step as ONE shard_map region: local dual SpMV over
# the gate-aligned permuted rows, local cell close over the shard's hidden
# slice, then the h all-gather that feeds the next layer / next step.
# Layer params MUST be partition_lstm_params' permuted layout.

def _layer_specs(layers):
    return [{k: (_packed_spec(v) if isinstance(
                    v, (RowBalancedSparse, RowBalancedSparseQ8))
                 else P("model"))
             for k, v in lp.items()} for lp in layers]


def dist_lstm_step(mesh: Mesh, layers, x_t, state, *, pwl: bool = False,
                   dtype=jnp.float32, act_scales=None,
                   backend: str | None = None):
    """One sharded packed LSTM step (the ``LSTMModel._step`` twin).

    ``layers``: partition_lstm_params' per-layer ``{w_x, w_h, b}`` (gate-
    aligned permuted rows); ``state``: per-layer (c, h) with c sharded
    over its hidden slice and h replicated. ``act_scales``: per-layer
    (s_x, s_h) static activation scales for q8 layers (None entries fall
    back to the scheme default). Returns (h_last, new_state) exactly as
    the single-device step — bitwise, since every output row is computed
    by exactly one shard with unchanged per-row arithmetic.
    """
    b = batch_axis(mesh, x_t.shape[0])
    state = [tuple(st) for st in state]     # scan carries tuples
    st_spec = [(P(b, "model"), P(b, None)) for _ in layers]

    def f(layers_, x_, state_):
        inp = x_
        new = []
        for i, (lp, (c, h)) in enumerate(zip(layers_, state_)):
            if isinstance(lp["w_x"], RowBalancedSparseQ8):
                ax, ah = act_scales[i] if act_scales else (None, None)
                c2, h2 = K.brds_lstm_step_q8(
                    lp["w_x"], inp, lp["w_h"], h, lp["b"], c,
                    act_scale_x=ax, act_scale_h=ah, pwl=pwl,
                    backend=backend)
            else:
                c2, h2 = K.brds_lstm_step(lp["w_x"], inp, lp["w_h"], h,
                                          lp["b"], c, pwl=pwl,
                                          backend=backend)
            c2, h2 = c2.astype(dtype), h2.astype(dtype)
            h2 = gather_hidden(h2)         # THE per-step collective
            new.append((c2, h2))
            inp = h2
        return inp, new

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(_layer_specs(layers), P(b, None), st_spec),
        out_specs=(P(b, None), st_spec), check_vma=False)(
            layers, x_t, state)


def dist_delta_lstm_step(mesh: Mesh, layers, x_t, state, delta, *,
                         pwl: bool = False, dtype=jnp.float32,
                         act_scales=None, backend: str | None = None):
    """One sharded temporally-sparse step (the ``_delta_step`` twin).

    ``state``: per-layer dicts {c, h, x_ref, h_ref, m, nx, nh} with c and
    the partial-sum memory m sharded (m rides the permuted gate rows),
    everything else replicated. Thresholding runs on the replicated
    (gathered) reference state, so every shard derives the SAME fired
    sets and reference updates — the delta gating never needs a
    collective of its own. ``act_scales`` arrive already delta-doubled
    (the model owns that adjustment).
    """
    b = batch_axis(mesh, x_t.shape[0])
    state = list(state)                     # scan may carry a tuple
    st_spec = [{"c": P(b, "model"), "h": P(b, None), "x_ref": P(b, None),
                "h_ref": P(b, None), "m": P(b, "model"), "nx": P(b),
                "nh": P(b)} for _ in layers]

    def f(layers_, x_, state_):
        inp = x_
        new = []
        for i, (lp, st) in enumerate(zip(layers_, state_)):
            dx, fx, x_ref = delta_threshold(inp, st["x_ref"],
                                            delta.theta_x, delta.cap_x)
            dh, fh, h_ref = delta_threshold(st["h"], st["h_ref"],
                                            delta.theta_h, delta.cap_h)
            if isinstance(lp["w_x"], RowBalancedSparseQ8):
                ax, ah = act_scales[i] if act_scales else (None, None)
                c2, h2, m2 = K.brds_delta_lstm_step_q8(
                    lp["w_x"], dx, fx, lp["w_h"], dh, fh, st["m"], lp["b"],
                    st["c"], act_scale_x=ax, act_scale_h=ah, pwl=pwl,
                    backend=backend)
            else:
                c2, h2, m2 = K.brds_delta_lstm_step(
                    lp["w_x"], dx, fx, lp["w_h"], dh, fh, st["m"], lp["b"],
                    st["c"], pwl=pwl, backend=backend)
            h2 = gather_hidden(h2.astype(dtype))
            new.append({
                "c": c2.astype(dtype), "h": h2,
                "x_ref": x_ref, "h_ref": h_ref,
                "m": m2.astype(jnp.float32),
                "nx": st["nx"] + jnp.sum(fx, axis=1, dtype=jnp.float32),
                "nh": st["nh"] + jnp.sum(fh, axis=1, dtype=jnp.float32)})
            inp = h2
        return inp, new

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(_layer_specs(layers), P(b, None), st_spec),
        out_specs=(P(b, None), st_spec), check_vma=False)(
            layers, x_t, state)
