"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics contracts: tests assert the kernels (interpret=True
on CPU) match these to fp tolerance across shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.packing import RowBalancedSparse


# ---------------------------------------------------------------- rb_spmv

def rb_spmv_ref(s: RowBalancedSparse, x: jnp.ndarray) -> jnp.ndarray:
    """y[b, r] = sum_k vals[r, k] * x[b, cols[r, k]].  x: (B, ncols)."""
    s = s.logical()          # oracles compute logical rows only
    cols = s.col_indices()                                 # (R, K)
    g = jnp.take(x, cols, axis=1)                          # (B, R, K)
    return jnp.einsum("brk,rk->br", g.astype(jnp.float32),
                      s.values.astype(jnp.float32)).astype(x.dtype)


def rb_dual_spmv_ref(sx: RowBalancedSparse, x: jnp.ndarray,
                     sh: RowBalancedSparse, h: jnp.ndarray,
                     bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """The LSTM gate preactivation: z = Sx@x + Sh@h (+ bias).

    Both packed matrices have the same row count (4H in the paper); the
    hardware analogue runs them on the Large/Small mult-arrays in lockstep.
    """
    z = (rb_spmv_ref(sx, x).astype(jnp.float32)
         + rb_spmv_ref(sh, h).astype(jnp.float32))
    if bias is not None:
        z = z + bias[:z.shape[-1]].astype(jnp.float32)[None, :]
    return z.astype(x.dtype)


# ----------------------------------------------------------- delta_rb_spmv

def delta_rb_spmv_ref(s: RowBalancedSparse, d: jnp.ndarray,
                      fired: jnp.ndarray) -> jnp.ndarray:
    """Temporal-delta SpMV: y[b, r] = Σ_k vals[r, k] · fired[b, c] · d[b, c].

    ``d`` (B, ncols) is a raw activation delta, ``fired`` its 0/1
    threshold-crossing mask (Spartus-style temporal sparsity): columns that
    did not fire contribute an exact 0.0 — the products a delta accelerator
    skips. Equivalent to ``rb_spmv_ref(s, fired * d)``.
    """
    return rb_spmv_ref(s, (d.astype(jnp.float32)
                           * fired.astype(jnp.float32)).astype(d.dtype))


def delta_rb_dual_spmv_ref(sx: RowBalancedSparse, dx: jnp.ndarray,
                           fx: jnp.ndarray, sh: RowBalancedSparse,
                           dh: jnp.ndarray, fh: jnp.ndarray,
                           m: jnp.ndarray) -> jnp.ndarray:
    """Fused temporal-delta gate update: m' = m + Sx@(fx·dx) + Sh@(fh·dh).

    ``m`` (B, 4H) is the partial-sum memory carried across decode steps;
    the bias is NOT folded in (the caller adds it once per step on top of
    m', keeping m a pure accumulation of delta contributions).
    """
    z = (m.astype(jnp.float32)
         + delta_rb_spmv_ref(sx, dx, fx).astype(jnp.float32)
         + delta_rb_spmv_ref(sh, dh, fh).astype(jnp.float32))
    return z.astype(m.dtype)


# ------------------------------------------------------------ quantized

def rb_spmv_q8_ref(s, qx: jnp.ndarray, act_scale) -> jnp.ndarray:
    """Quantized packed SpMV oracle: integer products, int32 accumulate,
    one dequant multiply per row.

    ``s``: a :class:`repro.quant.RowBalancedSparseQ8` (int codes + f32
    per-row scales); ``qx`` (B, ncols) int activation codes; ``act_scale``
    the scalar activation scale. Returns (B, rows) float32 =
    ``(Σ_k codes · qx) · (row_scale · act_scale)``. The accumulation is
    exact integer arithmetic, so the Pallas kernel matches bit-for-bit.
    """
    s = s.logical()          # oracles compute logical rows only
    cols = s.col_indices()                                  # (R, K)
    # keep the codes at their storage width into the dot (s8/s16 operands,
    # int32 accumulation via preferred_element_type): exact integer math,
    # and the compiled HLO shows an int8-operand dot so the roofline's
    # int8 bucket (roofline.int8_dot_flops) costs it at the int8 peak
    g = jnp.take(qx, cols, axis=1)                          # (B, R, K)
    acc = jnp.einsum("brk,rk->br", g, s.values,
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (s.scales * act_scale)[None, :]


def rb_dual_spmv_q8_ref(sx, qx, ax, sh, qh, ah,
                        bias: jnp.ndarray) -> jnp.ndarray:
    """Quantized dual-ratio gate preactivation oracle:
    z = dq(Sx@qx) + dq(Sh@qh) + bias, each family dequantized with its own
    combined (row × activation) scales. Returns (B, rows) float32."""
    z = rb_spmv_q8_ref(sx, qx, ax) + rb_spmv_q8_ref(sh, qh, ah)
    return z + bias[:z.shape[-1]].astype(jnp.float32)[None, :]


def delta_rb_dual_spmv_q8_ref(sx, qdx, ax, sh, qdh, ah,
                              m: jnp.ndarray) -> jnp.ndarray:
    """Quantized temporal partial-sum update oracle:
    m' = m + dq(Sx@qdx) + dq(Sh@qdh). ``qdx``/``qdh`` are int codes of the
    MASKED deltas (exact 0 where unfired — a zero code contributes a zero
    integer product, the skip a delta accelerator never issues). ``m``
    (B, rows) float32; bias NOT folded (the caller adds it per step)."""
    return (m.astype(jnp.float32) + rb_spmv_q8_ref(sx, qdx, ax)
            + rb_spmv_q8_ref(sh, qdh, ah))


# ---------------------------------------------------------------- lstm cell

def pwl_tables(n_seg: int = 16, lo: float = -8.0, hi: float = 8.0):
    """Piecewise-linear coefficient tables (a, b per segment) for sigmoid and
    tanh — the paper's LUT-based activation (§4: out = a*x + b per segment).
    Computed by least-squares-free endpoint interpolation per segment."""
    import numpy as np
    xs = np.linspace(lo, hi, n_seg + 1)
    def mk(f):
        y = f(xs)
        a = (y[1:] - y[:-1]) / (xs[1:] - xs[:-1])
        b = y[:-1] - a * xs[:-1]
        return a.astype(np.float32), b.astype(np.float32)
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    tanh = np.tanh
    a_s, b_s = mk(sig)
    a_t, b_t = mk(tanh)
    return dict(lo=lo, hi=hi, n_seg=n_seg, sig=(a_s, b_s), tanh=(a_t, b_t))


def _pwl_apply(x, a, b, lo, hi, n_seg, sat_lo, sat_hi):
    xc = jnp.clip(x, lo, hi - 1e-6)
    idx = jnp.floor((xc - lo) / (hi - lo) * n_seg).astype(jnp.int32)
    idx = jnp.clip(idx, 0, n_seg - 1)
    y = a[idx] * xc + b[idx]
    y = jnp.where(x < lo, sat_lo, y)
    y = jnp.where(x >= hi, sat_hi, y)
    return y


def pwl_sigmoid_ref(x, tables=None):
    t = tables or pwl_tables()
    a, b = map(jnp.asarray, t["sig"])
    return _pwl_apply(x.astype(jnp.float32), a, b, t["lo"], t["hi"], t["n_seg"], 0.0, 1.0)


def pwl_tanh_ref(x, tables=None):
    t = tables or pwl_tables()
    a, b = map(jnp.asarray, t["tanh"])
    return _pwl_apply(x.astype(jnp.float32), a, b, t["lo"], t["hi"], t["n_seg"], -1.0, 1.0)


def lstm_cell_ref(zf, zi, zg, zo, c_prev, *, pwl: bool = False):
    """Paper eq. (1)-(2) elementwise part, from gate preactivations.

    c = sig(zf) * c_prev + sig(zi) * tanh(zg);  h = sig(zo) * tanh(c)
    """
    f32 = jnp.float32
    if pwl:
        sig, th = pwl_sigmoid_ref, pwl_tanh_ref
        f, i, g, o = sig(zf), sig(zi), th(zg), sig(zo)
        c = f * c_prev.astype(f32) + i * g
        h = o * th(c)
    else:
        f = jax.nn.sigmoid(zf.astype(f32))
        i = jax.nn.sigmoid(zi.astype(f32))
        g = jnp.tanh(zg.astype(f32))
        o = jax.nn.sigmoid(zo.astype(f32))
        c = f * c_prev.astype(f32) + i * g
        h = o * jnp.tanh(c)
    return c.astype(c_prev.dtype), h.astype(c_prev.dtype)


# ---------------------------------------------------------------- attention

def mha_ref(q, k, v, *, causal: bool = True, scale: float | None = None,
            window: int | None = None) -> jnp.ndarray:
    """Reference attention. q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D).
    GQA: Hq must be a multiple of Hkv. window: local-attention window
    (keys within [qpos-window+1, qpos])."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    Sk = k.shape[2]
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths) -> jnp.ndarray:
    """Single-token decode attention. q: (B, Hq, D); k, v: (B, Hkv, S, D);
    lengths: (B,) valid cache lengths. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32) * D ** -0.5, kf)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bhkd->bhd", p, vf).astype(q.dtype)


# ------------------------------------------------------------------ mamba-2

def mamba2_ssm_step_ref(state, x, dt, A, Bm, Cm, D):
    """One Mamba-2 decode step. state (B, H, P, N); x (B, H, P); dt (B, H)
    (after softplus); A (H,) (negative); Bm, Cm (B, G, N), head h reading
    group h // (H/G); D (H,). Returns (new state, y (B, H, P)), float32:

        S ← exp(dt A) S + (dt x) ⊗ B;   y = S C + D x
    """
    f32 = jnp.float32
    H, G = state.shape[1], Bm.shape[1]
    Bh = jnp.repeat(Bm.astype(f32), H // G, axis=1)[:, :, None, :]
    Ch = jnp.repeat(Cm.astype(f32), H // G, axis=1)[:, :, None, :]
    dt = dt.astype(f32)
    x = x.astype(f32)
    decay = jnp.exp(dt * A.astype(f32)[None, :])
    s = (decay[..., None, None] * state.astype(f32)
         + (dt[..., None] * x)[..., None] * Bh)
    y = jnp.sum(s * Ch, axis=-1) + D.astype(f32)[None, :, None] * x
    return s, y
