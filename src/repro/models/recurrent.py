"""Recurrent sequence mixers: RG-LRU (RecurrentGemma/Griffin), RWKV6 and
Mamba-2 (Granite 4.0-H).

All are the paper's W_h analogue made modern — data-dependent diagonal /
low-rank / selective state-space recurrences. Training/prefill use
parallel forms (associative scan for RG-LRU; chunked linear attention for
RWKV6) or, for Mamba-2, a sequential scan of the decode step's state
update; decode uses O(1) state updates. These blocks make the `long_500k`
shape runnable (sub-quadratic).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .layers import PSpec, _act
from ..core.packing import RowBalancedSparse
from ..kernels import ops as kops
from ..sharding import constrain


def _proj(x, w, precision=None):
    """y = x @ W for dense (d_in, *out) weights, at ``precision``, OR
    BRDS-packed weights (RowBalancedSparse with rows = flattened out dim,
    cols = d_in).

    Packed weights go through ``kernels.ops.rb_project``, the one packed
    projection every model family shares: on a TPU the Pallas gather
    streams only the (rows, K) values and narrow delta indices, with no
    (B·S, rows, K) gather materialised. Returns (B, S, F) with
    F = prod(out dims)."""
    if isinstance(w, RowBalancedSparse):
        y = constrain(kops.rb_project(x, w), None, None, "mlp")
        return y.astype(x.dtype)
    return jnp.einsum("bsd,df->bsf", x, w.reshape(w.shape[0], -1),
                      precision=precision)

# ================================================================= RG-LRU

RG_C = 8.0  # Griffin's fixed temperature on the recurrence gate


def rglru_defs(d_model: int, d_rnn: int, conv_width: int, dtype) -> dict:
    return {
        "w_in_gelu": PSpec((d_model, d_rnn), ("embed", "mlp"), dtype=dtype),
        "w_in_rec": PSpec((d_model, d_rnn), ("embed", "mlp"), dtype=dtype),
        "conv_w": PSpec((conv_width, d_rnn), ("conv", "mlp"), dtype=dtype,
                        scale=0.3),
        "conv_b": PSpec((d_rnn,), ("mlp",), init="zeros", dtype=dtype),
        "w_gate_a": PSpec((d_rnn, d_rnn), ("mlp", "embed"), dtype=dtype),
        "w_gate_x": PSpec((d_rnn, d_rnn), ("mlp", "embed"), dtype=dtype),
        "lam": PSpec((d_rnn,), ("mlp",), init="ones", dtype=jnp.float32),
        "w_out": PSpec((d_rnn, d_model), ("mlp", "embed"), dtype=dtype),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x (B, S, D), w (W, D). state (B, W-1, D) for
    decode. Returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # (B, S+W-1, D)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :] for i in range(W))
    y = y + b[None, None, :]
    new_state = xp[:, -(W - 1):] if W > 1 else None
    return y, new_state


def _rglru_gates(p, xr):
    """Gate computations shared by scan/step. xr (..., d_rnn) → (log_a, gx)."""
    ga = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xr, p["w_gate_a"])
                        .astype(jnp.float32))
    gx = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xr, p["w_gate_x"])
                        .astype(jnp.float32))
    log_a = -RG_C * jax.nn.softplus(p["lam"]) * ga  # (..., d_rnn) ≤ 0
    return log_a, gx


def rglru_apply(p: dict, x, state=None):
    """Full-sequence RG-LRU block. x (B, S, d_model). state: dict with
    'h' (B, d_rnn) and 'conv' (B, W-1, d_rnn) for chained prefill/decode.
    Returns (y (B, S, d_model), new_state)."""
    gelu_branch = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, p["w_in_gelu"]))
    xr = jnp.einsum("bsd,de->bse", x, p["w_in_rec"])
    xr, conv_state = _causal_conv(xr, p["conv_w"], p["conv_b"],
                                  None if state is None else state["conv"])
    log_a, gx = _rglru_gates(p, xr)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))
    b = beta * gx * xr.astype(jnp.float32)          # (B, S, d_rnn)

    # h_t = a_t * h_{t-1} + b_t  — associative scan over seq
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    a_sc, b_sc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = b_sc
    if state is not None:
        h = h + a_sc * state["h"].astype(jnp.float32)[:, None, :]
    h = h.astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", gelu_branch * h, p["w_out"])
    new_state = {"h": h[:, -1], "conv": conv_state}
    return y, new_state


def rglru_step(p: dict, x, state):
    """Single-token decode. x (B, 1, d_model) → (y (B, 1, d), new_state)."""
    gelu_branch = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, p["w_in_gelu"]))
    xr = jnp.einsum("bsd,de->bse", x, p["w_in_rec"])
    xr, conv_state = _causal_conv(xr, p["conv_w"], p["conv_b"], state["conv"])
    log_a, gx = _rglru_gates(p, xr)
    a = jnp.exp(log_a)[:, 0]                        # (B, d_rnn)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9))[:, 0]
    b = beta * gx[:, 0] * xr[:, 0].astype(jnp.float32)
    h = a * state["h"].astype(jnp.float32) + b
    h = h.astype(x.dtype)
    y = jnp.einsum("be,ed->bd", gelu_branch[:, 0] * h, p["w_out"])[:, None]
    return y, {"h": h, "conv": conv_state}


def rglru_state_defs(batch: int, d_rnn: int, conv_width: int, dtype) -> dict:
    return {
        "h": PSpec((batch, d_rnn), ("batch", "mlp"), init="zeros", dtype=dtype),
        "conv": PSpec((batch, conv_width - 1, d_rnn), ("batch", "conv", "mlp"),
                      init="zeros", dtype=dtype),
    }


# ================================================================== RWKV6

def rwkv_defs(d_model: int, num_heads: int, head_dim: int, d_ff: int,
              dtype) -> dict:
    H, Dk = num_heads, head_dim
    return {
        # token-shift lerp coefficients (r, k, v, w, g)
        "mu": PSpec((5, d_model), (None, "embed"), init="zeros",
                    dtype=jnp.float32),
        "w_r": PSpec((d_model, H, Dk), ("embed", "heads", "head_dim"),
                     dtype=dtype),
        "w_k": PSpec((d_model, H, Dk), ("embed", "heads", "head_dim"),
                     dtype=dtype),
        "w_v": PSpec((d_model, H, Dk), ("embed", "heads", "head_dim"),
                     dtype=dtype),
        "w_g": PSpec((d_model, H, Dk), ("embed", "heads", "head_dim"),
                     dtype=dtype),
        # data-dependent decay: w_t = exp(-exp(w0 + x @ w_w))
        "w0": PSpec((H, Dk), ("heads", "head_dim"), init="zeros",
                    dtype=jnp.float32),
        "w_w": PSpec((d_model, H, Dk), ("embed", "heads", "head_dim"),
                     scale=0.01, dtype=dtype),
        "u": PSpec((H, Dk), ("heads", "head_dim"), init="zeros",
                   dtype=jnp.float32),
        "gn": PSpec((H, Dk), ("heads", "head_dim"), init="zeros",
                    dtype=jnp.float32),  # per-head group-norm scale
        "w_out": PSpec((H, Dk, d_model), ("heads", "head_dim", "embed"),
                       dtype=dtype),
        # channel-mix
        "mu_cm": PSpec((d_model,), ("embed",), init="zeros", dtype=jnp.float32),
        "w_cm1": PSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "w_cm2": PSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def _token_shift(x, x_prev_last):
    """x (B, S, d); x_prev_last (B, d) = last token of the previous segment.
    Returns x_{t-1} sequence aligned with x."""
    prev = x_prev_last[:, None, :].astype(x.dtype)
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _rwkv_projections(p, x, x_shift):
    mu = p["mu"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    sf = x_shift.astype(jnp.float32)
    B, S = x.shape[:2]
    H, Dk = p["u"].shape
    mix = lambda i: (xf + mu[i] * (sf - xf)).astype(x.dtype)
    hd = lambda y: y.reshape(B, S, H, Dk)
    r = hd(_proj(mix(0), p["w_r"]))
    k = hd(_proj(mix(1), p["w_k"]))
    v = hd(_proj(mix(2), p["w_v"]))
    wraw = hd(_proj(mix(3), p["w_w"])).astype(jnp.float32)
    g = jax.nn.silu(hd(_proj(mix(4), p["w_g"])))
    # log decay in [-~20, -1e-4]; clamp for numerical sanity
    log_w = -jnp.exp(jnp.clip(p["w0"].astype(jnp.float32) + wraw, -8.0, 4.0))
    return r, k, v, g, log_w


def rwkv_time_mix(p: dict, x, state, *, chunk: int = 128):
    """Chunked-parallel RWKV6 time mix. x (B, S, d); state dict with
    'S' (B, H, Dk, Dk) and 'x_tm' (B, d). Returns (y, new_state)."""
    B, S, d = x.shape
    H, Dk = p["u"].shape
    L = min(chunk, S)
    while S % L:        # largest divisor of S ≤ chunk (shapes are powers of 2)
        L -= 1
    nc = S // L

    x_shift = _token_shift(x, state["x_tm"])
    r, k, v, g, log_w = _rwkv_projections(p, x, x_shift)
    u = p["u"].astype(jnp.float32)

    rc = r.reshape(B, nc, L, H, Dk).transpose(1, 0, 3, 2, 4)  # (nc,B,H,L,Dk)
    kc = k.reshape(B, nc, L, H, Dk).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, nc, L, H, Dk).transpose(1, 0, 3, 2, 4)
    wc = log_w.reshape(B, nc, L, H, Dk).transpose(1, 0, 3, 2, 4)

    def chunk_step(S_prev, xs):
        rb, kb, vb, lwb = xs                      # (B, H, L, Dk) each
        rb = rb.astype(jnp.float32)
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        logc = jnp.cumsum(lwb, axis=2)            # inclusive per-channel decay
        logc_excl = logc - lwb                    # exclusive (up to t-1)
        # inter-chunk: r_t ⊙ c_{t-1} applied to carried state
        q_in = rb * jnp.exp(logc_excl)
        o_inter = jnp.einsum("bhld,bhde->bhle", q_in, S_prev)
        # intra-chunk, strict lower triangle with pairwise decay
        # decay3[t, s, d] = exp(logc_excl[t] - logc[s]) for s < t
        dt = logc_excl[:, :, :, None, :] - logc[:, :, None, :, :]
        tri = (jnp.arange(L)[:, None] > jnp.arange(L)[None, :])
        decay3 = jnp.where(tri[None, None, :, :, None], jnp.exp(dt), 0.0)
        att = jnp.einsum("bhtd,bhsd,bhtsd->bhts", rb, kb, decay3)
        o_intra = jnp.einsum("bhts,bhse->bhte", att, vb)
        # current-token bonus: (r_t · u ⊙ k_t) v_t
        bonus = jnp.einsum("bhld,bhld->bhl", rb, u[None, :, None, :] * kb)
        o_bonus = bonus[..., None] * vb
        o = o_inter + o_intra + o_bonus           # (B, H, L, Dk)
        # state update: S = exp(logc_L) ⊙ S_prev + Σ_s exp(logc_L - logc_s) k_s v_sᵀ
        c_end = jnp.exp(logc[:, :, -1])           # (B, H, Dk)
        k_sc = kb * jnp.exp(logc[:, :, -1:, :] - logc)
        S_new = c_end[..., None] * S_prev + jnp.einsum("bhld,bhle->bhde",
                                                       k_sc, vb)
        return S_new, o

    S_fin, outs = jax.lax.scan(chunk_step, state["S"].astype(jnp.float32),
                               (rc, kc, vc, wc))
    o = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, Dk)
    o = _rwkv_out(p, o, g)
    return o, {"S": S_fin, "x_tm": x[:, -1]}


def _rwkv_out(p, o, g):
    """Per-head RMS group-norm, gate, output projection."""
    of = o.astype(jnp.float32)
    var = jnp.mean(of * of, axis=-1, keepdims=True)
    of = of * jax.lax.rsqrt(var + 1e-6) * (1.0 + p["gn"].astype(jnp.float32))
    of = of * g.astype(jnp.float32)
    B, S = of.shape[:2]
    w = p["w_out"]
    if not isinstance(w, RowBalancedSparse):
        w = w.reshape(-1, w.shape[-1])
    return _proj(of.astype(g.dtype).reshape(B, S, -1), w)


def rwkv_time_mix_step(p: dict, x, state):
    """Single-token decode. x (B, 1, d)."""
    B = x.shape[0]
    H, Dk = p["u"].shape
    x_shift = state["x_tm"][:, None, :].astype(x.dtype)
    r, k, v, g, log_w = _rwkv_projections(p, x, x_shift)
    rb = r[:, 0].astype(jnp.float32)              # (B, H, Dk)
    kb = k[:, 0].astype(jnp.float32)
    vb = v[:, 0].astype(jnp.float32)
    w = jnp.exp(log_w[:, 0])                      # (B, H, Dk)
    u = p["u"].astype(jnp.float32)
    S_prev = state["S"].astype(jnp.float32)       # (B, H, Dk, Dk)
    kv = kb[..., :, None] * vb[..., None, :]      # (B, H, Dk, Dk)
    o = jnp.einsum("bhd,bhde->bhe", rb, S_prev + u[None, :, :, None] * kv)
    S_new = w[..., None] * S_prev + kv
    o = _rwkv_out(p, o[:, None], g)               # (B,1,H,Dk) → (B,1,d)
    return o, {"S": S_new, "x_tm": x[:, -1]}


def rwkv_channel_mix(p: dict, x, state_x):
    """x (B, S, d); state_x (B, d) last token of prev segment."""
    x_shift = _token_shift(x, state_x)
    mu = p["mu_cm"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    mixed = (xf + mu * (x_shift.astype(jnp.float32) - xf)).astype(x.dtype)
    h = _proj(mixed, p["w_cm1"])
    h = jax.nn.relu(h)
    h = h * h
    y = _proj(h, p["w_cm2"])
    return y, x[:, -1]


def rwkv_state_defs(batch: int, num_heads: int, head_dim: int, d_model: int,
                    dtype) -> dict:
    return {
        "S": PSpec((batch, num_heads, head_dim, head_dim),
                   ("batch", "heads", "head_dim", None), init="zeros",
                   dtype=jnp.float32),
        "x_tm": PSpec((batch, d_model), ("batch", "embed"), init="zeros",
                      dtype=dtype),
        "x_cm": PSpec((batch, d_model), ("batch", "embed"), init="zeros",
                      dtype=dtype),
    }


# ================================================================ Mamba-2

def mamba2_defs(d_model: int, heads: int, head_dim: int, d_state: int,
                groups: int, conv_width: int, dtype) -> dict:
    """Mamba-2 mixer (the SSD layer of Dao & Gu 2024, as HF's
    ``GraniteMoeHybridMambaLayer``): in_proj to [z; xBC; dt], a causal
    depthwise conv with bias over xBC, per-head dt bias, A (as log -A) and
    skip D, a gated RMSNorm over d_inner, out_proj."""
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * groups * d_state
    return {
        "w_inproj": PSpec((d_model, d_inner + conv_dim + heads),
                          ("embed", "mlp"), dtype=dtype),
        "conv_w": PSpec((conv_width, conv_dim), ("conv", "mlp"),
                        dtype=dtype, scale=0.3),
        "conv_b": PSpec((conv_dim,), ("mlp",), init="zeros", dtype=dtype),
        "dt_bias": PSpec((heads,), ("heads",), init="zeros",
                         dtype=jnp.float32),
        "A_log": PSpec((heads,), ("heads",), init="zeros",
                       dtype=jnp.float32),
        "D": PSpec((heads,), ("heads",), init="ones", dtype=jnp.float32),
        "norm": PSpec((d_inner,), ("mlp",), init="zeros",
                      dtype=jnp.float32),
        "w_outproj": PSpec((d_inner, d_model), ("mlp", "embed"),
                           dtype=dtype),
    }


def mamba2_state_defs(batch: int, heads: int, head_dim: int, d_state: int,
                      groups: int, conv_width: int) -> dict:
    """Per-slot state: the conv's last ``conv_width - 1`` xBC inputs and
    the SSM state, both float32."""
    conv_dim = heads * head_dim + 2 * groups * d_state
    return {
        "conv": PSpec((batch, conv_width - 1, conv_dim),
                      ("batch", "conv", "mlp"), init="zeros",
                      dtype=jnp.float32),
        "ssm": PSpec((batch, heads, head_dim, d_state),
                     ("batch", "heads", "head_dim", None), init="zeros",
                     dtype=jnp.float32),
    }


def _mamba_in(p, x, precision):
    """in_proj and its split → (z, xBC before the conv, dt after softplus
    (..., H), A (H,))."""
    H = p["A_log"].shape[-1]
    d_inner = p["norm"].shape[-1]
    zxbcdt = _proj(x, p["w_inproj"], precision)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:-H]
    dt = jax.nn.softplus(zxbcdt[..., -H:].astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    return z, xbc, dt, A


def _mamba_split(p, xbc, groups: int):
    """Conv output (..., conv_dim) → x (..., H, P), B and C (..., G, N)."""
    H, d_inner = p["A_log"].shape[-1], p["norm"].shape[-1]
    lead = xbc.shape[:-1]
    N = (xbc.shape[-1] - d_inner) // (2 * groups)
    x = xbc[..., :d_inner].reshape(*lead, H, d_inner // H)
    Bm = xbc[..., d_inner:d_inner + groups * N].reshape(*lead, groups, N)
    Cm = xbc[..., d_inner + groups * N:].reshape(*lead, groups, N)
    return x, Bm, Cm


def _mamba_out(p, y, z, eps: float, precision):
    """Gated RMSNorm over d_inner (one group) and out_proj:
    out_proj(rmsnorm(y · silu(z)) · w)."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(var + eps) * (1.0 + p["norm"].astype(jnp.float32))
    return _proj(yf.astype(z.dtype), p["w_outproj"], precision)


def mamba2_step(p: dict, x, state, *, groups: int, eps: float,
                precision=None):
    """Single-token decode. x (B, 1, d_model); state {'conv', 'ssm'}.
    The state update is ``kernels.ops.mamba2_ssm_step`` (the Pallas kernel
    on a TPU); ``precision`` is that of dense projections. Returns
    (y (B, 1, d_model), new state)."""
    B = x.shape[0]
    z, xbc, dt, A = _mamba_in(p, x, precision)
    xbc, conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], state["conv"])
    xs, Bm, Cm = _mamba_split(p, jax.nn.silu(xbc[:, 0]), groups)
    ssm, y = kops.mamba2_ssm_step(state["ssm"], xs, dt[:, 0], A, Bm, Cm,
                                  p["D"], backend=kops.model_backend())
    y = y.reshape(B, 1, -1).astype(x.dtype)
    return _mamba_out(p, y, z, eps, precision), {
        "conv": conv.astype(jnp.float32), "ssm": ssm}


def mamba2_apply(p: dict, x, *, groups: int, eps: float, length=None,
                 backend: str | None = None, precision=None):
    """Whole sequence from zero state. x (B, S, d_model). The projections
    and the conv run once over all positions; the recurrence is a
    sequential scan of the decode step's state update (exact). ``length``
    ((B,) or scalar true lengths) masks padding: past it dt is 0, so the
    SSM state stays put, and the conv state holds the last
    ``conv_width - 1`` inputs before it. ``backend`` picks the state
    update's implementation (``ref`` in training: the kernel has no VJP),
    ``precision`` that of dense projections. Returns (y (B, S, d_model),
    state)."""
    Bsz, S = x.shape[:2]
    z, xbc_in, dt, A = _mamba_in(p, x, precision)
    xbc, _ = _causal_conv(xbc_in, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = _mamba_split(p, jax.nn.silu(xbc), groups)
    W = p["conv_w"].shape[0]
    xp = jnp.concatenate(
        [jnp.zeros((Bsz, W - 1, xbc_in.shape[-1]), xbc_in.dtype), xbc_in], 1)
    if length is None:
        conv = xp[:, S:]
    else:
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (Bsz,))
        dt = jnp.where(jnp.arange(S)[None, :, None] < length[:, None, None],
                       dt, 0.0)
        idx = length[:, None] + jnp.arange(W - 1)[None, :]
        conv = jnp.take_along_axis(xp, idx[..., None], axis=1)
    if backend is None:
        backend = kops.model_backend()
    H, P = xs.shape[-2:]
    s0 = jnp.zeros((Bsz, H, P, Bm.shape[-1]), jnp.float32)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        return kops.mamba2_ssm_step(s, x_t, dt_t, A, b_t, c_t, p["D"],
                                    backend=backend)

    ssm, ys = jax.lax.scan(step, s0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (xs, dt, Bm, Cm)))
    y = jnp.swapaxes(ys, 0, 1).reshape(Bsz, S, -1).astype(x.dtype)
    return _mamba_out(p, y, z, eps, precision), {
        "conv": conv.astype(jnp.float32), "ssm": ssm}
