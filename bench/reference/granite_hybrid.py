"""Plain reference of the Granite 4.0-H hybrid (HF ``GraniteMoeHybrid``
with no experts), in ``jax.numpy``.

No kernel, no packing, no cache, no batching tricks. Tokens are embedded
and scaled, every layer is

    x += r · mixer(rms(x) · w1);   x += r · mlp(rms(x) · w2)
    mlp(h) = W_down (silu(h W_gate) · (h W_up))      ([a; u] = W_in h)

with r = ``residual_multiplier``, and the logits are
rms(x) · w_f · Eᵀ / ``logits_scaling`` (E the tied embedding table). A
Mamba-2 mixer is

    [z; xBC; dt] = h W_in;   xBC = silu(causal depthwise conv(xBC) + b)
    x, B, C = split(xBC);     dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_h ← exp(dt_h A_h) S_h + dt_h x_h ⊗ B;   y_h = S_h C + D_h x_h
    out = (rms(y · silu(z)) · w_n) W_out

run as a sequential recurrence from zero state, and an attention mixer is
causal GQA softmax(q kᵀ · ``attention_multiplier``) v with no position
embedding (NoPE), queries in blocks so the scores stay small.

Weights are the dense (pruned) matrices in (in, out) orientation; norm
weights are the HF ones (multiplied, not offset). It imports nothing of
the program. Departures from HF: none beyond the precision — float32 at
"highest" matmul precision (HF serves the published bf16 weights).
``dtype=bfloat16`` at the default precision is the control that a check
must be able to tell apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def _mlp(p, h):
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _mamba(p, h, spec):
    N_, T = h.shape[:2]
    H, P, N, G = (spec["mamba_heads"], spec["mamba_head_dim"],
                  spec["mamba_d_state"], spec["mamba_groups"])
    d_inner = H * P
    zxbcdt = h @ p["in_proj"]
    z, xbc, dt = (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:-H],
                  zxbcdt[..., -H:])
    W = p["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + T] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(N_, T, H, P).astype(F32)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(N_, T, G, N).astype(F32)
    Cm = xbc[..., d_inner + G * N:].reshape(N_, T, G, N).astype(F32)
    Bm, Cm = (jnp.repeat(Bm, H // G, axis=2), jnp.repeat(Cm, H // G, axis=2))
    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))
    D = p["D"].astype(F32)
    sdt = h.dtype               # the state runs in the computation's dtype

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp           # (N_, H, P), (N_, H), (N_, H, N)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        S = S.astype(sdt)
        y = jnp.einsum("bhpn,bhn->bhp", S, c_t.astype(sdt)).astype(F32)
        return S, y + D[None, :, None] * x_t

    S0 = jnp.zeros((N_, H, P, N), sdt)
    _, ys = jax.lax.scan(step, S0, tuple(
        jnp.swapaxes(a, 0, 1) for a in (xs, dt, Bm, Cm)))
    y = jnp.swapaxes(ys, 0, 1).reshape(N_, T, d_inner)
    y = y * jax.nn.silu(z.astype(F32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + spec["rms_norm_eps"]) * p["norm"].astype(F32)
    return y.astype(h.dtype) @ p["out_proj"]


def _attention(p, h, spec, block=128):
    N_, T = h.shape[:2]
    Hq, Hkv, Dh = (spec["num_attention_heads"], spec["num_key_value_heads"],
                   spec["head_dim"])
    q = (h @ p["q"]).reshape(N_, T, Hq, Dh)
    k = jnp.repeat((h @ p["k"]).reshape(N_, T, Hkv, Dh), Hq // Hkv, axis=2)
    v = jnp.repeat((h @ p["v"]).reshape(N_, T, Hkv, Dh), Hq // Hkv, axis=2)
    nb = -(-T // block)
    qb = jnp.pad(q, ((0, 0), (0, nb * block - T), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(N_, nb, block, Hq, Dh), 1, 0)

    def one(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(F32)
        s = s * spec["attention_multiplier"]
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v)

    o = jax.lax.map(one, (jnp.arange(nb), qb))
    o = jnp.moveaxis(o, 0, 1).reshape(N_, nb * block, Hq * Dh)[:, :T]
    return o @ p["o"]


def hidden(params, tokens, spec, dtype=F32):
    """tokens (N, T) int → the final-normed hidden states (N, T, d)."""
    ps = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.take(ps["embed"], tokens, axis=0) * spec["embedding_multiplier"]
    r, eps = spec["residual_multiplier"], spec["rms_norm_eps"]
    for lp in ps["layers"]:
        h = _rms(x, lp["input_norm"], eps)
        if "in_proj" in lp["mixer"]:
            y = _mamba(lp["mixer"], h, spec)
        else:
            y = _attention(lp["mixer"], h, spec)
        x = x + r * y
        x = x + r * _mlp(lp["mlp"], _rms(x, lp["post_norm"], eps))
    return _rms(x, ps["final_norm"], eps)


def logits(params, hs, spec, dtype=F32):
    """hs (..., d) → logits (..., V), float32."""
    table = params["embed"].astype(dtype)
    out = jnp.einsum("...d,vd->...v", hs.astype(dtype), table)
    return out.astype(F32) / spec["logits_scaling"]


def served_gaps(params, tokens, targets, valid, spec, control=False,
                block=128):
    """The gap by which each served token's logit lies below the
    reference's best at its position, the head run over ``block``
    positions at a time.

    tokens (N, T): prompt and served tokens but the last; targets (N, T):
    the token served at each position (valid where ``valid``); T a
    multiple of ``block``. Returns (gap of the served token, gap of the
    token the bfloat16 control puts first, or zeros without ``control``),
    each (N, T), 0 where not valid. Call it under
    ``jax.default_matmul_precision("highest")``.
    """
    N_, T = tokens.shape
    hs = hidden(params, tokens, spec)
    low = hidden(params, tokens, spec, jnp.bfloat16) if control else hs
    nb = T // block
    split = lambda a: jnp.moveaxis(a.reshape(N_, nb, block, *a.shape[2:]),
                                   1, 0)

    def one(args):
        h, hl, tg, ok = args
        ref = logits(params, h, spec)
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, tg[..., None], axis=-1)[..., 0]
        gap = jnp.where(ok, best - served, 0.0)
        if not control:
            return gap, jnp.zeros_like(gap)
        pick = jnp.argmax(logits(params, hl, spec, jnp.bfloat16), axis=-1)
        ctl = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return gap, jnp.where(ok, best - ctl, 0.0)

    gap, ctl = jax.lax.map(one, (split(hs), split(low), split(targets),
                                 split(valid)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(N_, T)
    return join(gap), join(ctl)
