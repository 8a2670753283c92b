"""Plain reference of the paper's LSTM (BRDS eq. 1-2), in ``jax.numpy``.

No kernel, no packing, no cache, no batching tricks: each layer is

    z_t = W_x x_t + W_h h_{t-1} + b,  rows grouped [f; i; g; o]
    f, i, o = sigmoid(z_f), sigmoid(z_i), sigmoid(z_o);  g = tanh(z_g)
    c_t = f * c_{t-1} + i * g;        h_t = o * tanh(c_t)

scanned over time from zero state, with the dense weights the benchmark
made (already row-balanced sparse, so no pruning happens here). A
language model embeds token ids first and applies its head to every
position; a framewise classifier applies its head to the last valid
frame. It imports nothing of the program.

``dtype`` is the precision the whole computation runs in: float32 at
"highest" matmul precision is the reference; bfloat16 at the default
precision is the control that a check must be able to tell apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _prec(dtype):
    return HIGHEST if dtype == jnp.float32 else None


def _cell(z, c, H):
    sig = jax.nn.sigmoid
    f, i = sig(z[:, :H]), sig(z[:, H:2 * H])
    g, o = jnp.tanh(z[:, 2 * H:3 * H]), sig(z[:, 3 * H:])
    c = f * c + i * g
    return c, o * jnp.tanh(c)


def layers(params, xs, lengths, dtype=jnp.float32):
    """xs (N, T, X) → (hidden states of the last layer (N, T, H),
    [(c, h) at each row's last valid step, per layer])."""
    prec = _prec(dtype)
    N, T = xs.shape[:2]
    xs = xs.astype(dtype)
    finals = []
    for lp in params["layers"]:
        H = lp["w_h"].shape[1]
        w_x, w_h = lp["w_x"].astype(dtype), lp["w_h"].astype(dtype)
        b = lp["b"].astype(dtype)

        def step(carry, xt, w_x=w_x, w_h=w_h, b=b, H=H):
            c, h = carry
            x_t, t = xt
            z = (jnp.dot(x_t, w_x.T, precision=prec)
                 + jnp.dot(h, w_h.T, precision=prec) + b)
            c2, h2 = _cell(z, c, H)
            keep = (t < lengths)[:, None]
            c2, h2 = jnp.where(keep, c2, c), jnp.where(keep, h2, h)
            return (c2, h2), h2

        zero = jnp.zeros((N, H), dtype)
        (c, h), hs = jax.lax.scan(step, (zero, zero),
                                  (xs.transpose(1, 0, 2), jnp.arange(T)))
        finals.append((c, h))
        xs = hs.transpose(1, 0, 2)
    return xs, finals


def lm_hidden(params, tokens, lengths, dtype=jnp.float32):
    """tokens (N, T) int → last layer's hidden states (N, T, H)."""
    xs = jnp.take(params["embed"]["table"], tokens, axis=0)
    return layers(params, xs, lengths, dtype)[0]


def head(params, hs, dtype=jnp.float32):
    """hs (..., H) → logits (..., V or C), float32."""
    return jnp.dot(hs.astype(dtype), params["head"]["w"].astype(dtype),
                   precision=_prec(dtype)).astype(jnp.float32)


def served_gaps(params, tokens, lengths, targets, valid,
                control: bool = False):
    """The gap by which each served token's logit lies below the
    reference's best at its position.

    tokens (N, T): prompt and served tokens but the last; targets (N, T):
    the token served at each position (valid where ``valid``). Returns
    (gap of the served token, gap of the token the bfloat16 control puts
    first, or zeros without ``control``), each (N, T), 0 where not valid.
    """
    hs = lm_hidden(params, tokens, lengths)
    ref = head(params, hs)
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, targets[..., None], axis=-1)[..., 0]
    gap = jnp.where(valid, best - served, 0.0)
    if not control:
        return gap, jnp.zeros_like(gap)
    low = head(params, lm_hidden(params, tokens, lengths, jnp.bfloat16),
               jnp.bfloat16)
    pick = jnp.argmax(low, axis=-1)
    ctl = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return gap, jnp.where(valid, best - ctl, 0.0)


def final_states(params, xs, lengths, dtype=jnp.float32):
    """Framewise model: ([(c, h)] per layer at each row's last valid
    frame, logits (N, C) of that frame)."""
    hs, finals = layers(params, xs, lengths, dtype)
    last = finals[-1][1]
    return finals, head(params, last, dtype)
