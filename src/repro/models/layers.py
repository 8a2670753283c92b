"""Parameter definition system + basic NN layers (pure functional JAX).

Params are nested dicts of arrays. Structure is declared once as a pytree of
`PSpec` (shape + logical sharding axes + init); the same declaration yields
concrete params (init), abstract params (dry-run ShapeDtypeStructs), and
NamedShardings (via repro.sharding).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packing import RowBalancedSparse
from ..kernels.ops import rb_project
from ..sharding import Axes, constrain

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


@dataclasses.dataclass(frozen=True)
class PSpec:
    """Declaration of one parameter tensor."""
    shape: tuple
    axes: tuple                      # logical axis names, len == ndim
    init: str = "normal"             # normal | zeros | ones
    scale: float | None = None       # stddev override (default 1/sqrt(fan_in))
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def _default_scale(shape) -> float:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return 1.0 / math.sqrt(max(fan_in, 1))


def init_params(defs, rng):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_pspec)
    keys = jax.random.split(rng, max(len(leaves), 1))
    out = []
    for k, d in zip(keys, leaves):
        if d.init == "zeros":
            a = jnp.zeros(d.shape, d.dtype)
        elif d.init == "ones":
            a = jnp.ones(d.shape, d.dtype)
        else:
            s = d.scale if d.scale is not None else _default_scale(d.shape)
            a = (jax.random.normal(k, d.shape, jnp.float32) * s).astype(d.dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


def abstract_params(defs):
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
                        defs, is_leaf=is_pspec)


def param_axes(defs):
    return jax.tree.map(lambda d: Axes(*d.axes), defs, is_leaf=is_pspec)


def param_shapes(defs):
    return jax.tree.map(lambda d: d.shape, defs, is_leaf=is_pspec)


def stack_defs(defs, n: int):
    """Prepend a scan-stacked 'layers' dimension to every PSpec."""
    return jax.tree.map(
        lambda d: PSpec((n, *d.shape), ("layers", *d.axes), d.init, d.scale,
                        d.dtype),
        defs, is_leaf=is_pspec)


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_pspec)
    return sum(int(np.prod(d.shape)) for d in leaves)


# ------------------------------------------------------------------ layers

def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return y.astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32)) \
        + b.astype(jnp.float32)
    return y.astype(x.dtype)


def norm_defs(kind: str, dim: int) -> dict:
    if kind == "rmsnorm":
        return {"w": PSpec((dim,), ("embed",), init="zeros", dtype=jnp.float32)}
    return {"w": PSpec((dim,), ("embed",), init="zeros", dtype=jnp.float32),
            "b": PSpec((dim,), ("embed",), init="zeros", dtype=jnp.float32)}


def apply_norm(kind: str, p: dict, x, eps: float | None = None):
    """The block's norm; ``eps`` None is each norm's own default."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"], **kw)
    return layernorm(x, p["w"], p["b"], **kw)


# ------------------------------------------------------------------ RoPE

def rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, D) or (..., H, D) w/ scalar positions. positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                                      # (..., S, 1, half)
    sin = sin[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------- TP-aware matmul

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def pmm(x, w, precision=None):
    """y = x @ w over the last dim of x; w (K, N) or (K, *N) flattened.

    Same forward as einsum; the custom VJP keeps the ACTIVATION gradient in
    the activation dtype (bf16). jax's default VJP marks backward dots
    preferred_element_type=f32, which makes XLA all-reduce f32 partials when
    the contracted dim is model-sharded — 2× the wire bytes of the tensor-
    parallel backward (nemotron §Perf cell 2). Weight grads stay f32.
    ``precision`` is the dots' (None: the backend's default).
    """
    wf = w.reshape(w.shape[0], -1)
    y = jnp.matmul(x, wf, precision=precision)
    return y.reshape(*x.shape[:-1], *w.shape[1:])


def _pmm_fwd(x, w, precision):
    return pmm(x, w, precision), (x, w)


def _pmm_bwd(precision, res, g):
    x, w = res
    wf = w.reshape(w.shape[0], -1)
    g2 = g.reshape(*x.shape[:-1], wf.shape[1]).astype(x.dtype)
    # bf16-wire activation grad
    gx = jnp.matmul(g2, wf.T, precision=precision)
    gw = jax.lax.dot_general(
        x.reshape(-1, x.shape[-1]), g2.reshape(-1, g2.shape[-1]),
        (((0,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)          # f32 accumulation
    # cotangent dtype must match the primal's; the f32 accumulation above
    # still protects the local reduction, the DP all-reduce rides in bf16
    return gx.astype(x.dtype), gw.reshape(w.shape).astype(w.dtype)


pmm.defvjp(_pmm_fwd, _pmm_bwd)


def project(x, w, precision=None):
    """x @ w over the last dim of x: ``pmm`` at ``precision`` for a dense
    (K, *N) weight, ``kernels.ops.rb_project`` for a packed one (rows =
    flattened N), which returns (..., rows)."""
    if isinstance(w, RowBalancedSparse):
        return rb_project(x, w)
    return pmm(x, w, precision)


# ------------------------------------------------------------------ MLP

def mlp_defs(d_model: int, d_ff: int, activation: str, dtype) -> dict:
    if activation in ("silu_glu", "gelu_glu"):
        return {
            "w_gate": PSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
            "w_up": PSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
            "w_down": PSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
        }
    return {
        "w_up": PSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "w_down": PSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def _act(activation: str, x):
    if activation.startswith("silu"):
        return jax.nn.silu(x)
    if activation.startswith("gelu"):
        return jax.nn.gelu(x)
    if activation == "sq_relu":
        r = jax.nn.relu(x)
        return r * r
    if activation == "relu":
        return jax.nn.relu(x)
    raise ValueError(activation)


def mlp_apply(p: dict, x, activation: str, precision=None):
    """x: (..., d_model). Weight masks (BRDS) are pre-applied to params;
    ``precision`` is that of the dense projections."""
    if activation.endswith("_glu"):
        g = _act(activation, project(x, p["w_gate"], precision))
        u = project(x, p["w_up"], precision)
        h = g * u
    else:
        h = _act(activation, project(x, p["w_up"], precision))
    h = constrain(h, "batch", "seq", "mlp") if h.ndim == 3 else h
    return project(h, p["w_down"], precision)


# ------------------------------------------------------------------ embed

def pad_vocab(v: int, mult: int = 256) -> int:
    return ((v + mult - 1) // mult) * mult


def embed_defs(vocab_padded: int, d_model: int, dtype) -> dict:
    return {"table": PSpec((vocab_padded, d_model), ("vocab", "embed"),
                           scale=1.0, dtype=dtype)}


def embed_apply(p: dict, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def logits_apply(p_head, x, real_vocab: int):
    """x (..., d) @ head (d, Vp) → (..., V) fp32, padding masked to -inf.

    The pad mask is an elementwise iota compare (partition-friendly along a
    model-sharded vocab dim, unlike a slice-update)."""
    logits = jnp.einsum("...d,dv->...v", x, p_head["w"]).astype(jnp.float32)
    vp = p_head["w"].shape[-1]
    if vp != real_vocab:
        vocab_pos = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                             logits.ndim - 1)
        logits = jnp.where(vocab_pos < real_vocab, logits, -1e30)
    return logits
