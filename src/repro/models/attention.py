"""Attention: GQA/MQA/MHA with qk-norm, RoPE or NoPE, local windows, KV
caches.

Tensor-parallel strategy (model axis = 16 on the production mesh):
- Projection WEIGHTS shard greedily: heads → model if divisible, else
  head_dim → model (within-head Megatron split), else replicated.
- Attention COMPUTE always shards over q heads: `prepare_heads` repeats kv
  to the q-head count (GQA dup) and pads heads up to the next multiple of
  the model-axis size (dummy heads are zero → inert; outputs are sliced
  back). This keeps the online-softmax scan free of collectives; XLA
  inserts one reshard after the projections and one all-reduce after the
  output projection — the standard Megatron pattern, GQA-safe for any
  head count (llava's 56, llama's 24, MQA's 1, ...).

Two execution paths:
- `blocked_attention` — memory-safe online-softmax attention in pure jnp
  (nested lax.scan over q/kv blocks); the dry-run lowers this for
  train/prefill. The Pallas flash kernel is its TPU twin.
- `decode_attention_einsum` — single-token decode against a long cache
  (directly einsum-able; Pallas decode kernel is the TPU twin).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from .layers import PSpec, rope, rmsnorm, pmm, project
from ..core.packing import RowBalancedSparse
from ..sharding import constrain, _current_mesh


def attn_defs(d_model: int, num_heads: int, num_kv_heads: int, head_dim: int,
              qk_norm: bool, dtype) -> dict:
    d = {
        "wq": PSpec((d_model, num_heads, head_dim),
                    ("embed", "heads", "head_dim"), dtype=dtype),
        "wk": PSpec((d_model, num_kv_heads, head_dim),
                    ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": PSpec((d_model, num_kv_heads, head_dim),
                    ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": PSpec((num_heads, head_dim, d_model),
                    ("heads", "head_dim", "embed"), dtype=dtype),
    }
    if qk_norm:
        d["q_norm"] = PSpec((head_dim,), ("head_dim",), init="zeros",
                            dtype=jnp.float32)
        d["k_norm"] = PSpec((head_dim,), ("head_dim",), init="zeros",
                            dtype=jnp.float32)
    return d


def model_axis_size() -> int:
    mesh = _current_mesh()
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def _heads(x, w, head_dim: int, precision=None):
    """x (B, S, d) @ a (d, H, Dh) weight → (B, S, H, Dh), a dense one at
    ``precision``; a packed weight (rows = H·Dh) goes through
    ``layers.project``."""
    if isinstance(w, RowBalancedSparse):
        y = project(x, w)
        return y.reshape(*y.shape[:-1], -1, head_dim)
    return pmm(x, w, precision)


def qkv_project(p: dict, x, positions, *, qk_norm: bool, rope_theta: float,
                use_rope: bool = True, head_dim: int | None = None,
                precision=None):
    """x (B, S, d) → q (B, S, Hq, Dh), k/v (B, S, Hkv, Dh). ``head_dim``
    shapes the heads of packed weights; ``precision`` is that of dense
    ones."""
    q = _heads(x, p["wq"], head_dim, precision)
    k = _heads(x, p["wk"], head_dim, precision)
    v = _heads(x, p["wv"], head_dim, precision)
    if qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q, k, v


def prepare_heads(q, k, v, true_heads: int):
    """GQA dup + head padding for clean tensor parallelism.

    q (B,S,H_eff,D) where H_eff ≥ true_heads (param-level TP padding);
    k/v (B,S,Hkv,D). kv heads are repeated per TRUE GQA group
    (G = true_heads // Hkv), then everything is padded to
    Hp = H_eff rounded up to a model-axis multiple. Padded q rows attend to
    zero keys → uniform garbage that is sliced/masked away downstream.
    Returns (q', k', v') all (B,S,Hp,D)."""
    B, S, H_eff, D = q.shape
    Hkv = k.shape[2]
    G = true_heads // Hkv
    ms = model_axis_size()
    Hp = ((H_eff + ms - 1) // ms) * ms
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    if Hp != k.shape[2]:
        pad = ((0, 0), (0, 0), (0, Hp - k.shape[2]), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    if Hp != H_eff:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - H_eff), (0, 0)))
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    return q, k, v


def out_project(p: dict, o, precision=None):
    wo = p["wo"]
    if isinstance(wo, RowBalancedSparse):
        return project(o.reshape(*o.shape[:2], -1), wo)
    return pmm(o.reshape(*o.shape[:2], -1),
               wo.reshape(-1, wo.shape[-1]), precision)


# ------------------------------------------------------- blocked attention

def blocked_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                      q_offset: int = 0, block_q: int = 512,
                      block_kv: int = 1024, scale: float | None = None,
                      precision=None):
    """Online-softmax attention, lax.scan over q and kv blocks. MHA layout:
    q, k, v (B, S, H, D) with equal head counts (see prepare_heads).
    q_offset: absolute position of q[0] (kv positions are 0..Sk-1).
    ``scale`` None is 1/sqrt(D); ``precision`` that of the two einsums."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_kv, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = jnp.float32(D ** -0.5 if scale is None else scale)

    qb = q.reshape(B, nq, bq, H, D).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, nk, bk, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, bk, H, D).transpose(1, 0, 2, 3, 4)
    NEG = jnp.float32(-1e30)

    def q_step(_, qx):
        iq, qblk = qx                            # qblk (B, bq, H, D)
        qf = qblk.astype(jnp.float32) * scale

        def kv_step(carry, kx):
            m, l, acc = carry
            ik, kblk, vblk = kx
            s = jnp.einsum("bqhd,bkhd->bhqk", qf,
                           kblk.astype(jnp.float32),
                           precision=precision)             # (B,H,bq,bk)
            qpos = q_offset + iq * bq + jnp.arange(bq)
            kpos = ik * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(s > NEG / 2, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32),
                precision=precision)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), NEG, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,H,bq,D)
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)


def full_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                   scale=None, precision=None):
    """Direct einsum attention (small seq). MHA layout (B, S, H, D).
    ``scale`` None is 1/sqrt(D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32), precision=precision)
    qpos = q_offset + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                   precision=precision)
    return o.astype(q.dtype)


def decode_attention_einsum(q, k_cache, v_cache, length, window=None,
                            scale=None, precision=None):
    """q: (B, 1, H, D) (post prepare_heads); caches (B, Smax, H, D);
    length: scalar valid length, or (B,) per-sequence lengths (continuous
    batching over ragged sequences). ``scale`` None is 1/sqrt(D).
    Returns (B, 1, H, D)."""
    B, _, H, D = q.shape
    Smax = k_cache.shape[1]
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32), precision=precision)
    if getattr(length, "ndim", 0) == 1:
        length = length.reshape(-1, 1, 1, 1)
    kpos = jnp.arange(Smax)[None, None, None, :]
    mask = kpos < length
    if window is not None:
        mask = mask & (kpos > length - 1 - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v_cache.astype(jnp.float32),
                   precision=precision)
    return o.astype(q.dtype)


def expand_cache_heads(k_cache, v_cache, true_heads: int, h_eff: int):
    """Repeat+pad cached TRUE kv heads (B,S,Hkv,D) to the padded q-head
    layout for decode compute. Per-chip slices only under SPMD."""
    Hkv = k_cache.shape[2]
    G = true_heads // Hkv
    ms = model_axis_size()
    Hp = ((h_eff + ms - 1) // ms) * ms
    if G > 1:
        k_cache = jnp.repeat(k_cache, G, axis=2)
        v_cache = jnp.repeat(v_cache, G, axis=2)
    if Hp != k_cache.shape[2]:
        pad = ((0, 0), (0, 0), (0, Hp - k_cache.shape[2]), (0, 0))
        k_cache, v_cache = jnp.pad(k_cache, pad), jnp.pad(v_cache, pad)
    # decode keeps the split-KV layout: seq stays model-sharded, heads
    # replicated (head expansion is then a purely local slice)
    k_cache = constrain(k_cache, "batch", "cache_seq", None, None)
    v_cache = constrain(v_cache, "batch", "cache_seq", None, None)
    return k_cache, v_cache, Hp


def pad_q_heads(q):
    """Pad q (B,1,H_eff,D) to the model-axis multiple (decode path).
    Decode q stays head-replicated: the model axis is spent on the cache
    seq dim (split-KV), and single-token attention flops are negligible."""
    B, S, Hq, D = q.shape
    ms = model_axis_size()
    Hp = ((Hq + ms - 1) // ms) * ms
    if Hp != Hq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - Hq), (0, 0)))
    return constrain(q, "batch", "seq", None, None), Hq


# ----------------------------------------------------------------- caches

def kv_cache_defs(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype, quant: bool = False) -> dict:
    """KV cache declarations. quant=True stores int8 values + per-(pos,head)
    f32 scales — a beyond-paper extension of BRDS's quantization axis
    (fixed-16 there): decode_32k cells are CACHE-streaming-bound, so int8
    halves their dominant roofline term at ~1.6% scale overhead."""
    shape = (batch, max_len, num_kv_heads, head_dim)
    axes = ("batch", "cache_seq", "kv_heads", "head_dim")
    if quant:
        sshape = (batch, max_len, num_kv_heads, 1)
        return {
            "k": PSpec(shape, axes, init="zeros", dtype=jnp.int8),
            "v": PSpec(shape, axes, init="zeros", dtype=jnp.int8),
            "k_scale": PSpec(sshape, axes, init="zeros", dtype=jnp.float32),
            "v_scale": PSpec(sshape, axes, init="zeros", dtype=jnp.float32),
        }
    return {"k": PSpec(shape, axes, init="zeros", dtype=dtype),
            "v": PSpec(shape, axes, init="zeros", dtype=dtype)}


def _quantize_kv(x):
    """(B,S,H,D) → (int8 values, (B,S,H,1) f32 scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_cache(cache: dict, dtype):
    """→ plain {'k','v'} view in compute dtype (no-op if unquantized)."""
    if "k_scale" not in cache:
        return cache
    k = (cache["k"].astype(jnp.float32) * cache["k_scale"]).astype(dtype)
    v = (cache["v"].astype(jnp.float32) * cache["v_scale"]).astype(dtype)
    return {"k": k, "v": v}


def kv_cache_update(cache: dict, k_new, v_new, pos):
    """Insert k/v (B, S_new, Hkv, D) at `pos` — a scalar (every sequence at
    the same position) or an (B,) int vector of per-sequence positions
    (continuous batching over ragged sequences; S_new must be 1)."""
    if getattr(pos, "ndim", 0) == 1:
        idx = jnp.arange(k_new.shape[0])

        def ins(buf, new):
            return buf.at[idx, pos].set(new[:, 0].astype(buf.dtype))
    else:
        def ins(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (0, pos, 0, 0))
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        return {
            "k": ins(cache["k"], kq),
            "v": ins(cache["v"], vq),
            "k_scale": ins(cache["k_scale"], ks),
            "v_scale": ins(cache["v_scale"], vs),
        }
    return {"k": ins(cache["k"], k_new), "v": ins(cache["v"], v_new)}
