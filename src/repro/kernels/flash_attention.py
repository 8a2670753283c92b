"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Used by the 32k-prefill and 4k-train paths of the assigned transformer
architectures. Causal + GQA + local-window support. Grid is
(batch, q_heads, q_blocks, kv_blocks) with fp32 running max / sum / acc
scratch carried across the kv_blocks dimension; fully-masked kv blocks are
skipped (causal/window block pruning), which matters at 32k: the causal
triangle halves the streamed bytes and FLOPs.

K/V BlockSpec index maps fold GQA: q head h reads kv head h // group, so
K/V tiles are fetched once per kv head group, not per q head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, bq, bk, sq, sk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # --- causal/window block pruning: q rows are right-aligned to the kv end
    off = sk - sq
    q_lo = iq * bq + off          # first absolute q position in this block
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = q @ k.T                                        # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...][:, :1]                         # (bq, 1)
        l_prev = l_scr[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(s > NEG / 2, jnp.exp(s - m_new), 0.0)
        l_new = jnp.exp(m_prev - m_new) * l_prev + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        acc_scr[...] = acc_scr[...] * jnp.exp(m_prev - m_new) + p @ v
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = 256, block_kv: int = 256,
                    interpret: bool):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0.

    Returns (B, Hq, Sq, D) in q.dtype. Sq/Sk must divide by the block sizes
    (ops wrapper pads)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_kv, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = D ** -0.5
    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=Sq, sk=Sk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
