"""Pallas TPU kernel: single-token decode attention over a long KV cache.

The dominant op of the decode_32k cells: one query attends to a 32k cache.
Purely memory-bound (arithmetic intensity ≈ 1 flop/byte), so the kernel's
job is to stream K/V through VMEM exactly once with online softmax, skipping
blocks past the valid cache length. Valid lengths live in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, bk, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]
    live = ik * bk < length

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, d) q-head group
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = q @ k.T                                          # (G, bk)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(s > NEG / 2, jnp.exp(s - m_new), 0.0)
        l_new = jnp.exp(m_prev - m_new) * l_prev + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * jnp.exp(m_prev - m_new) + p @ v
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k, v, lengths, *, block_kv: int = 512,
                     interpret: bool):
    """q: (B, Hq, D); k, v: (B, Hkv, S, D); lengths: (B,) int32.

    Returns (B, Hq, D). The q heads of one kv group ride in the same tile
    (G = Hq // Hkv rows), so K/V stream once per kv head."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    bk = min(block_kv, S)
    assert S % bk == 0, (S, bk)
    nk = S // bk
    qg = q.reshape(B, Hkv, G, D)
    grid = (B, Hkv, nk)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=D ** -0.5, bk=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, *_: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, *_: (b, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((G, 128), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, Hq, D)
