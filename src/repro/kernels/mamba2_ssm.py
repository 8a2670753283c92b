"""Pallas TPU kernel: one Mamba-2 state-space decode step, state in place.

Per slot b and head h the selective state S (head_dim × d_state) decays by
exp(dt·A), takes the rank-one input dt·x ⊗ B, and is read out against C:

    S ← exp(dt_{b,h} A_h) · S + (dt_{b,h} x_{b,h}) ⊗ B_{b,g(h)}
    y_{b,h} = S C_{b,g(h)} + D_h x_{b,h}

with g(h) the head's group. The state is the bulk of the step's bytes
(slots × heads × head_dim × d_state float32), so it is read once and
written back over itself (``input_output_aliases``). The grid runs over
slots × blocks of heads; the per-(slot, head) scalars — the decay, dt and
D — ride in SMEM (scalar prefetch), and each head of a block is one
(head_dim, d_state) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_HEAD_BLOCK = 16


def head_block(heads_per_group: int) -> int:
    """Heads per grid step: the largest divisor of a group's heads up to
    ``MAX_HEAD_BLOCK`` (a block never spans two groups)."""
    return max(d for d in range(1, MAX_HEAD_BLOCK + 1)
               if heads_per_group % d == 0)


def _kernel(decay_ref, dt_ref, d_ref, s_ref, x_ref, b_ref, c_ref,
            s_out, y_out, *, hb: int, H: int):
    b, j = pl.program_id(0), pl.program_id(1)
    bv = b_ref[0, 0]                                  # (1, N)
    cv = c_ref[0, 0]                                  # (1, N)
    for i in range(hb):
        h = j * hb + i
        k = b * H + h
        x = x_ref[0, i]                               # (P, 1)
        s = decay_ref[k] * s_ref[0, i] + (dt_ref[k] * x) * bv
        s_out[0, i] = s
        y_out[0, i] = (jnp.sum(s * cv, axis=1, keepdims=True)
                       + d_ref[h] * x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_ssm_step(state, x, dt, A, Bm, Cm, D, *, interpret: bool):
    """state (B, H, P, N) float32; x (B, H, P); dt (B, H) (after softplus);
    A (H,) (negative); Bm, Cm (B, G, N); D (H,).
    Returns (new state (B, H, P, N), y (B, H, P)), both float32."""
    B, H, P, N = state.shape
    G = Bm.shape[1]
    hpg = H // G
    hb = head_block(hpg)
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * A.astype(f32)[None, :])
    x4 = x.astype(f32).reshape(B, H, P, 1)
    b4 = Bm.astype(f32).reshape(B, G, 1, N)
    c4 = Cm.astype(f32).reshape(B, G, 1, N)
    state_spec = pl.BlockSpec((1, hb, P, N), lambda b, j, *_: (b, j, 0, 0))
    head_spec = pl.BlockSpec((1, hb, P, 1), lambda b, j, *_: (b, j, 0, 0))
    group_spec = pl.BlockSpec((1, 1, 1, N),
                              lambda b, j, *_: (b, (j * hb) // hpg, 0, 0))
    s_new, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // hb),
            in_specs=[state_spec, head_spec, group_spec, group_spec],
            out_specs=[state_spec, head_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, P, N), f32),
                   jax.ShapeDtypeStruct((B, H, P, 1), f32)],
        # operands count the three scalar-prefetch arrays: the state is 3
        input_output_aliases={3: 0},
        interpret=interpret,
        name="mamba2_ssm_step",
    )(decay.reshape(-1), dt.reshape(-1), D.astype(f32), state.astype(f32),
      x4, b4, c4)
    return s_new, y.reshape(B, H, P)
