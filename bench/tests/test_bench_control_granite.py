"""The granite_h_decode control: the plain reference, put in the program's
place and run in bfloat16 (the next precision below the configuration's
float32), reads above the cell's ``logit_gap`` limit, and the float32
reference's own picks read 0. At a width the CPU holds in a test: hidden
256, vocab 4096, the ten-layer period, 4 sequences of 256 positions."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench.lib import harness, hybrid_lm, registry
from bench.reference import granite_hybrid as ref

WIDTHS = dict(hidden_size=256, intermediate_size=1024, vocab_size=4096,
              num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
              mamba_d_head=32, mamba_d_state=32)


def test_bf16_control_fails_the_limit():
    cfg = registry.config(registry.benchmark(), "granite_4_h_micro")
    cfg.update(WIDTHS)
    arch = hybrid_lm.model_config(cfg)
    params = hybrid_lm.reference_params(
        hybrid_lm.make_params(cfg, arch, 5), cfg)
    spec = hybrid_lm.spec(cfg)
    N, T, P = 4, 256, 8
    tokens = np.random.default_rng(5).integers(
        0, cfg["vocab_size"], size=(N, T)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        hs = ref.hidden(params, jnp.asarray(tokens), spec)
        # served tokens: the float32 reference's own pick at each position
        targets = jnp.argmax(ref.logits(params, hs, spec), axis=-1)
        valid = np.zeros((N, T), bool)
        valid[:, P - 1:] = True
        gap, ctl = jax.jit(functools.partial(
            ref.served_gaps, spec=spec, control=True))(
            params, jnp.asarray(tokens), targets, jnp.asarray(valid))
    gap, ctl = float(jnp.max(gap)), float(jnp.max(ctl))
    limit = harness.limits("granite_h_decode")["logit_gap"]
    assert gap <= limit < ctl, (gap, limit, ctl)
