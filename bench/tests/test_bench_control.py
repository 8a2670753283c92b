"""The control: the plain reference, put in the program's place and run in
bfloat16 (the next precision below the configurations' float32), reads
above each cell's limit. At a width the CPU holds in a test: 256 hidden
units, a 4000-word vocabulary, 1024 served positions."""
import jax
import jax.numpy as jnp
import numpy as np

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench.lib import harness, weights
from bench.reference import lstm as ref

LM = {"name": "ctl_lm", "model": {"input_size": 256, "hidden": 256,
                                  "num_layers": 2, "vocab_size": 4000},
      "sparsity": {"spar_x": 0.75, "spar_h": 0.5}}
FW = {"name": "ctl_fw", "model": {"input_size": 153, "hidden": 256,
                                  "num_layers": 1, "num_classes": 61},
      "sparsity": {"spar_x": 0.75, "spar_h": 0.5}}


def test_bf16_control_fails_the_lm_limit():
    params = weights.make_params(LM, 3)
    g = np.random.default_rng(3)
    N, T, P = 4, 256, 8
    tokens = g.integers(0, 4000, size=(N, T)).astype(np.int32)
    # served tokens: the float32 reference's own pick at each position
    hs = ref.lm_hidden(params, jnp.asarray(tokens), jnp.full((N,), T))
    targets = np.asarray(jnp.argmax(ref.head(params, hs), axis=-1))
    valid = np.zeros((N, T), bool)
    valid[:, P - 1:] = True
    gap, ctl = jax.jit(ref.served_gaps, static_argnames=("control",))(
        params, jnp.asarray(tokens), jnp.full((N,), T), jnp.asarray(targets),
        jnp.asarray(valid), control=True)
    gap, ctl = float(jnp.max(gap)), float(jnp.max(ctl))
    limit = harness.limits("ptb_decode")["logit_gap"]
    assert gap <= limit < ctl, (gap, limit, ctl)


def test_bf16_control_fails_the_framewise_limit():
    params = weights.make_params(FW, 4)
    N, T = 8, 300
    xs = jax.random.normal(jax.random.key(4), (N, T, 153))
    lengths = jnp.asarray(np.linspace(90, T, N).astype(np.int32))
    finals, logits = ref.final_states(params, xs, lengths)
    low, low_logits = ref.final_states(params, xs, lengths, jnp.bfloat16)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
              for a, b in zip([x for f in low for x in f] + [low_logits],
                              [x for f in finals for x in f] + [logits]))
    assert err > harness.limits("timit_batch")["state_err"]
