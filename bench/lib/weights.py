"""Dense float32 LSTM weights made on the device from the seed.

One jitted call makes every leaf in the layout the program's
``LSTMModel`` declares: per layer ``w_x`` (4H, X_in), ``w_h`` (4H, H),
``b`` (4H,), gate rows grouped [f; i; g; o]; an embedding table and a
head for a language model, a head for a classifier. ``w_x`` and ``w_h``
are already row-balanced sparse at the configuration's two ratios: each
row has exactly the kept count of non-zeros, at positions drawn from the
seed, so pruning them by magnitude keeps exactly these entries and the
reference and the program serve the same model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .cost import keep_count


def _row_balanced(key, shape, k):
    """Exactly k non-zeros per row, at the positions of the row's k
    largest uniform draws (by index, so a tie cannot add a k+1-th)."""
    ku, kv = jax.random.split(key)
    idx = jax.lax.top_k(jax.random.uniform(ku, shape), k)[1]
    keep = jnp.zeros(shape, bool).at[
        jnp.arange(shape[0])[:, None], idx].set(True)
    vals = jax.random.normal(kv, shape, jnp.float32) / jnp.sqrt(float(k))
    return jnp.where(keep, vals, 0.0)


def _make(key, *, X, H, L, V, C, spar_x, spar_h):
    keys = iter(jax.random.split(key, 3 * L + 2))
    layers = []
    for i in range(L):
        x_in = X if i == 0 else H
        layers.append({
            "w_x": _row_balanced(next(keys), (4 * H, x_in),
                                 keep_count(x_in, spar_x)),
            "w_h": _row_balanced(next(keys), (4 * H, H),
                                 keep_count(H, spar_h)),
            "b": 0.1 * jax.random.normal(next(keys), (4 * H,), jnp.float32),
        })
    params = {"layers": layers}
    k_emb, k_head = next(keys), next(keys)
    out = V or C
    if V:
        params["embed"] = {"table": jax.random.normal(k_emb, (V, X),
                                                      jnp.float32)}
    params["head"] = {"w": jax.random.normal(k_head, (H, out), jnp.float32)
                      / jnp.sqrt(float(H))}
    return params


def make_params(cfg: dict, seed: int):
    m, sp = cfg["model"], cfg["sparsity"]
    fn = jax.jit(functools.partial(
        _make, X=m["input_size"], H=m["hidden"], L=m["num_layers"],
        V=m.get("vocab_size", 0), C=m.get("num_classes", 0),
        spar_x=sp["spar_x"], spar_h=sp["spar_h"]))
    return fn(jax.random.key(seed))
