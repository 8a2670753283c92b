"""Serving example: BRDS-sparse decode through the unified runtime — the
paper's deployment scenario (inference on the pruned network).

Three stages:
1. The paper's LSTM served END-TO-END on the packed row-balanced kernels:
   SparsityPlan.pack'd params flow through ServeEngine's on-device decode
   loop, so every generated token runs rb_dual_spmv + lstm_gates.
2. A transformer served dense vs masked-sparse through the same engine
   (transformers keep dense matmul serving; packing is the LSTM datapath).
3. A ragged request stream through the continuous-batching scheduler.

Prints the memory-traffic model that drives the TPU speedup (decode is
HBM-bound; packed weights move (1-sparsity) of the bytes — the paper's
effective-throughput argument).

  PYTHONPATH=src python examples/serve_sparse_decode.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.models import build_model, LSTMModel, LSTMConfig
from repro.sparse import lstm_policy, transformer_policy, use_backend
from repro.serving import (ServeEngine, ContinuousBatchingEngine,
                          SamplingConfig)
from repro import hw


def serve_packed_lstm():
    """The headline path: BRDS-pruned LSTM decoding on packed kernels."""
    cfg = LSTMConfig("lstm_demo", input_size=128, hidden=256, vocab_size=512)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    B, P, G = 4, 16, 24
    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)

    eng = ServeEngine(model, cfg, max_len=P + G, batch=B,
                      sparsity=lstm_policy(0.875, 0.75))
    packed, rep = eng.prepare(params)       # prune AND pack (LSTM decodes packed)
    with use_backend("ref"):                # jnp formulation of the kernels on CPU
        t0 = time.time()
        out = eng.generate(packed, prompt, steps=G)
        out.block_until_ready()
        dt = time.time() - t0
    print(f"packed LSTM decode: {B * G / dt:.0f} tok/s, "
          f"weights {rep['ratio']:.1%} of dense bytes "
          f"(sparsity {rep['sparsity']:.1%})")


def serve_transformer():
    cfg = smoke_config("minitron-8b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, P, G = 4, 32, 16
    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)

    # dual-ratio policy: family A (feed-forward) 87.5%, family B (mixers) 75%
    eng = ServeEngine(model, cfg, max_len=P + G, batch=B,
                      sparsity=transformer_policy(0.875, 0.75))
    t0 = time.time()
    eng.generate(params, prompt, steps=G).block_until_ready()
    t_dense = time.time() - t0

    sparse_params, rep = eng.prepare(params)
    t0 = time.time()
    eng.generate(sparse_params, prompt, steps=G).block_until_ready()
    t_sparse = time.time() - t0
    print(f"dense decode: {t_dense:.2f}s; sparse decode (masked): "
          f"{t_sparse:.2f}s; model sparsity {rep['sparsity']:.1%}")

    # TPU v5e traffic model for the FULL minitron-8b (decode, per token):
    from repro.configs import get_arch
    full = get_arch("minitron-8b")
    n = build_model(full).param_count()
    dense_bytes = n * 2
    packed_bytes = n * (1 - rep["sparsity"]) * 2 \
        + n * (1 - rep["sparsity"]) * 1          # values + int8 deltas
    bw = hw.peaks(hw.TARGET_KIND).hbm_bytes_per_s
    print(f"v5e per-token weight traffic: dense {dense_bytes/1e9:.1f} GB "
          f"({dense_bytes/bw*1e3:.2f} ms), packed "
          f"{packed_bytes/1e9:.1f} GB ({packed_bytes/bw*1e3:.2f} ms) "
          f"→ {dense_bytes/packed_bytes:.1f}x decode speedup headroom")
    return model, cfg, params


def serve_continuous(model, cfg, params):
    """Ragged request stream: admission/eviction over 2 shared slots."""
    sched = ContinuousBatchingEngine(model, params, slots=2, max_len=48,
                                     sampling=SamplingConfig(), chunk=8)
    for i, (plen, gen) in enumerate([(4, 12), (20, 6), (9, 16), (14, 4)]):
        prompt = jax.random.randint(jax.random.key(10 + i), (1, plen), 0,
                                    cfg.vocab_size)
        sched.submit(prompt, gen)
    t0 = time.time()
    results = sched.run()
    dt = time.time() - t0
    total = sum(len(v) for v in results.values())
    print(f"continuous batching: {len(results)} ragged requests, "
          f"{total} tokens in {dt:.2f}s over 2 slots "
          f"({sched.steps_dispatched} chunk dispatches)")


def main():
    serve_packed_lstm()
    model, cfg, params = serve_transformer()
    serve_continuous(model, cfg, params)


if __name__ == "__main__":
    main()
