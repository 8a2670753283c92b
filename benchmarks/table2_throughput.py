"""Table 2 analogue: dense vs BRDS-sparse LSTM inference step.

Measures wall time per step on this host (CPU; jit'd dense einsum vs jit'd
packed gather path — the kernels' ref formulations, since Pallas interpret
mode measures Python, not hardware), and derives the TPU-v5e roofline-model
step times + effective-throughput ratio = 1/(1-sparsity) that the paper's
headline numbers (GOPS, effective GOPS) correspond to."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LSTMModel, LSTMConfig
from repro import hw
from .common import time_call, row, smoke


def main():
    # paper's TIMIT configuration (hidden shrunk under the CI smoke run)
    cfg = LSTMConfig("timit", input_size=153, hidden=smoke(128, 1024),
                     num_classes=61, framewise=True)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    OS = 0.875
    pruned, masks = model.prune(params, OS, OS)
    packed = model.pack(pruned, masks)
    B = 1
    x = jnp.asarray(np.random.default_rng(0).normal(size=(B, 153)),
                    jnp.float32)
    st = model.init_state(B)

    dense_fn = jax.jit(lambda xx, ss: model.dense_step(pruned, xx, ss))
    sparse_fn = jax.jit(
        lambda xx, ss: model.sparse_step(packed, xx, ss, backend="ref"))
    us_dense = time_call(dense_fn, x, st)
    us_sparse = time_call(sparse_fn, x, st)

    H, X = cfg.hidden, 153
    ops = 2 * 4 * H * (X + H)                       # dense MACs per step
    x_sp, h_sp = packed[0]["sx"].K, packed[0]["sh"].K
    ops_sp = 2 * 4 * H * (x_sp + h_sp)
    row("table2_cpu_dense_step", us_dense, f"GOPS={ops/us_dense/1e3:.2f}")
    row("table2_cpu_sparse_step", us_sparse,
        f"GOPS={ops_sp/us_sparse/1e3:.2f} "
        f"effGOPS={ops/us_sparse/1e3:.2f} speedup={us_dense/us_sparse:.2f}x")

    # TPU v5e roofline model (decode MxV is HBM-bound):
    bytes_dense = (4 * H * (X + H)) * 2             # bf16 weights
    bytes_sparse = sum(s.memory_bytes()["values"] // 2  # →16-bit values
                       + s.memory_bytes()["indices"]
                       for s in (packed[0]["sx"], packed[0]["sh"]))
    bw = hw.peaks(hw.TARGET_KIND).hbm_bytes_per_s
    t_dense = bytes_dense / bw
    t_sparse = bytes_sparse / bw
    row("table2_v5e_model_dense", t_dense * 1e6,
        f"bytes={bytes_dense} effGOPS={ops/t_dense/1e9:.0f}")
    row("table2_v5e_model_sparse", t_sparse * 1e6,
        f"bytes={bytes_sparse} effGOPS={ops/t_sparse/1e9:.0f} "
        f"speedup={t_dense/t_sparse:.2f}x "
        f"(paper effective-throughput factor 1/(1-s)={1/(1-OS):.1f}x)")


if __name__ == "__main__":
    main()
