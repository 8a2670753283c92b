"""Tiny widths and traffic for running the benchmark's cells on the CPU,
with the Pallas kernels in interpret mode."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run as bench_run  # noqa: E402
from bench.lib import harness, registry  # noqa: E402

TRAFFIC = {
    "closed": dict(clients=4, requests_per_client=4,
                   prompt={"median": 4, "sigma": 0.5, "lo": 2, "hi": 8},
                   output={"median": 8, "sigma": 0.5, "lo": 4, "hi": 16},
                   slots=2, max_len=64, chunk=4, ramp_chunks=2,
                   check_requests=3),
    "offline_batches": dict(batch=4, pool_batches=3,
                            frames={"median": 12, "sigma": 0.5, "lo": 5,
                                    "hi": 30},
                            max_bucket=32, check_batches=2),
}


def config(bench, name):
    cfg = registry.config(bench, name)
    m = cfg["model"]
    if m["vocab_size"]:
        m.update(input_size=32, hidden=32, vocab_size=64)
    else:
        m.update(input_size=12, hidden=32, num_classes=5)
    return cfg


def run(workload, *, seed=2 ** 31 + 11, seconds=1.0, trace=False,
        control=False):
    """One run of ``workload`` at tiny size on the CPU → (result, Run)."""
    bench = registry.benchmark()
    cell = registry.cell(bench, workload)
    traffic = registry.traffic(cell["traffic"])
    traffic.update(TRAFFIC[traffic["driver"]])
    rc, result, r = bench_run.run_cell(
        bench, cell, config(bench, cell["config"]), traffic, seed=seed,
        seconds=seconds, trace=trace, t_start=time.perf_counter(),
        limits=harness.limits(workload), require_tpu=False, control=control)
    assert rc == 0
    return result, r
