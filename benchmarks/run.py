"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows AND, per module, writes a
machine-readable ``BENCH_<module>.json`` record (wall time, the parsed
per-row fields — tok/s, effective-ops reductions, byte ratios) so the
perf trajectory can be diffed across PRs. ``REPRO_BENCH_DIR`` overrides
the output directory (default: the current working directory).
"""
import json
import os
import sys
import time

from . import common


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import decode_throughput, fig4_dual_ratio, fig9_patterns, \
        fig_delta_occupancy, fig_quant_tradeoff, obs, pipeline, spec, \
        table1_resources, table2_throughput, traffic
    out_dir = os.environ.get("REPRO_BENCH_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    for mod in (table1_resources, table2_throughput, decode_throughput,
                fig9_patterns, fig4_dual_ratio, fig_delta_occupancy,
                fig_quant_tradeoff, traffic, pipeline, spec, obs):
        common.drain_records()
        t0 = time.time()
        mod.main()
        wall = time.time() - t0
        name = mod.__name__.rsplit(".", 1)[-1]
        payload = {"benchmark": name, "smoke": common.SMOKE,
                   "wall_time_s": round(wall, 3),
                   "rows": common.drain_records()}
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
