"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``; the traffic's driver makes the inputs and the system
under test from the seed, warms every shape the traffic uses, measures
for ``--seconds``, then checks what the timed path produced against the
plain reference. ``--trace 1`` profiles the first part of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), with ``checks`` (each compared number and its limit) last.
The same checks end standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.lib import harness, peaks, registry, xtrace  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def enable_cache():
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` in this checkout; every program is
    cached, however quickly it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(require_tpu: bool, chips: int):
    """(platform, kind, count) of the devices, or None where the cell
    cannot run here."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        harness.log(f"no TPU: JAX found platform {devs[0].platform!r}; "
                    "refusing to run")
        return None
    if require_tpu:
        peaks.peaks(devs[0].device_kind)    # an unknown chip is an error
    if len(devs) < chips:
        harness.log(f"the cell asks for {chips} chips, JAX found "
                    f"{len(devs)}; refusing to run")
        return None
    return devs[0].platform, devs[0].device_kind, len(devs)


def layer_metrics(run, bench, cell, kind):
    """Per-layer readings of a traced run, and its breakdown."""
    path = run.trace_file()
    reduced = xtrace.reduce(xtrace.read(path)) if path else None
    ctx = harness.MetricContext(run, reduced, peaks.PEAKS.get(kind))
    values = {}
    for m in registry.per_layer(bench, cell["name"]):
        v = registry.metric_reader(m["name"]).read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        breakdown = {"device_ops": reduced.top_ops(10),
                     "idle_gaps": reduced.top_gaps(10)}
    return values, reduced, breakdown


def run_cell(bench, cell, cfg, traffic, *, seed, seconds, trace, t_start,
             limits, require_tpu=True, control=False):
    """One run of one cell → (exit code, result dict or None, Run)."""
    info = device_info(require_tpu, cell["chips"])
    if info is None:
        return 2, None, None
    platform, kind, count = info
    run = harness.Run(workload=cell["name"], cfg=cfg, traffic=traffic,
                      seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, limits=limits)
    run.control = control
    try:
        out = registry.driver(traffic["driver"]).run(run)
        device = {"platform": platform, "kind": kind, "count": count,
                  "memory_peak_bytes": int(run.memory_peak)}
        breakdown = None
        if trace:
            metrics, reduced, breakdown = layer_metrics(run, bench, cell,
                                                        kind)
            if reduced is not None:
                device["busy_s"] = reduced.busy_s
                device["window_s"] = reduced.window_s
        else:
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            metrics = {name: {"value": float(v), "unit": units[name]}
                       for name, v in out["metrics"].items()
                       if name in units and math.isfinite(v)}
            metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}
    finally:
        run.cleanup()
    correct = bool(run.checks) and all(v <= lim for _, v, lim in run.checks)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in run.checks}
    return 0, result, run


def main(argv=None) -> int:
    t_start = harness.process_start()
    args = parse(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if device_info(True, cell["chips"]) is None:
        return 2
    enable_cache()
    rc, result, run = run_cell(
        bench, cell, cfg, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=t_start,
        limits=harness.limits(cell["name"]))
    if result is None:
        return rc
    harness.log(f"setup_s={run.setup_s:.3f} compiles_in_window="
                f"{sum(run.compiles_in_window.values())} "
                f"{run.compiles_in_window} gc_in_window="
                f"{run.gc_in_window} longest_poll_gap_s="
                f"{run.longest_gap:.4f}")
    harness.log(f"facts {json.dumps(run.facts, default=str)}")
    for name, c in result["checks"].items():
        harness.log(f"check {name} value={c['value']!r} "
                    f"limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
