"""Readings that a cell's limits are set from, over many seeds in one
process.

    python bench/calibrate.py --workload <name> --seeds 101-112 --seconds 10

Runs the cell once per seed as ``bench/run.py`` would (the same drivers,
sizes and traffic, a window of ``--seconds``), and on the same served
outputs also reads the control: the plain reference in bfloat16, the
precision below the configuration's float32. Prints one JSON line per
seed and a summary: for each compared number the largest program reading
(the lower end of its limit) and the smallest control reading (the upper
end), and on how many seeds the program and the control, each judged by
the cell's limits, came out correct. The benchmark's own runs never read
the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402
from bench.lib import harness, registry  # noqa: E402


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,5000")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    if bench_run.device_info(True, cell["chips"]) is None:
        return 2
    bench_run.enable_cache()
    rows = []
    for seed in seeds(args.seeds):
        t = time.perf_counter()
        rc, result, run = bench_run.run_cell(
            bench, cell, cfg, traffic, seed=seed, seconds=args.seconds,
            trace=False, t_start=t, limits=harness.limits(cell["name"]),
            control=True)
        if result is None:
            return rc
        row = {"seed": seed, "correct": result["correct"],
               "program": {k: c["value"] for k, c in
                           result["checks"].items()},
               "control": run.control_readings,
               # the control in the program's place, judged as a run is
               "control_correct": all(v <= run.limits[k] for k, v in
                                      run.control_readings.items()),
               "metrics": {k: m["value"] for k, m in
                           result["metrics"].items()},
               "facts": run.facts.get("checked"),
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["program"]:
        summary[name] = {
            "lower": max(r["program"][name] for r in rows),
            "upper": min((r["control"][name] for r in rows
                          if name in r["control"]), default=None),
            "seeds": len(rows)}
    print(json.dumps({"workload": cell["name"], "summary": summary,
                      "program_correct": sum(r["correct"] for r in rows),
                      "control_correct": sum(r["control_correct"]
                                             for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
