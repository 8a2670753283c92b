"""Everything is found by name: a configuration, a traffic mix and a
per-layer metric added as new files are picked up without an edit, and
BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
import shutil

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench.lib import harness, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_added_files_are_found_by_name(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(registry.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "lstm_tiny.json").write_text(json.dumps(
        {"name": "lstm_tiny", "model": {"input_size": 8, "hidden": 8,
                                        "num_layers": 1, "vocab_size": 16},
         "sparsity": {"spar_x": 0.5, "spar_h": 0.5}}))
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"driver": "closed", "clients": 128}))
    (root / "bench" / "layer_metrics" / "queue_wait.chat.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "lstm_tiny", "source": "x",
                             "file": "bench/configs/lstm_tiny.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny_burst", "config": "lstm_tiny",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "queue_wait.chat", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "scheduler",
                               "moves": "tpot_p90_ms",
                               "workloads": ["tiny_burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = registry.benchmark(root)
    cell = registry.cell(loaded, "tiny_burst")
    assert registry.config(loaded, cell["config"], root)["model"]["hidden"] \
        == 8
    tr = registry.traffic(cell["traffic"], root / "bench")
    assert registry.driver(tr["driver"], root / "bench").run is not None
    names = [m["name"] for m in registry.per_layer(loaded, "tiny_burst")]
    assert names == ["queue_wait.chat"]
    reader = registry.metric_reader("queue_wait.chat", root / "bench")
    assert reader.read(None) == 42.0


def test_benchmark_json_keeps_the_contract():
    bench = registry.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(configs) + list(cells) + list(e2e) + \
        [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (registry.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    assert {w["config"] for w in cells.values()} == set(configs)
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        tr = registry.traffic(w["traffic"])
        assert (registry.BENCH / "drivers" / f"{tr['driver']}.py").is_file()
        assert set(harness.limits(w["name"]))
        reported = [m["name"] for m in registry.end_to_end(bench, w["name"])]
        assert "setup_s" in reported and len(reported) >= 2
        assert registry.per_layer(bench, w["name"])
    for m in bench["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert (registry.BENCH / "layer_metrics" / f"{m['name']}.py") \
            .is_file()
        for w in m["workloads"]:
            assert m["moves"] in [x["name"] for x in
                                  registry.end_to_end(bench, w)]
