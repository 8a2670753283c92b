"""The result line: exactly the contract's keys, ``checks`` last; no
result at all without a TPU."""
import json

import bench_tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_no_tpu_no_result(capsys):
    rc = bench_tiny.bench_run.main(["--workload", "timit_batch", "--seed",
                                    "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_result_keys_and_order():
    result, run = bench_tiny.run("timit_batch")
    assert list(json.loads(json.dumps(result))) == KEYS
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    assert all(set(m) == {"value", "unit"}
               for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["checks"]["state_err"]["limit"] > 0
    assert run.setup_s > 0
