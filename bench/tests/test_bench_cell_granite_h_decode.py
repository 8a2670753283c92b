"""The granite_h_decode cell end to end at tiny width on the CPU: a sound
run is correct, and a run with the timed path broken underneath is not.
The cell's configuration keeps its period of ten layers; its widths and
traffic are cut here, so the shared tiny settings are left alone."""
import time

import pytest

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench import run as bench_run
from bench.lib import harness, registry

CELL = "granite_h_decode"
WIDTHS = dict(hidden_size=64, intermediate_size=128, vocab_size=256,
              num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
              mamba_d_head=16, mamba_d_state=16)
TRAFFIC = dict(clients=4, requests_per_client=4,
               prompt={"median": 6, "sigma": 0.3, "lo": 5, "hi": 8},
               output={"median": 10, "sigma": 0.3, "lo": 6, "hi": 14},
               slots=2, max_len=32, chunk=1, ramp_chunks=2,
               check_requests=3)


def run_tiny(seed=2 ** 31 + 15):
    bench = registry.benchmark()
    cell = registry.cell(bench, CELL)
    cfg = registry.config(bench, cell["config"])
    cfg.update(WIDTHS)
    traffic = registry.traffic(cell["traffic"])
    traffic.update(TRAFFIC)
    rc, result, run = bench_run.run_cell(
        bench, cell, cfg, traffic, seed=seed, seconds=1.0, trace=False,
        t_start=time.perf_counter(), limits=harness.limits(CELL),
        require_tpu=False)
    assert rc == 0
    return result, run


def test_sound_run_is_correct():
    result, run = run_tiny()
    assert result["correct"] is True, result["checks"]
    assert run.facts["checked"]["requests"] >= 2
    assert result["checks"]["short_requests"]["value"] == 0
    assert sum(run.compiles_in_window.values()) == 0
    assert run.facts["gather_visit_share"]


def _state_unchanged(monkeypatch):
    from repro.models.transformer import TransformerLM
    step = TransformerLM.decode_step

    def frozen(self, params, cache, tokens, pos):
        logits, _ = step(self, params, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(TransformerLM, "decode_step", frozen)


def _token_altered(monkeypatch):
    from repro.serving import runtime
    sample = runtime.sample

    def shifted(key, logits, cfg):
        tok = sample(key, logits, cfg)
        return (tok + 1) % logits.shape[-1]
    monkeypatch.setattr(runtime, "sample", shifted)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, _ = run_tiny()
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
