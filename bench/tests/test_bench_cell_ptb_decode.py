"""The ptb_decode cell end to end at tiny width (interpret mode): a sound run
is correct, and a run with the timed path broken underneath is not."""
import pytest

import bench_tiny


def test_sound_run_is_correct():
    result, run = bench_tiny.run("ptb_decode")
    assert result["correct"] is True
    assert run.facts["checked"]["requests"] >= 2
    assert result["checks"]["logit_gap"]["value"] == 0.0
    assert sum(run.compiles_in_window.values()) == 0


def _state_unchanged(monkeypatch):
    from repro.models.lstm import LSTMModel
    step = LSTMModel.decode_step

    def frozen(self, params, cache, tokens, pos):
        logits, _ = step(self, params, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(LSTMModel, "decode_step", frozen)


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
    result, _ = bench_tiny.run("ptb_decode")
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
