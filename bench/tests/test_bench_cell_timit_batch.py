"""The timit_batch cell end to end at tiny width (interpret mode): a sound
run is correct, and a run with the timed path broken underneath is not."""
import jax.numpy as jnp
import pytest

import bench_tiny


def test_sound_run_is_correct():
    result, run = bench_tiny.run("timit_batch")
    assert result["correct"] is True
    assert run.facts["checked"]["batches"] >= 2
    assert result["checks"]["state_err"]["value"] < 1e-5
    assert sum(run.compiles_in_window.values()) == 0


def _patch_prefill(monkeypatch, change):
    from repro.models.lstm import LSTMModel
    prefill = LSTMModel.prefill

    def broken(self, params, tokens, max_len, extra=None, length=None):
        logits, cache = prefill(self, params, tokens, max_len, extra, length)
        return change(self, logits, cache, tokens)
    monkeypatch.setattr(LSTMModel, "prefill", broken)


def _state_unchanged(self, logits, cache, tokens):
    zero = self.init_cache(tokens.shape[0], 1)
    return logits, zero


def _half_batch(self, logits, cache, tokens):
    half = tokens.shape[0] // 2
    fill = lambda x: jnp.concatenate([x[:half], x[:x.shape[0] - half]])
    return fill(logits), {"layers": [{k: fill(v) for k, v in lp.items()}
                                     for lp in cache["layers"]]}


def _answer_altered(self, logits, cache, tokens):
    return logits.at[0, 0, 0].add(1.0), cache


@pytest.mark.parametrize("change", [_state_unchanged, _half_batch,
                                    _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_broken_path_is_not_correct(monkeypatch, change):
    _patch_prefill(monkeypatch, change)
    result, _ = bench_tiny.run("timit_batch")
    assert result["correct"] is False
