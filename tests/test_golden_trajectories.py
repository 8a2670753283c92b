"""Golden-trajectory regression tests: checked-in greedy token
trajectories for a fixed-seed tiny LSTM-LM across the five deployment
variants (dense, packed chained, packed fused, Θ=0 delta, calibrated q8).

The pairwise bitwise parities elsewhere in the suite prove variants agree
WITH EACH OTHER — these goldens pin the absolute numerics, so silent
drift from a kernel edit or an XLA/jax version bump fails loudly even if
every variant drifts in lockstep. The checked-in seed was selected so
every greedy argmax margin exceeds ~7.2e-3 (recorded in the JSON) —
orders of magnitude above cross-platform ulp noise, so a token mismatch
means real numeric change, not reassociation jitter. Regenerate the JSON
only for an INTENTIONAL numeric change, and say why in the commit. (The
last regeneration followed jax's switch to the partitionable threefry
random stream, which changed the seeded weights and prompts — not the
numerics.)
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.models import LSTMConfig, LSTMModel
from repro.serving import ServeEngine
from repro.sparse import DeltaGateConfig, QuantConfig, lstm_policy

GOLDEN = os.path.join(os.path.dirname(__file__), "golden_trajectories.json")

with open(GOLDEN) as f:
    G = json.load(f)

SX, SH = G["spar"]


def _variant(name):
    if name == "dense":
        return False, None, False
    if name == "packed_chained":
        return False, lstm_policy(SX, SH), False
    if name == "packed_fused":
        return True, lstm_policy(SX, SH), False
    if name == "delta_theta0":
        return False, lstm_policy(
            SX, SH, delta=DeltaGateConfig(theta_x=0.0, theta_h=0.0)), False
    if name == "calibrated_q8":
        return False, lstm_policy(SX, SH, quant=QuantConfig("int8")), True
    raise KeyError(name)


def _fixtures():
    cfg = LSTMConfig(f"golden{G['seed']}", **G["model"])
    params = LSTMModel(cfg).init(jax.random.key(G["seed"]))
    prompt = jax.random.randint(jax.random.key(G["seed"] + 1000),
                                (G["batch"], G["prompt_len"]), 0,
                                G["model"]["vocab_size"])
    calib = jax.random.randint(jax.random.key(G["seed"] + 2000), (2, 8),
                               0, G["model"]["vocab_size"])
    return cfg, params, prompt, calib


@pytest.mark.parametrize("name", sorted(G["trajectories"]))
def test_golden_trajectory(name):
    cfg, params, prompt, calib = _fixtures()
    fused, policy, needs_calib = _variant(name)
    eng = ServeEngine(LSTMModel(cfg, fused=fused), cfg,
                      max_len=G["prompt_len"] + G["steps"],
                      batch=G["batch"], sparsity=policy)
    p = params
    if policy is not None:
        p, _ = eng.prepare(params, calib=calib if needs_calib else None)
    toks = np.asarray(eng.generate(p, prompt, G["steps"]))
    expect = np.asarray(G["trajectories"][name], np.int32)
    np.testing.assert_array_equal(
        toks, expect,
        err_msg=f"{name}: greedy trajectory drifted from the golden — "
                "a kernel/XLA numeric change; regenerate the golden only "
                "if the change is intentional")


def test_goldens_cover_all_variants():
    assert set(G["trajectories"]) == {"dense", "packed_chained",
                                      "packed_fused", "delta_theta0",
                                      "calibrated_q8"}
    # the established bitwise parities must hold inside the goldens too
    assert (G["trajectories"]["packed_chained"]
            == G["trajectories"]["packed_fused"]
            == G["trajectories"]["delta_theta0"])
    assert G["min_argmax_margin"] > 1e-3
