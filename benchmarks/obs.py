"""obs: instrumentation cost and counter fidelity of repro.obs.

Observability only earns its place if it is (a) free when off, (b) cheap
when on, and (c) EXACT — the harvested on-device counters must agree
with the offline reductions the repo already trusts. Three row families
pin all three:

  obs_overhead_disabled /    closed-loop scheduler throughput on packed
  obs_overhead_enabled       delta-gated weights with counters off vs on
                             (same prompts, same instance-warmed jits);
                             the enabled row carries ``overhead_pct`` —
                             the acceptance target is ≤ 5%.
  obs_counter_parity         fired_match: harvested fired-column gauges
                             == the drained cache's nx/nh sums (and the
                             scorecard's fired-weighted MACs ==
                             ``occupancy_report``'s). spec_match: spec
                             counters == ``spec_stats()``. Both exact.
  obs_scorecard              the effective-GOPS scorecard joined from the
                             enabled run: achieved/effective GOPS vs the
                             memory-roofline bound, bytes/token.
"""
import math
import time

import jax
import numpy as np

from repro.models import LSTMModel
from repro.obs import counters as OC
from repro.obs import scorecard as OS
from repro.serving import ContinuousBatchingEngine, SamplingConfig, \
    ServeEngine
from repro.sparse import (DeltaGateConfig, lstm_policy, occupancy_report,
                          use_backend)
from repro.spec import DraftModel
from .common import bench_lstm_cfg, smoke, row

SLOTS = smoke(4, 8)
GEN = smoke(8, 24)
CHUNK = 8
REPS = smoke(2, 5)
GREEDY = SamplingConfig(eos_id=-1)    # fixed token count per run
MAX_LEN = smoke(48, 96)


def _submit(sched, cfg, rng):
    lens = [max(4, MAX_LEN // 4 - 3 * i) for i in range(SLOTS)]
    for i, plen in enumerate(lens):
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (1, plen),
                                    0, cfg.vocab_size)
        sched.submit(prompt, GEN)


def _serve(sched, cfg):
    _submit(sched, cfg, jax.random.key(1))
    t0 = time.perf_counter()
    results = sched.run()
    dt = time.perf_counter() - t0
    return dt, sum(len(v) for v in results.values())


def main():
    cfg = bench_lstm_cfg()
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    pol = lstm_policy(0.875, 0.75, backend="ref",
                      delta=DeltaGateConfig(theta_x=0.1, theta_h=0.1))
    eng = ServeEngine(model, cfg, max_len=MAX_LEN, batch=SLOTS,
                      sparsity=pol)
    packed, _ = eng.prepare(params)

    with use_backend("ref"):
        # ---- enabled-vs-disabled overhead (per-instance warmed jits) --
        walls, scheds = {}, {}
        for label, flag in (("disabled", False), ("enabled", True)):
            sched = ContinuousBatchingEngine(
                eng.model, packed, slots=SLOTS, max_len=MAX_LEN,
                sampling=GREEDY, chunk=CHUNK, counters=flag)
            _serve(sched, cfg)                      # compile warmup
            ts = []
            for _ in range(REPS):
                dt, tokens = _serve(sched, cfg)
                ts.append(dt)
            ts.sort()
            walls[label] = (ts[len(ts) // 2], tokens)
            scheds[label] = sched
        dis, en = walls["disabled"], walls["enabled"]
        row("obs_overhead_disabled", dis[0] / dis[1] * 1e6,
            f"toks_per_s={dis[1] / dis[0]:.1f} tokens={dis[1]}")
        overhead = (en[0] - dis[0]) / dis[0] * 100.0
        row("obs_overhead_enabled", en[0] / en[1] * 1e6,
            f"toks_per_s={en[1] / en[0]:.1f} overhead_pct={overhead:.2f} "
            f"target_pct=5")

        # ---- exact parity: counters vs the offline reductions ---------
        sched = scheds["enabled"]
        c = sched.counters()
        fired_ok = all(
            c[f"fired_x_l{i}"] == float(np.asarray(lp["nx"]).sum())
            and c[f"fired_h_l{i}"] == float(np.asarray(lp["nh"]).sum())
            for i, lp in enumerate(sched.cache["layers"]))
        occ = occupancy_report(sched.cache, steps=sched.slot_steps,
                               packed=packed)
        card = OS.build(packed, c, en[0], batch=SLOTS,
                        step_sum=float(np.sum(sched.slot_steps)))
        fired_ok &= math.isclose(card["executed_macs"],
                                 occ["effective_macs"], rel_tol=1e-9)

        draft = DraftModel(model, params)           # target drafts itself
        ssched = ContinuousBatchingEngine(
            model, params, slots=SLOTS, max_len=MAX_LEN, sampling=GREEDY,
            chunk=CHUNK, draft=draft, spec_k=3, counters=True)
        _serve(ssched, cfg)
        st = ssched.spec_stats()
        sc = ssched.counters()
        spec_ok = (sc["spec_rounds"] == st["rounds"]
                   and sc["spec_drafted"] == st["drafted"]
                   and sc["spec_accepted"] == st["accepted"]
                   and st["drafted"] > 0)
        row("obs_counter_parity", 0.0,
            f"fired_match={int(fired_ok)} spec_match={int(spec_ok)} "
            f"occupancy_x={occ['occupancy_x']:.4f}")

        # ---- the scorecard itself, from the enabled run's harvest -----
        bound = (f"bound_effective_gops={card['bound_effective_gops']:.1f}"
                 f" roofline_gap={card['roofline_gap']:.1f}x"
                 if "roofline_gap" in card else
                 f"roofline=none({card['device_kind']})")
        row("obs_scorecard", en[0] / en[1] * 1e6,
            f"effective_gops={card['effective_gops']:.4f} {bound} "
            f"bytes_per_token={card['bytes_per_token']}")


if __name__ == "__main__":
    main()
