"""Closed loop over the continuous-batching scheduler, serving the hybrid
Mamba-2 + attention model (``bench/lib/hybrid_lm.py``).

``clients`` users each send their next request the moment the last one
finished. Each client has its own list of requests, cycled if it runs
out: their lengths and their order are the same for every seed, and
their token ids are drawn from the seed. The first wave starts with part
of each budget already spent (stratified fractions), and the scheduler
runs until ``ramp_chunks`` decode chunks went out before the window
opens, so the window sees requests at every stage of their lives.

Traffic keys: clients, requests_per_client, prompt and output (log-normal
length specs), slots, max_len, chunk, dispatch_depth, prefill_batch,
ramp_chunks, check_requests, trace_seconds.
"""
from __future__ import annotations

import gc
import math

import numpy as np

from bench.lib import gen, harness, hybrid_lm, stats


def run(run) -> dict:
    tr, cfg = run.traffic, run.cfg
    n_clients, per = tr["clients"], tr["requests_per_client"]
    sizes = gen.schedule_rng(1)
    plens = gen.lognormal(n_clients * per, tr["prompt"], sizes)
    outs = gen.lognormal(n_clients * per, tr["output"], sizes)
    spent = gen.fractions(n_clients, sizes)
    toks = gen.prompts(plens, cfg["vocab_size"], gen.rng(run.seed, 1))

    log = stats.Log(run.now)
    t = run.now()
    dense, model, packed, report = hybrid_lm.prepared(
        cfg, run.seed, batch=tr["slots"], max_len=tr["max_len"])
    run.facts["gather_visit_share"] = report["gather_visit_share"]
    t_prep = run.now()
    sched = hybrid_lm.scheduler(model, packed, tr, log.on_token)
    hybrid_lm.warm(sched, plens, tr)
    harness.log(f"set-up: weights and prepare {t_prep - t:.1f} s, "
                f"warm-up {run.now() - t_prep:.1f} s")

    prompts: dict[int, np.ndarray] = {}
    owner: dict[int, int] = {}
    sent = [0] * n_clients

    def send(c: int, budget_share: float = 1.0):
        i = c * per + sent[c] % per
        sent[c] += 1
        budget = max(1, math.ceil(int(outs[i]) * budget_share))
        uid = sched.submit(toks[i], budget)
        log.add(uid, len(toks[i]), budget, run.now())
        prompts[uid], owner[uid] = toks[i], c

    def step():
        with run.annotate("sched.step"):
            fins = sched.step()
        for fin in fins:
            log.on_finished(fin)
            if fin.uid in owner:
                send(owner[fin.uid])

    for c in range(n_clients):
        send(c, float(spent[c]))
    ramp = sched.steps_dispatched + tr["ramp_chunks"]
    while sched.steps_dispatched < ramp:
        step()

    run.open_window()
    while True:
        now = run.now()
        run.poll(now)
        if now >= run.t_close:
            break
        step()
    run.close_window()
    t0, t1 = run.t_open, run.t_close
    recs = list(log.records.values())

    run.facts.update(hybrid_lm.trace_facts(run, recs, tr))

    del sched, packed, model
    gc.collect()
    hybrid_lm.check(run, dense, recs, prompts)
    active = stats.active_in(recs, t0, t1)
    return {
        "attempted": len(active),
        "failed": sum(r.reason not in ("", "done") for r in active),
        "metrics": {
            "out_tokens_per_s": stats.tokens_in(recs, t0, t1) / (t1 - t0),
            "tpot_p90_ms": 1e3 * stats.percentile(
                stats.token_gaps(recs, t0, t1), 90),
        },
    }
