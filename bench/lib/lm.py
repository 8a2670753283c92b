"""The language-model serving path the ``ptb_*`` cells drive, and the
check of what it served.

Set-up: dense weights from the seed, ``ServeEngine.prepare`` under the
paper's dual-ratio policy (prune and pack), one
``ContinuousBatchingEngine`` on the packed weights, and a warm-up of
exactly the shapes the cell's traffic uses (each prompt bucket at one
request per prefill, the join, the decode chunk) on that same instance,
whose jitted programs are its own.

Check: once the window has closed and the scheduler is freed, a sample of
the requests it finished, drawn from the seed and holding the longest,
runs through the plain reference as prompt plus served tokens; the number
compared is the widest gap by which a served token's logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import numpy as np

from . import gen, stats, weights


def model_config(cfg: dict):
    from repro.models.lstm import LSTMConfig
    m = cfg["model"]
    return LSTMConfig(cfg["name"], input_size=m["input_size"],
                      hidden=m["hidden"], num_layers=m["num_layers"],
                      vocab_size=m.get("vocab_size", 0),
                      num_classes=m.get("num_classes", 0),
                      framewise=m.get("framewise", False))


def prepared(cfg: dict, seed: int, *, batch: int, max_len: int):
    """(dense weights, the engine's model, its packed weights)."""
    from repro.models import LSTMModel
    from repro.serving import ServeEngine
    from repro.sparse import lstm_policy
    dense = weights.make_params(cfg, seed)
    sp = cfg["sparsity"]
    eng = ServeEngine(LSTMModel(model_config(cfg)), None, max_len=max_len,
                      batch=batch,
                      sparsity=lstm_policy(sp["spar_x"], sp["spar_h"]))
    packed, _ = eng.prepare(dense)
    return dense, eng.model, packed


def scheduler(model, packed, tr: dict, on_token):
    from repro.serving import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        model, packed, slots=tr["slots"], max_len=tr["max_len"],
        chunk=tr["chunk"], dispatch_depth=tr["dispatch_depth"],
        prefill_batch=tr["prefill_batch"], on_token=on_token)


def warm(sched, prompt_lens, tr: dict):
    """Run one request per prompt bucket the traffic uses, to the end."""
    for w in sorted({gen.bucket(int(n), tr["max_len"] - 1)
                     for n in prompt_lens}):
        sched.submit(np.zeros(w, np.int32), tr["chunk"] + 1)
    sched.run()


def trace_facts(run, records, tr: dict) -> dict:
    """What the per-layer readers need of the traced window: tokens
    served, first tokens and their prompts, and the batch of each kind
    of program."""
    t0, t1 = run.trace_window()
    firsts = [r for r in records
              if r.first_token is not None and t0 <= r.first_token < t1]
    return dict(window_s=t1 - t0,
                out_tokens=stats.tokens_in(records, t0, t1),
                first_tokens=len(firsts),
                prompt_tokens=sum(r.prompt_len for r in firsts),
                slots=tr["slots"],
                batch={"prefill": tr["prefill_batch"],
                       "decode": tr["slots"]})


def sample(records, n: int, seed: int) -> list:
    """Up to n finished requests, drawn from the seed, the longest
    (most served tokens) among them."""
    done = [r for r in records if r.reason == "done" and r.tokens is not None
            and len(r.tokens)]
    if not done:
        return []
    done.sort(key=lambda r: r.uid)
    longest = max(done, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in done if r is not longest]
    g = gen.rng(seed, 7)
    pick = g.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gap(dense, chosen, prompts: dict, *, control: bool = False,
               block: int = 128):
    """(widest gap of a served token, widest gap of the token the
    bfloat16 control puts first) over the chosen requests."""
    import jax
    import jax.numpy as jnp
    from bench.reference import lstm as ref

    seqs, tgts = [], []
    for r in chosen:
        p, s = prompts[r.uid], np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([p, s[:-1]]))
        tgts.append(np.concatenate([np.zeros(len(p) - 1, np.int32), s]))
    T = max(len(x) for x in seqs)
    T += (-T) % block
    N = len(seqs)
    tokens = np.zeros((N, T), np.int32)
    targets = np.zeros((N, T), np.int32)
    valid = np.zeros((N, T), bool)
    for i, (x, t, r) in enumerate(zip(seqs, tgts, chosen)):
        tokens[i, :len(x)] = x
        targets[i, :len(t)] = t
        valid[i, len(prompts[r.uid]) - 1:len(t)] = True
    lengths = np.array([len(x) for x in seqs], np.int32)
    fn = jax.jit(ref.served_gaps, static_argnames=("control",))
    gap, ctl = fn(dense, jnp.asarray(tokens), jnp.asarray(lengths),
                  jnp.asarray(targets), jnp.asarray(valid), control=control)
    return float(jnp.max(gap)), float(jnp.max(ctl))


def check(run, dense, records, prompts: dict):
    """Add the served-path checks to ``run``; with ``run.control`` also
    read the bfloat16 control on the same requests."""
    chosen = sample(records, run.traffic["check_requests"], run.seed)
    short = sum(len(r.tokens) != r.budget for r in chosen)
    served = sum(len(r.tokens) for r in chosen)
    if chosen:
        gap, ctl = served_gap(dense, chosen, prompts, control=run.control)
    else:
        gap, ctl = float("inf"), float("inf")
    run.facts["checked"] = {"requests": len(chosen), "tokens": served}
    run.check("logit_gap", gap)
    run.check("short_requests", short)
    if run.control:
        run.control_readings["logit_gap"] = ctl
