"""Offline batches through the model's length-masked prefill, with no
scheduler: framewise transcription of a pool of utterances.

Utterance lengths are drawn, sorted, and cut into batches of ``batch``;
each batch is padded to the power-of-two bucket of its longest utterance.
The lengths and the order of the batches are the same for every seed;
the seed draws each utterance's row in its batch and its frames, made on
the device. The window sends the pool's batches in that order, cycling,
with ``dispatch_depth`` batches in flight, and counts the true frames of
the batches that ran inside it over its length; the batch running at the
close counts for the part of its run inside.

Check: once the window has closed, the batches drawn from the seed (the
one holding the longest utterance among them) run through the plain
reference; the number compared is the largest absolute difference over
every utterance's final (c, h) of each layer and its last valid frame's
logits.

Traffic keys: batch, pool_batches, frames (log-normal length spec),
max_bucket, dispatch_depth, check_batches, trace_seconds.
"""
from __future__ import annotations

import collections
import functools
import gc

import numpy as np

from bench.lib import gen, lm, stats


def _frames(key, *, B, T, X):
    import jax
    return jax.random.normal(key, (B, T, X))


def run(run) -> dict:
    import jax
    import jax.numpy as jnp
    from bench.reference import lstm as ref

    tr, cfg = run.traffic, run.cfg
    B, nb, X = tr["batch"], tr["pool_batches"], cfg["model"]["input_size"]
    sizes = gen.schedule_rng(3)
    groups = np.sort(gen.lognormal(B * nb, tr["frames"], sizes))
    groups = groups.reshape(nb, B)
    order = sizes.permutation(nb)
    g = gen.rng(run.seed, 3)
    lengths = [g.permutation(grp).astype(np.int32) for grp in groups]
    buckets = [gen.bucket(int(grp.max()), tr["max_bucket"])
               for grp in groups]
    key = jax.random.key(run.seed)
    make = {T: jax.jit(functools.partial(_frames, B=B, T=T, X=X))
            for T in set(buckets)}
    frames = [make[T](jax.random.fold_in(key, b))
              for b, T in enumerate(buckets)]
    lens_dev = [jnp.asarray(x) for x in lengths]

    dense, model, packed = lm.prepared(cfg, run.seed, batch=B,
                                       max_len=tr["max_bucket"])
    prefill = jax.jit(model.prefill, static_argnames=("max_len",))

    def send(b):
        return prefill(packed, frames[b], max_len=tr["max_bucket"],
                       length=lens_dev[b])

    for T in sorted(set(buckets)):
        jax.block_until_ready(send(buckets.index(T)))

    outputs, sent = {}, []          # sent: [batch, sent at, done at]
    inflight = collections.deque()
    k = 0
    run.open_window()
    while True:
        now = run.now()
        run.poll(now)
        if now >= run.t_close:
            break
        if len(inflight) < tr["dispatch_depth"]:
            b = int(order[k % nb])
            k += 1
            with run.annotate("driver.send"):
                sent.append([b, run.now(), None])
                inflight.append((sent[-1], send(b)))
            continue
        rec, out = inflight.popleft()
        with run.annotate("driver.wait"):
            jax.block_until_ready(out)
        rec[2] = run.now()
        if rec[2] < run.t_close:
            outputs[rec[0]] = out
    for rec, out in inflight:       # the batches running at the close
        jax.block_until_ready(out)
        rec[2] = run.now()
    run.close_window()
    t0, t1 = run.t_open, run.t_close
    tt0, tt1 = run.trace_window()
    done = [(b, t) for b, _, t in sent if t < t1]
    run.facts.update(
        window_s=tt1 - tt0,
        frames=stats.batch_work_in(
            [(s, t, float(lengths[b].sum())) for b, s, t in sent], tt0, tt1),
        utterances=stats.batch_work_in(
            [(s, t, float(B)) for _, s, t in sent], tt0, tt1),
        batch={"prefill": B})

    del packed, model, prefill, inflight
    gc.collect()
    longest = max(outputs, key=lambda b: int(lengths[b].max())) \
        if outputs else None
    rest = sorted(set(outputs) - {longest})
    pick = g.choice(len(rest), size=min(tr["check_batches"] - 1, len(rest)),
                    replace=False) if rest else []
    chosen = ([longest] if longest is not None else []) + \
        [rest[i] for i in sorted(pick)]
    err = ctl = 0.0 if chosen else float("inf")
    check = jax.jit(ref.final_states, static_argnames=("dtype",))
    for b in chosen:
        logits, cache = outputs[b]
        finals, ref_logits = check(dense, frames[b], lens_dev[b])
        got = [x for lp in cache["layers"] for x in (lp["c"], lp["h"])]
        want = [x for c, h in finals for x in (c, h)]
        got.append(logits[:, -1])
        want.append(ref_logits)
        err = max(err, max(float(jnp.max(jnp.abs(a - w)))
                           for a, w in zip(got, want)))
        if run.control:
            low, low_logits = check(dense, frames[b], lens_dev[b],
                                    dtype=jnp.bfloat16)
            lw = [x for c, h in low for x in (c, h)] + [low_logits]
            ctl = max(ctl, max(float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - w)))
                for a, w in zip(lw, want)))
    run.facts["checked"] = {"batches": len(chosen)}
    run.check("state_err", err)
    if run.control:
        run.control_readings["state_err"] = ctl
    # all the frames over all the window: the batch running at the close
    # counts for the part of its run inside the window
    worked = stats.batch_work_in(
        [(s, t, float(lengths[b].sum())) for b, s, t in sent], t0, t1)
    return {
        "attempted": B * len(done),
        "failed": 0,
        "metrics": {"frames_per_s": worked / (t1 - t0)},
    }
