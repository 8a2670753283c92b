"""The on-device speculate→verify→accept round loop.

``spec_decode_loop`` is ``decode_loop``'s speculative sibling: same carry
discipline (per-sequence done/emitted/pos, EOS/budget/limit stops, pad
emission after done, chunk-resumable state dict) but the unit of work is a
ROUND, not a token — draft proposes k tokens, the target verifies the
whole block in one dispatch, an acceptance rule keeps a prefix, and both
models roll back to the committed point. Each active row commits at least
one token per round (the round-opening target sample), so the
``lax.while_loop`` terminates within ``steps`` rounds.

The carry's distribution slot: where ``decode_loop`` carries the last
logits, this loop carries ``probs`` — the (B, V) sampling DISTRIBUTION for
each row's next token (a ``sampling.sample_dist`` output, or the
rejection-sampling residual). Greedy distributions are one-hot, so the
greedy path commits exactly the target argmax chain: bitwise identical to
target-only greedy decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..serving.sampling import SamplingConfig, sample_dist, sample_from_dist
from . import verify as V
from .accept import greedy_accept, rejection_accept, residual_dist

__all__ = ["spec_decode_loop"]


def spec_decode_loop(model, draft, params, dparams, cache, dstate, probs,
                     pos, rng, steps: int, k: int,
                     sampling: SamplingConfig, *, done=None, budget=None,
                     limit: int | None = None):
    """Generate up to ``steps`` tokens per row via speculative rounds.

    Parameters (beyond ``decode_loop``'s)
    -------------------------------------
    draft : DraftModel
        The recurrent draft adapter.
    dparams / dstate : pytree
        Draft params and per-row recurrent state (primed on the same
        prompt as ``cache``).
    probs : jnp.ndarray
        (B, V) fp32 sampling distribution for the next token —
        ``sample_dist(prefill_logits[:, -1], sampling)``, or the carried
        distribution of a previous chunk.
    pos : jnp.ndarray
        Scalar or (B,) next cache position. Always vectorized internally:
        per-row commit counts diverge, and vector positions keep
        ``kv_cache_update`` on the scatter path whose out-of-bounds
        writes drop (the scalar path clamps).
    k : int
        Draft tokens proposed per round (static). k=0 degenerates to
        verified-one-token-per-round, i.e. plain autoregressive decode.

    Returns
    -------
    (tokens, state)
        ``tokens`` (B, steps) int32 — emitted tokens, pad-filled after a
        row finishes/pauses. ``state`` carries everything ``decode_loop``'s
        does (with ``dstate``/``probs`` in place of ``logits``) plus
        per-row round accounting: ``rounds``, ``drafted``, ``accepted`` —
        acceptance-rate = accepted / drafted.
    """
    B, Vv = probs.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    if done is None:
        done = jnp.zeros((B,), bool)
    greedy = sampling.temperature <= 0.0
    dcfg = draft.sampling if draft.sampling is not None else sampling
    out0 = jnp.full((B, steps), jnp.int32(sampling.pad_id))
    zeros = jnp.zeros((B,), jnp.int32)

    def cond(carry):
        _, _, _, _, done, _, emitted, out, *_ = carry
        return jnp.any(~done & (emitted < steps))

    def body(carry):
        (cache, dstate, probs, pos, done, rng, emitted, out,
         rounds, drafted, accepted) = carry
        rng, r_nxt, r_draft, r_acc = jax.random.split(rng, 4)
        active = ~done & (emitted < steps)

        # round-opening token: the sample the previous round left pending
        nxt = sample_from_dist(r_nxt, probs, sampling)
        nxt = jnp.where(done, jnp.int32(sampling.pad_id), nxt)

        # draft chain + one-dispatch target verify of [nxt, d_1..d_k]
        d_toks, q_dists, d_states = draft.propose(
            dparams, dstate, nxt, pos, k, r_draft, dcfg)
        block = jnp.concatenate([nxt[:, None], d_toks], axis=1)
        t_logits, cache, t_states = V.verify_chain(
            model, params, cache, block, pos)
        p_dists = sample_dist(t_logits, sampling)

        if k == 0:
            a = zeros
        elif greedy:
            a = greedy_accept(d_toks, t_logits)
        else:
            a = rejection_accept(r_acc, d_toks, p_dists, q_dists)

        # stepwise emission — decode_loop's exact stop discipline applied
        # to the a+1 committable tokens (EOS itself emitted, budget
        # checked post-increment, limit = next write position, steps caps
        # the chunk WITHOUT setting done so a later chunk resumes)
        rd, em, m = done, emitted, zeros
        rows = jnp.arange(B)
        for j in range(k + 1):
            tok_j = block[:, j]
            can = ~rd & (j <= a) & (em < steps)
            slot = jnp.minimum(em, steps - 1)
            out = out.at[rows, slot].set(
                jnp.where(can, tok_j, out[rows, slot]))
            em = em + can.astype(jnp.int32)
            m = m + can.astype(jnp.int32)
            if sampling.stops:
                rd = rd | (can & (tok_j == sampling.eos_id))
            if budget is not None:
                rd = rd | (can & (em >= budget))
            if limit is not None:
                rd = rd | (can & (pos + m >= limit))

        # roll both models back to the per-row committed point
        pos2 = pos + m
        cache2 = V.rollback(model, cache, t_states, m)
        dstate2 = draft.select(dstate, d_states, m)

        # next round's pending distribution: the residual at the stop slot
        # when the commit ended exactly at the acceptance boundary, the
        # verify distribution after the last committed token otherwise
        # (early stop via EOS/budget/limit); untouched when nothing moved
        p_stop = residual_dist(p_dists, q_dists, a)
        idx = jnp.maximum(m - 1, 0)
        p_m = jnp.take_along_axis(
            p_dists, idx[:, None, None], axis=1)[:, 0]
        base = jnp.where((idx == a)[:, None], p_stop, p_m)
        probs2 = jnp.where((m == 0)[:, None], probs, base)

        inc = active.astype(jnp.int32)
        return (cache2, dstate2, probs2, pos2, rd, rng, em, out,
                rounds + inc, drafted + k * inc, accepted + a * inc)

    carry = (cache, dstate, probs, pos, done, rng, jnp.zeros((B,), jnp.int32),
             out0, zeros, zeros, zeros)
    (cache, dstate, probs, pos, done, rng, emitted, out,
     rounds, drafted, accepted) = jax.lax.while_loop(cond, body, carry)
    return out, dict(cache=cache, dstate=dstate, probs=probs, pos=pos,
                     rng=rng, done=done, emitted=emitted, rounds=rounds,
                     drafted=drafted, accepted=accepted)
