"""Bring-up smoke run: BRDS serving on a TPU chip at ``lstm_ptb``'s width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded decode on a 1x4 mesh

One chip drives the serving path a user calls — ``lstm_policy(0.75, 0.5)``
→ ``ServeEngine.prepare`` → packed fused Pallas decode — then the
continuous-batching scheduler and the delta (Θ=0) and calibrated int8
variants, each checked against the float32 ``jax.numpy`` reference
backend ("ref") on the same chip. ``--four-chips`` runs only sharded
decode (``repro.dist``) against one-device decode in the same process.

Weights are random, made from ``--seed``. The model is ``lstm_ptb`` at its
published width (X=H=1500, vocab 10000, one layer). The script exits
non-zero and prints no result line when JAX finds no TPU or any phase
fails; otherwise its last stdout line is one JSON object naming the
device. Everything else is printed on earlier lines.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SPAR_X, SPAR_H = 0.75, 0.5          # the serve defaults (--spar-a/--spar-b)
U = 2.0 ** -24                      # float32 unit roundoff


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def logit_tolerance(cfg) -> float:
    """Largest |Δlogit| allowed between the Pallas and ``ref`` decodes.

    Both evaluate the same float32 products (the dense head at full f32
    precision in both); they differ only in the order each packed row
    sums its n = Kx + Kh + 1 terms (128-lane chunks in the kernel, XLA's
    dot in ``ref``) and in how the cell's σ/tanh are lowered. Reordering
    a sum of n zero-mean terms whose total is of size |z| moves it by
    about u·√(n/2)·|z| (u = 2^-24, one rounding of a partial sum of
    size ~|z|·√(k/n) per addition, random signs); gate preactivations
    here have |z| ≲ 1. The cell passes that through slopes ≤ 1, the
    recurrence forgets it geometrically (forget gate ≈ σ(0) = ½, so ≤ 2×),
    and the head's dot of 1/√H-scale weights keeps its size; the largest
    of B·vocab logits sits within 4σ. So tol = 4·2·u·√(n/2): 1.13e-05 at
    ``lstm_ptb`` width. Packed values rounded to bfloat16 move logits by
    ~1e-4 or more, and ``lockstep`` checks that such a control fails."""
    from repro.core.sparsity import keep_count
    n = (keep_count(cfg.input_size, SPAR_X) + keep_count(cfg.hidden, SPAR_H)
         + 1)
    return 4 * 2 * U * (n / 2) ** 0.5


def _timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def _has_kernel(fn, *args) -> bool:
    """Whether ``fn``'s compiled program holds a Pallas TPU kernel."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _finite(x) -> bool:
    import numpy as np
    return bool(np.isfinite(np.asarray(x)).all())


def _bf16_values(packed):
    """``packed`` with every packed weight value rounded to bfloat16."""
    import jax.numpy as jnp
    from repro.core.packing import RowBalancedSparse

    def rnd(leaf):
        if not isinstance(leaf, RowBalancedSparse):
            return leaf
        v = leaf.values
        return dataclasses.replace(
            leaf, values=v.astype(jnp.bfloat16).astype(v.dtype))
    return {**packed, "layers": [{k: rnd(v) for k, v in lp.items()}
                                 for lp in packed["layers"]]}


def _precision(backend: str):
    """The "highest" matmul precision for the ``ref`` backend, whose
    packed products are XLA dots; the default (what users run) for
    Pallas."""
    import jax
    return (jax.default_matmul_precision("highest") if backend == "ref"
            else contextlib.nullcontext())


def lockstep(cfg, *, seed: int, batch: int, prompt_len: int, gen: int):
    """Lockstep generate on the default fused Pallas path at the default
    matmul precision, as ``launch.serve`` runs it; then the same decode
    with ``backend="ref"`` under "highest" precision, whose packed
    products are XLA dots that would otherwise take bf16 operands. A
    control prefill with the packed values rounded to bfloat16 must fail
    the tolerance. Returns the Pallas tokens, prompts and prepared
    state."""
    import jax
    import numpy as np
    from repro.kernels import interpret_mode
    from repro.models import LSTMModel
    from repro.serving import ServeEngine
    from repro.sparse import lstm_policy, use_backend

    model = LSTMModel(cfg)
    params = model.init(jax.random.key(seed))
    eng = ServeEngine(model, cfg, max_len=prompt_len + gen, batch=batch,
                      sparsity=lstm_policy(SPAR_X, SPAR_H))
    (packed, report), t_prep = _timed(eng.prepare, params)
    print(f"lockstep: prepare {t_prep:.2f}s packed_bytes="
          f"{report['packed_bytes']} dense_bytes={report['dense_bytes']}")
    prompts = jax.random.randint(jax.random.key(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    tol = logit_tolerance(cfg)

    runs = {}
    for backend, m in (("pallas", eng.model), ("ref", copy.copy(eng.model))):
        with use_backend(backend), _precision(backend):
            cache = m.init_cache(batch, prompt_len + gen)
            tok0 = prompts[:, :1]
            # the compiled step proves which backend ran: a Mosaic kernel
            # on the Pallas path (interpret mode off the chip inlines it)
            want = backend == "pallas" and not interpret_mode()
            check(_has_kernel(m.decode_step, packed, cache, tok0, 0) == want,
                  f"{backend} decode step: Pallas kernel present != {want}")
            prefill = jax.jit(m.prefill, static_argnames=("max_len",))
            (lg0, _), t_pf = _timed(prefill, packed, prompts,
                                    max_len=prompt_len + gen)
            e = eng if backend == "pallas" else ServeEngine(
                m, cfg, max_len=prompt_len + gen, batch=batch)
            (toks, st), t_gen = _timed(e.generate, packed, prompts, gen,
                                       return_state=True)
            if backend == "pallas":
                _, t_warm = _timed(e.generate, packed, prompts, gen)
                ctrl, _ = prefill(_bf16_values(packed), prompts,
                                  max_len=prompt_len + gen)
        print(f"lockstep[{backend}]: prefill {t_pf:.2f}s, generate "
              f"{t_gen:.2f}s (compile included)")
        check(toks.shape == (batch, gen), f"{backend} tokens {toks.shape}")
        check(_finite(lg0) and _finite(st["logits"]),
              f"{backend} logits not finite")
        runs[backend] = (np.asarray(lg0), np.asarray(toks),
                         np.asarray(st["logits"]))

    print(f"lockstep[pallas]: warm generate {t_warm:.3f}s for "
          f"{prompt_len} prefill + {gen} decode steps at B={batch}")
    (p0, pt, pl), (r0, rt, rl) = runs["pallas"], runs["ref"]
    d_pre = float(np.max(np.abs(p0 - r0)))
    d_ctrl = float(np.max(np.abs(np.asarray(ctrl) - r0)))
    agree = float(np.mean(pt == rt))
    print(f"lockstep: max|dlogit| prefill={d_pre:.3e} tol={tol:.3e}")
    print(f"lockstep: bf16-valued control max|dlogit| prefill={d_ctrl:.3e}")
    print(f"lockstep: greedy-token agreement {agree:.4f} "
          f"({int(np.sum(pt == rt))}/{pt.size})")
    check(d_pre <= tol, f"prefill logits differ by {d_pre:.3e} > {tol:.3e}")
    check(d_ctrl > tol, f"bf16-valued control passes the tolerance "
          f"({d_ctrl:.3e} <= {tol:.3e}): the check cannot see precision loss")
    check(agree == 1.0, "greedy tokens diverged from the ref backend")
    d_last = float(np.max(np.abs(pl - rl)))
    print(f"lockstep: max|dlogit| after step {gen}={d_last:.3e}")
    check(d_last <= tol, f"final logits differ by {d_last:.3e} > {tol:.3e}")
    return dict(model=eng.model, packed=packed, prompts=np.asarray(prompts),
                tokens=pt)


def scheduler(cfg, state, *, slots: int, requests: int, gen: int):
    """``ContinuousBatchingEngine`` with ``slots`` slots serving
    ``requests`` ragged requests: every request completes with its budget,
    and its tokens equal a batch-1 lockstep decode of the same prompt
    (the scheduler's parity contract, tests/test_serving.py), at the
    default matmul precision users run.

    That contract needs every row's arithmetic to be independent of the
    batch it sits in: the packed kernels' is by construction, and the
    model pins its dense head dot to full f32 precision."""
    import jax
    import numpy as np
    from repro.serving import ContinuousBatchingEngine, ServeEngine

    model, packed, prompts = state["model"], state["packed"], state["prompts"]
    plen = prompts.shape[1]
    max_len = plen + gen
    lens = [plen - (plen // 4) * (i % 4) for i in range(requests)]
    budgets = [gen - (gen // 2) * (i % 2) for i in range(requests)]
    reqs = [jax.numpy.asarray(prompts[i % len(prompts), :lens[i]])[None]
            for i in range(requests)]
    sched = ContinuousBatchingEngine(model, packed, slots=slots,
                                     max_len=max_len)
    uids = [sched.submit(p, b) for p, b in zip(reqs, budgets)]
    results, t_run = _timed(sched.run)
    one = ServeEngine(model, None, max_len=max_len, batch=1)
    wants = [np.asarray(one.generate(packed, p, b))[0]
             for p, b in zip(reqs, budgets)]
    print(f"scheduler: {requests} requests through {slots} slots in "
          f"{t_run:.2f}s (compile included), "
          f"{sched.steps_dispatched} chunk dispatches")
    check(sorted(results) == sorted(uids), "requests missing from results")
    for i, (uid, want) in enumerate(zip(uids, wants)):
        got = np.asarray(results[uid])
        check(len(got) == budgets[i],
              f"request {i} emitted {len(got)} != budget {budgets[i]}")
        diff = np.flatnonzero(got != want)
        check(diff.size == 0,
              f"request {i} tokens differ from batch-1 lockstep decode "
              f"from token {diff[:1].tolist()} on")
    print(f"scheduler: all {requests} requests complete and match batch-1 "
          "lockstep decode")


def variants(cfg, *, seed: int, batch: int, prompt_len: int, gen: int):
    """Delta Θ=0 and calibrated int8, a few decode steps each, against
    their ``ref`` backend (Pallas at the default precision, ``ref`` at
    "highest", as in ``lockstep``)."""
    import jax
    import numpy as np
    from repro.models import LSTMModel
    from repro.serving import ServeEngine
    from repro.sparse import (DeltaGateConfig, QuantConfig, lstm_policy,
                              use_backend)

    tol = logit_tolerance(cfg)
    params = LSTMModel(cfg).init(jax.random.key(seed))
    prompts = jax.random.randint(jax.random.key(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    calib = jax.random.randint(jax.random.key(seed + 2),
                               (batch, prompt_len), 0, cfg.vocab_size)
    cases = {
        "delta_theta0": dict(delta=DeltaGateConfig(theta_x=0.0,
                                                   theta_h=0.0)),
        "int8": dict(quant=QuantConfig("int8")),
    }
    for name, rule in cases.items():
        eng = ServeEngine(LSTMModel(cfg), cfg, max_len=prompt_len + gen,
                          batch=batch,
                          sparsity=lstm_policy(SPAR_X, SPAR_H, **rule))
        packed, _ = eng.prepare(params, calib=calib if "quant" in rule
                                else None)
        out = {}
        for backend, m in (("pallas", eng.model),
                           ("ref", copy.copy(eng.model))):
            with use_backend(backend), _precision(backend):
                e = ServeEngine(m, cfg, max_len=prompt_len + gen,
                                batch=batch)
                (toks, st), t_gen = _timed(e.generate, packed, prompts, gen,
                                           return_state=True)
            check(_finite(st["logits"]), f"{name}[{backend}] not finite")
            out[backend] = (np.asarray(toks), np.asarray(st["logits"]))
            print(f"{name}[{backend}]: generate {t_gen:.2f}s "
                  "(compile included)")
        d = float(np.max(np.abs(out["pallas"][1] - out["ref"][1])))
        agree = float(np.mean(out["pallas"][0] == out["ref"][0]))
        print(f"{name}: max|dlogit| after step {gen}={d:.3e} tol={tol:.3e}"
              f" token agreement {agree:.4f}")
        check(agree == 1.0, f"{name} tokens diverged from ref")
        check(d <= tol, f"{name} logits differ by {d:.3e}")


def four_chips(cfg, *, seed: int, batch: int, prompt_len: int, gen: int):
    """Sharded packed decode on a (data=1, model=4) mesh against the
    one-device decode in the same process: at data=1 ``repro.dist`` is
    bitwise, so greedy tokens must be equal. The packed params must be
    placed across all four devices. Both run at the default matmul
    precision; at data=1 the logits must be bitwise equal too."""
    import jax
    import numpy as np
    from repro.core.packing import RowBalancedSparse
    from repro.launch.mesh import make_mesh
    from repro.models import LSTMModel
    from repro.serving import ServeEngine
    from repro.sparse import lstm_policy

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    params = LSTMModel(cfg).init(jax.random.key(seed))
    prompts = jax.random.randint(jax.random.key(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    out = {}
    for name, mesh in (("one_device", None),
                       ("sharded", make_mesh((1, 4), ("data", "model")))):
        eng = ServeEngine(LSTMModel(cfg), cfg, max_len=prompt_len + gen,
                          batch=batch, sparsity=lstm_policy(SPAR_X, SPAR_H),
                          mesh=mesh)
        packed, _ = eng.prepare(params)
        if mesh is not None:
            check(eng._dist, "engine did not take the repro.dist path")
            leaves = [a for lp in packed["layers"]
                      for s in (lp["w_x"], lp["w_h"])
                      if isinstance(s, RowBalancedSparse)
                      for a in (s.values, s.deltas)]
            devs = set().union(*(a.sharding.device_set for a in leaves))
            print(f"sharded: packed params span {len(devs)} devices "
                  f"({sorted(d.id for d in devs)})")
            check(len(devs) == 4 and all(len(a.sharding.device_set) == 4
                                         for a in leaves),
                  "packed params do not span 4 devices")
        (toks, st), t_gen = _timed(eng.generate, packed, prompts, gen,
                                   return_state=True)
        print(f"{name}: generate {t_gen:.2f}s (compile included)")
        check(_finite(st["logits"]), f"{name} logits not finite")
        out[name] = (np.asarray(toks), np.asarray(st["logits"]))
    agree = float(np.mean(out["sharded"][0] == out["one_device"][0]))
    d = float(np.max(np.abs(out["sharded"][1] - out["one_device"][1])))
    print(f"four_chips: token agreement {agree:.4f}, "
          f"max|dlogit| after step {gen}={d:.3e}")
    check(agree == 1.0, "sharded tokens differ from one-device tokens")
    check(d == 0.0, f"sharded logits differ from one-device logits by {d:.3e}"
          " (the data=1 contract is bitwise)")


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded decode on four chips and the "
                         "one-device decode it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.models import LSTM_CONFIGS

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} cache={cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    cfg = LSTM_CONFIGS["lstm_ptb"]
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(cfg, seed=args.seed, batch=8, prompt_len=64, gen=32)
    else:
        st = lockstep(cfg, seed=args.seed, batch=8, prompt_len=64, gen=32)
        scheduler(cfg, st, slots=4, requests=8, gen=32)
        variants(cfg, seed=args.seed, batch=8, prompt_len=64, gen=4)
    print(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
