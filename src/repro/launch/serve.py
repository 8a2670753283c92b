"""Serving driver: on-device batched decode, dense vs BRDS-sparse weights.

Serves every DecodeStep model — the transformer zoo AND the paper's LSTMs
(whose packed row-balanced kernels are exercised with --brds):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --prompt-len 64 --gen 32 --batch 4
  PYTHONPATH=src python -m repro.launch.serve --arch lstm_ptb --smoke --brds
  PYTHONPATH=src python -m repro.launch.serve --arch lstm_ptb --smoke \
      --brds --quant int8
  PYTHONPATH=src python -m repro.launch.serve --arch lstm_ptb --smoke \
      --brds --continuous --slots 4
  PYTHONPATH=src python -m repro.launch.serve --arch lstm_ptb --smoke \
      --brds --traffic --rate 16 --requests 64 --slots 8 --deadline 2.0
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --draft lstm_ptb --draft-brds --spec-k 4
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch lstm_ptb --smoke \
      --brds --mesh 2,4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def _build(args):
    """→ (model, cfg, vocab_size, sparsity_policy, extra_fn) where
    ``extra_fn(rng, batch)`` builds the family conditioning (encoder
    frames, patch embeds) for a batch of that size, or None."""
    from repro.models import LSTMModel, LSTM_CONFIGS

    if args.delta is None and (args.delta_h is not None
                               or args.occupancy is not None):
        raise SystemExit("--delta-h/--occupancy require --delta")
    if args.quant is not None and not args.brds:
        raise SystemExit("--quant requires --brds (quantization rides the "
                         "packed row-balanced weights)")
    if args.mesh is not None and args.arch in LSTM_CONFIGS and not args.brds:
        raise SystemExit("--mesh on an LSTM requires --brds (sharded decode "
                         "row-shards the packed gate rows — repro.dist)")
    if args.arch in LSTM_CONFIGS:
        cfg = LSTM_CONFIGS[args.arch]
        if args.smoke:
            cfg = dataclasses.replace(cfg, input_size=min(cfg.input_size, 128),
                                      hidden=min(cfg.hidden, 128))
        if not cfg.vocab_size:
            raise SystemExit(f"{args.arch} is not a language model")
        sparsity = None
        if args.brds or args.delta is not None:
            from repro.sparse import lstm_policy, DeltaGateConfig, QuantConfig
            delta = None
            if args.delta is not None:
                delta = DeltaGateConfig(
                    theta_x=args.delta,
                    theta_h=args.delta_h if args.delta_h is not None
                    else args.delta,
                    cap_x=args.occupancy, cap_h=args.occupancy)
            quant = QuantConfig(args.quant) if args.quant else None
            # ratio 0 compiles to an empty weight plan, so --delta without
            # --brds serves dense weights with temporal skipping only
            sparsity = lstm_policy(args.spar_a if args.brds else 0.0,
                                   args.spar_b if args.brds else 0.0,
                                   delta=delta, quant=quant)
        return (LSTMModel(cfg, fused=args.fused), cfg, cfg.vocab_size,
                sparsity, lambda rng, batch: None)

    if args.scorecard:
        raise SystemExit("--scorecard is LSTM-only (its MAC/byte ledger "
                         "covers the recurrent cell — repro.obs.scorecard)")
    if args.delta is not None:
        raise SystemExit("--delta is LSTM-only (temporal sparsity rides "
                         "the recurrent decode cache)")
    if args.quant is not None:
        raise SystemExit("--quant is LSTM-only (quantization rides the "
                         "packed LSTM decode path)")
    from repro.configs import get_arch, smoke_config
    from repro.models import build_model
    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    sparsity = None
    if args.brds:
        from repro.sparse import transformer_policy
        sparsity = transformer_policy(args.spar_a, args.spar_b)

    def extra_fn(rng, batch):
        if cfg.encdec:
            return jax.random.normal(rng, (batch, 32, cfg.d_model),
                                     dtype=cfg.jdtype)
        if cfg.num_patches:
            return jax.random.normal(rng, (batch, cfg.num_patches,
                                           cfg.d_model), dtype=cfg.jdtype)
        return None

    return model, cfg, cfg.vocab_size, sparsity, extra_fn


def _build_draft(args, vocab: int, max_len: int, batch: int):
    """Build the --draft DraftModel: an LSTM LM rebound to the target's
    vocab, prepared (prune/pack/delta/quant) through its own ServeEngine
    so every BRDS serving variant can play draft."""
    from repro.models import LSTMModel, LSTM_CONFIGS
    from repro.serving import ServeEngine
    from repro.spec import DraftModel

    if args.draft not in LSTM_CONFIGS:
        raise SystemExit(f"--draft wants an LSTM arch "
                         f"({', '.join(LSTM_CONFIGS)}), got {args.draft!r}")
    if args.draft_quant and not args.draft_brds:
        raise SystemExit("--draft-quant requires --draft-brds")
    cfg = LSTM_CONFIGS[args.draft]
    if args.smoke:
        cfg = dataclasses.replace(cfg, input_size=min(cfg.input_size, 128),
                                  hidden=min(cfg.hidden, 128))
    cfg = dataclasses.replace(cfg, vocab_size=vocab)
    sparsity = None
    if args.draft_brds or args.draft_delta is not None:
        from repro.sparse import lstm_policy, DeltaGateConfig, QuantConfig
        delta = None
        if args.draft_delta is not None:
            delta = DeltaGateConfig(theta_x=args.draft_delta,
                                    theta_h=args.draft_delta)
        quant = QuantConfig(args.draft_quant) if args.draft_quant else None
        sparsity = lstm_policy(args.spar_a if args.draft_brds else 0.0,
                               args.spar_b if args.draft_brds else 0.0,
                               delta=delta, quant=quant)
    deng = ServeEngine(LSTMModel(cfg), cfg, max_len=max_len, batch=batch,
                       sparsity=sparsity)
    dparams = deng.model.init(jax.random.key(7))
    calib = None
    if args.draft_quant:
        calib = jax.random.randint(jax.random.key(8),
                                   (batch, min(args.prompt_len, 32)),
                                   0, vocab)
    dparams, report = deng.prepare(dparams, calib=calib)
    if report is not None:
        print("draft BRDS:", report)
    return DraftModel(deng.model, dparams)


def _obs_outputs(args, params, counters, wall_s, *, batch, step_sum=None,
                 records=None, summary=None, spec=None, extra_gauges=None):
    """--scorecard / --metrics / --trace outputs, shared by the lockstep,
    --continuous, and --traffic paths (repro.obs)."""
    if args.scorecard and counters is not None:
        from repro.obs import scorecard as obs_scorecard
        card = obs_scorecard.build(params, counters, wall_s, batch=batch,
                                   step_sum=step_sum)
        print(obs_scorecard.render(card))
    if args.metrics:
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        if records is not None:
            reg.absorb_traffic(records, summary)
        reg.absorb_spec(spec)
        reg.absorb_counters(counters)
        for name, val in (extra_gauges or {}).items():
            reg.gauge(name).set(val)
        reg.dump(args.metrics)
        print(f"metrics -> {args.metrics}")
    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.save(args.trace)
        print(f"trace -> {args.trace} "
              f"({len(obs_trace.get_tracer().events)} events)")


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    help="transformer-zoo arch or lstm_ptb/lstm_timit/...")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--brds", action="store_true",
                    help="row-balanced prune (and, for the LSTM, pack) "
                         "the weights first")
    ap.add_argument("--spar-a", type=float, default=0.75)
    ap.add_argument("--spar-b", type=float, default=0.5)
    ap.add_argument("--delta", type=float, default=None, metavar="THETA",
                    help="LSTM only: serve with Spartus-style temporal "
                         "delta sparsity at threshold THETA (0 = exact; "
                         "composes with --brds packed weights)")
    ap.add_argument("--delta-h", type=float, default=None,
                    help="separate recurrent-path threshold "
                         "(default: same as --delta)")
    ap.add_argument("--occupancy", type=float, default=None, metavar="CAP",
                    help="cap the fired-column fraction per step "
                         "(hardware worst-case bound)")
    ap.add_argument("--quant", default=None, metavar="SCHEME",
                    help="LSTM only, requires --brds: serve fixed-point "
                         "quantized packed weights ('int8' or paper-style "
                         "'qM.N', e.g. 'q1.11'); activation scales are "
                         "calibrated on a prompt-shaped batch")
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="sparse-kernel backend for packed decode")
    ap.add_argument("--fused", dest="fused", action="store_true",
                    default=None,
                    help="LSTM: force single-launch fused decode kernels "
                         "(default: on wherever shapes allow; sharded "
                         "--mesh decode always chains)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="LSTM: force the chained per-kernel decode path")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass in (0, 1); 0 disables")
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve through a (data, model) device mesh, e.g. "
                         "'2,4' (repro.dist sharded packed decode; for the "
                         "LSTM requires --brds so the gate rows can be "
                         "row-sharded — force host devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N to try "
                         "on CPU)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a ragged request stream through the "
                         "continuous-batching scheduler instead of one "
                         "lockstep batch")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--traffic", action="store_true",
                    help="drive the scheduler with a seeded Poisson arrival "
                         "trace (repro.traffic.loadgen) and report the "
                         "latency curve: TTFT/TPOT percentiles, goodput, "
                         "drops. Composes with --brds/--delta/--quant/"
                         "--mesh; uses --slots and --dispatch-depth")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="--traffic: offered load, requests/second")
    ap.add_argument("--requests", type=int, default=64,
                    help="--traffic: total requests in the trace")
    ap.add_argument("--deadline", type=float, default=None, metavar="SEC",
                    help="--traffic: per-request TTLT deadline; queued "
                         "requests expire and in-slot requests are evicted "
                         "past it (overload shedding)")
    ap.add_argument("--dispatch-depth", type=int, default=2,
                    help="decode chunks kept in flight ahead of the host "
                         "(1 = synchronous harvest-before-dispatch)")
    ap.add_argument("--load-seed", type=int, default=0,
                    help="--traffic: arrival-trace RNG seed (the schedule "
                         "is fully deterministic given the seed)")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="speculative decoding: propose with this LSTM "
                         "arch (e.g. lstm_ptb) rebound to the target's "
                         "vocab; greedy output is bitwise identical to "
                         "serving without it. Composes with --continuous "
                         "and --traffic")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="--draft: tokens proposed per speculative round")
    ap.add_argument("--draft-brds", action="store_true",
                    help="row-balanced prune + pack the draft's weights "
                         "(--spar-a/--spar-b ratios)")
    ap.add_argument("--draft-delta", type=float, default=None,
                    metavar="THETA",
                    help="draft with temporal delta sparsity at THETA")
    ap.add_argument("--draft-quant", default=None, metavar="SCHEME",
                    help="draft with quantized packed weights ('int8' or "
                         "'qM.N'); requires --draft-brds")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record a Chrome-trace (Perfetto-loadable JSON) of "
                         "engine/scheduler spans to FILE (repro.obs.trace)")
    ap.add_argument("--metrics", default=None, metavar="FILE",
                    help="dump a metrics snapshot to FILE — Prometheus "
                         "text, or JSON when FILE ends in .json "
                         "(repro.obs.metrics)")
    ap.add_argument("--scorecard", action="store_true",
                    help="LSTM only: print the effective-GOPS scorecard — "
                         "harvested on-device counters against the decode "
                         "roofline (repro.obs.scorecard)")
    args = ap.parse_args()

    from repro.serving import (ServeEngine, ContinuousBatchingEngine,
                               SamplingConfig)
    from repro.sparse import set_default_backend

    set_default_backend(args.backend)
    if args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable()
    # counters ride the decode dispatches only when an obs output wants them
    want_counters = args.scorecard or args.metrics is not None
    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_host_mesh
        try:
            d, m = (int(v) for v in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh wants 'DATA,MODEL' ints, got "
                             f"{args.mesh!r}")
        try:
            mesh = make_host_mesh(data=d, model=m)
        except ValueError as e:
            raise SystemExit(
                f"--mesh {args.mesh}: {e} (force host devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        print(f"mesh: data={d} model={m} over {d * m} devices")
    model, cfg, vocab, sparsity, extra_fn = _build(args)
    params = model.init(jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.1f}M")

    max_len = args.prompt_len + args.gen
    eng = ServeEngine(model, cfg, max_len=max_len, batch=args.batch,
                      sparsity=sparsity, mesh=mesh)
    calib = None
    if args.quant:
        # calibrate activation scales on a prompt-shaped batch through the
        # dense params (prepare prunes/packs afterwards)
        calib = jax.random.randint(jax.random.key(3),
                                   (args.batch, min(args.prompt_len, 32)),
                                   0, vocab)
    params, brds_report = eng.prepare(params, calib=calib)
    if brds_report is not None:
        print("BRDS:", brds_report)
    rng = jax.random.key(1)
    sampling = SamplingConfig(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p, eos_id=args.eos_id)

    draft = None
    if args.draft is not None:
        if args.mesh is not None:
            raise SystemExit("--draft does not compose with --mesh yet")
        draft = _build_draft(args, vocab, max_len, args.batch)
        print(f"draft={args.draft} spec_k={args.spec_k}")

    if args.traffic:
        from repro.traffic import LoadConfig, poisson_trace, make_prompts, \
            serve_trace
        sched = ContinuousBatchingEngine(
            eng.model, params, slots=args.slots, max_len=max_len,
            sampling=sampling, dispatch_depth=args.dispatch_depth,
            mesh=mesh if eng._dist else None, draft=draft,
            spec_k=args.spec_k, counters=want_counters)
        short_hi = max(5, args.prompt_len // 4)
        long_hi = max(short_hi + 1, args.prompt_len)
        lc = LoadConfig(rate=args.rate, num_requests=args.requests,
                        prompt_short=(4, short_hi),
                        prompt_long=(short_hi, long_hi),
                        output_lens=(4, args.gen), deadline=args.deadline,
                        seed=args.load_seed)
        trace = poisson_trace(lc)
        prompts = make_prompts(trace, vocab, seed=args.load_seed)
        print(f"traffic: {args.requests} requests at {args.rate:.1f} req/s, "
              f"slots={args.slots} depth={args.dispatch_depth}"
              + (f" deadline={args.deadline}s" if args.deadline else ""))
        records, summary = serve_trace(sched, trace, prompts,
                                       offered_rps=args.rate)
        print(f"completed={summary['completed']} "
              f"expired={summary['expired']} rejected={summary['rejected']} "
              f"({summary['tokens']} tokens, {summary['wall_s']:.2f}s wall, "
              f"{sched.steps_dispatched} chunk dispatches)")
        ms = lambda v: "n/a" if v is None else f"{v:.2f}"
        print(f"TTFT ms: p50={ms(summary['p50_ttft_ms'])} "
              f"p90={ms(summary['p90_ttft_ms'])} "
              f"p99={ms(summary['p99_ttft_ms'])}")
        print(f"TPOT ms: p50={ms(summary['p50_tpot_ms'])} "
              f"p99={ms(summary['p99_tpot_ms'])}")
        print(f"goodput: {summary['goodput_tps']:.1f} tok/s "
              f"(total {summary['toks_per_s']:.1f} tok/s)")
        if draft is not None:
            st = sched.spec_stats()
            print(f"spec: acceptance={st['acceptance_rate']:.1%} "
                  f"({st['accepted']}/{st['drafted']} drafted over "
                  f"{st['rounds']} rounds)")
        _obs_outputs(
            args, params, sched.counters() if want_counters else None,
            summary["wall_s"], batch=args.slots,
            step_sum=float(np.sum(sched.slot_steps))
            if args.delta is not None else None,
            records=records, summary=summary,
            spec=sched.spec_stats() if draft is not None else None)
        return

    if args.continuous:
        # eng.model carries the delta/quant/mesh wiring applied by prepare;
        # only dist-partitioned serving passes the mesh through (the
        # scheduler has no sharded path for the transformer zoo)
        sched = ContinuousBatchingEngine(eng.model, params, slots=args.slots,
                                         max_len=max_len, sampling=sampling,
                                         mesh=mesh if eng._dist else None,
                                         draft=draft, spec_k=args.spec_k,
                                         counters=want_counters)
        lens = [max(4, args.prompt_len - 3 * i) for i in range(args.batch)]
        for i, plen in enumerate(lens):
            req_rng = jax.random.fold_in(rng, i)
            prompt = jax.random.randint(req_rng, (1, plen), 0, vocab)
            sched.submit(prompt, args.gen, extra=extra_fn(req_rng, 1))
        t0 = time.time()
        results = sched.run()
        dt = time.time() - t0
        total = sum(len(v) for v in results.values())
        print(f"served {len(results)} ragged requests "
              f"({total} tokens) in {dt:.2f}s ({total / dt:.1f} tok/s, "
              f"{sched.steps_dispatched} chunk dispatches)")
        if draft is not None:
            st = sched.spec_stats()
            print(f"spec: acceptance={st['acceptance_rate']:.1%} "
                  f"({st['accepted']}/{st['drafted']} drafted over "
                  f"{st['rounds']} rounds)")
        if args.delta is not None:
            from repro.sparse import occupancy_report
            occ = occupancy_report(
                sched.cache, steps=sched.slot_steps,
                packed=params if args.brds else None)
            line = (f"delta: occupancy x={occ['occupancy_x']:.1%} "
                    f"h={occ['occupancy_h']:.1%}")
            if "ops_reduction" in occ:
                line += (f", effective-ops reduction "
                         f"{occ['ops_reduction']:.2f}x")
            print(line + " (final slot residents)")
        _obs_outputs(
            args, params, sched.counters() if want_counters else None,
            dt, batch=args.slots,
            step_sum=float(np.sum(sched.slot_steps))
            if args.delta is not None else None,
            spec=sched.spec_stats() if draft is not None else None,
            extra_gauges={"serve_toks_per_s": total / dt})
        uid0 = min(results)
        print("sample ids:", results[uid0][:16])
        return

    tokens = jax.random.randint(rng, (args.batch, args.prompt_len), 0, vocab)
    extra = extra_fn(rng, args.batch)
    t0 = time.time()
    out, state = eng.generate(params, tokens, args.gen, extra=extra,
                              sampling=sampling, rng=jax.random.key(2),
                              return_state=True, draft=draft,
                              spec_k=args.spec_k)
    out.block_until_ready()
    dt = time.time() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, one decode dispatch)")
    spec = None
    if draft is not None:
        drafted = int(np.sum(np.asarray(state["drafted"])))
        accepted = int(np.sum(np.asarray(state["accepted"])))
        rounds = int(np.sum(np.asarray(state["rounds"])))
        spec = dict(rounds=rounds, drafted=drafted, accepted=accepted,
                    acceptance_rate=accepted / max(drafted, 1))
        print(f"spec: acceptance={accepted / max(drafted, 1):.1%} "
              f"({accepted}/{drafted} drafted over {rounds} rounds)")
    if args.delta is not None:
        from repro.sparse import occupancy_report
        occ = occupancy_report(
            state["cache"], steps=args.prompt_len + args.gen,
            packed=params if args.brds else None)
        line = (f"delta: occupancy x={occ['occupancy_x']:.1%} "
                f"h={occ['occupancy_h']:.1%}")
        if "ops_reduction" in occ:
            line += f", effective-ops reduction {occ['ops_reduction']:.2f}x"
        print(line)
    c = None
    if want_counters:
        from repro.obs import counters as obs_counters
        c = obs_counters.from_state(eng.model, state, steps=args.gen)
    _obs_outputs(
        args, params, c, dt, batch=args.batch,
        step_sum=float(args.batch * (args.prompt_len + args.gen))
        if args.delta is not None else None,
        spec=spec, extra_gauges={"serve_toks_per_s":
                                 args.batch * args.gen / dt})
    print("sample ids:", np.asarray(out[0][:16]))


if __name__ == "__main__":
    main()
