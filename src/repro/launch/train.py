"""End-to-end training driver.

CPU-scale example (default: a reduced config on the host device):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 128

Production shape (what a real pod launch runs — identical code path, the
mesh is bigger):
  python -m repro.launch.train --arch llama3.2-3b --steps 1000 --mesh pod

Features exercised: sharded train step (DP×TP), grad accumulation, BRDS
masked sparse training (--brds), checkpoint/restart (auto-resume), fault
injection (--inject-failure-at), straggler monitoring.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--brds", action="store_true",
                    help="apply BRDS dual-ratio masks and retrain")
    ap.add_argument("--spar-a", type=float, default=0.75)
    ap.add_argument("--spar-b", type=float, default=0.5)
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="raise at this step once (tests restart path)")
    args = ap.parse_args()

    from repro.configs import get_arch, smoke_config
    from repro.models import build_model
    from repro.training import (OptConfig, init_state, make_train_step,
                                jit_train_step, ZipfInduction, ShardedLoader,
                                CheckpointManager, StragglerMonitor)
    from repro.sparse import transformer_policy

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={model.param_count()/1e6:.1f}M "
          f"layers={cfg.num_layers}")

    rng = jax.random.key(0)
    params = model.init(rng)
    oc = OptConfig(lr=args.lr, total_steps=args.steps,
                   warmup_steps=max(args.steps // 20, 1))
    opt_state = init_state(oc, params)

    masks = None
    if args.brds:
        plan = transformer_policy(args.spar_a, args.spar_b).compile(params)
        params, masks = plan.prune(params)
        print("BRDS:", plan.summary(masks))

    if args.mesh == "host":
        step_fn = jax.jit(make_train_step(model, cfg, oc, masks))
    else:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)}
        with mesh:
            step_fn = jit_train_step(mesh, model, cfg, oc, batch_abs, masks)

    ds = ZipfInduction(vocab_size=cfg.vocab_size)
    loader = ShardedLoader(ds, args.batch, args.seq)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    mon = StragglerMonitor()

    start = 0
    latest = ckpt.latest_step()
    if latest is not None:
        (params, opt_state), meta = ckpt.restore((params, opt_state))
        start = meta["step"]
        print(f"resumed from checkpoint at step {start}")

    injected = [False]
    t_all = time.time()
    for step in range(start, args.steps):
        if step == args.inject_failure_at and not injected[0]:
            injected[0] = True
            print(f"!! injecting failure at step {step}; restarting from "
                  f"checkpoint")
            latest = ckpt.latest_step()
            if latest is not None:
                (params, opt_state), meta = ckpt.restore((params, opt_state))
                step = meta["step"]
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in loader.batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.int32(step))
        dt = time.time() - t0
        straggler = mon.record(dt)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms"
                  + (" [straggler]" if straggler else ""))
        if (step + 1) % args.save_every == 0:
            ckpt.save(step + 1, (params, opt_state))
    ckpt.wait()
    print(f"done in {time.time()-t_all:.1f}s; straggler events: {mon.flagged}")


if __name__ == "__main__":
    main()
