"""decode_throughput: serving tok/s through the unified runtime.

Measures the paper's LSTM LM on this host (jnp ref formulations — Pallas
interpret mode measures Python, not hardware) across the serving matrix:

  dense  × lockstep        — ServeEngine on dense weights
  packed × lockstep        — ServeEngine on SparsityPlan.pack'd weights
                             (rb_dual_spmv + lstm_gates datapath)
  packed × python-loop     — the pre-runtime per-token host loop, for the
                             dispatch-overhead comparison
  packed × continuous      — ContinuousBatchingEngine over ragged requests
  packed × delta           — temporal delta sparsity (Θ=0.1) on top of the
                             packed weights; the derived column reports the
                             effective-ops reduction (fired-column MACs vs.
                             always-on packed MACs)
  packed × sharded         — repro.dist row-sharded decode over (data, model)
                             meshes of 8 FORCED host devices (a subprocess
                             sets --xla_force_host_platform_device_count; the
                             numbers track Python/dispatch overhead of the
                             sharded path, not real interconnects)
  packed × chained/fused   — the ISSUE-7 comparison: chained per-kernel
                             decode vs the single-launch fused step, each
                             with the v5e HBM-roofline bound (B·BW /
                             per-step packed bytes) and its roofline_gap
  fused step vs scan       — kernel-level: T separate fused-step launches
                             vs one in-kernel scan launch at T ∈ {1, 8, 32}
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from repro.models import LSTMModel
from repro.serving import (ServeEngine, ContinuousBatchingEngine,
                          SamplingConfig)
from repro.sparse import (DeltaGateConfig, lstm_policy, occupancy_report,
                          use_backend)
from .common import (bench_lstm_cfg, bench_lstm_dims, row, smoke,
                     time_fn as _time)

B, P, G = bench_lstm_dims()


def main():
    cfg = bench_lstm_cfg()
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    plan = lstm_policy(0.875, 0.75, backend="ref").compile(params)
    pruned, masks = plan.prune(params)
    packed, pack_report = plan.pack(pruned, masks)
    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    eng = ServeEngine(model, cfg, max_len=P + G, batch=B)

    with use_backend("ref"):
        toks = B * G
        t = _time(lambda: eng.generate(params, prompt, G))
        row("decode_dense_lockstep", t / toks * 1e6,
            f"toks_per_s={toks / t:.0f}")
        t = _time(lambda: eng.generate(packed, prompt, G))
        row("decode_packed_lockstep", t / toks * 1e6,
            f"toks_per_s={toks / t:.0f}")

        # temporal delta sparsity composed with the packed weights
        deng = ServeEngine(model, cfg, max_len=P + G, batch=B,
                           sparsity=lstm_policy(
                               0.875, 0.75,
                               delta=DeltaGateConfig(theta_x=0.1,
                                                     theta_h=0.1)))
        dpacked, _ = deng.prepare(params)
        # return_state only changes the Python-side return, not the
        # compiled computation — time it directly and reuse the state
        dstate = {}
        def delta_run():
            toks, st = deng.generate(dpacked, prompt, G, return_state=True)
            dstate.update(st)
            return toks
        t = _time(delta_run)
        occ = occupancy_report(dstate["cache"], steps=P + G, packed=dpacked)
        row("decode_packed_delta_lockstep", t / toks * 1e6,
            f"toks_per_s={toks / t:.0f} "
            f"eff_ops_reduction={occ['ops_reduction']:.2f}x")

        # pre-runtime baseline: one host dispatch per token
        dstep = jax.jit(model.decode_step)

        def pyloop():
            lp, cache = eng._prefill(packed, prompt, max_len=P + G)
            out = None
            for i in range(G):
                out = jnp.argmax(lp[:, -1], -1)[:, None].astype(jnp.int32)
                lp, cache = dstep(packed, cache, out, P + i)
            return out

        t = _time(pyloop)
        row("decode_packed_pyloop", t / toks * 1e6,
            f"toks_per_s={toks / t:.0f}")

        def continuous():
            sched = ContinuousBatchingEngine(model, packed, slots=4,
                                             max_len=P + G,
                                             sampling=SamplingConfig(),
                                             chunk=8)
            for i in range(B):
                plen = 4 + (3 * i) % P
                pr = jax.random.randint(jax.random.key(10 + i), (1, plen),
                                        0, cfg.vocab_size)
                sched.submit(pr, G)
            return sched.run()

        # budgets are capped at the cache capacity left after each prompt,
        # so count the actually emitted tokens (the count run doubles as
        # warmup for the timed run)
        emitted = sum(len(v) for v in continuous().values())
        t = _time(continuous, warmup=0, iters=1)
        row("decode_packed_continuous", t / emitted * 1e6,
            f"toks_per_s={emitted / t:.0f} ragged_over_4_slots")

    # ---- chained vs fused single-launch decode (ISSUE 7), on the Pallas
    # kernels (the ref twins are structurally identical between the two
    # paths — only the kernel datapath exposes the launch difference:
    # 2 pallas_calls per layer-step chained vs 1 fused). Each row carries
    # its distance from the HBM roofline: every decoded token streams all
    # packed weight bytes, so the bound is B·BW/bytes. Longer decode +
    # more iters than the rows above keep per-launch overhead above the
    # wall-clock noise of a shared CPU host.
    from repro import hw
    bw = hw.peaks(hw.TARGET_KIND).hbm_bytes_per_s    # the v5e bound
    bound = B * bw / pack_report["packed_bytes"]
    G2 = 4 * G
    toks2 = B * G2
    ceng = ServeEngine(model.with_fused(False), cfg, max_len=P + G2,
                       batch=B)
    feng = ServeEngine(model.with_fused(True), cfg, max_len=P + G2,
                       batch=B)
    run_c = lambda: ceng.generate(packed, prompt, G2)
    run_f = lambda: feng.generate(packed, prompt, G2)
    # interleaved sampling so a host-load drift between the two
    # measurements cannot masquerade as a chained/fused difference
    for r in (run_c, run_f):
        jax.block_until_ready(r())
        jax.block_until_ready(r())
    cs, fs = [], []
    for _ in range(9):
        for r, ts in ((run_c, cs), (run_f, fs)):
            t0 = time.perf_counter()
            jax.block_until_ready(r())
            ts.append(time.perf_counter() - t0)
    t_c = sorted(cs)[len(cs) // 2]
    t_f = sorted(fs)[len(fs) // 2]
    row("decode_packed_chained_lockstep", t_c / toks2 * 1e6,
        f"toks_per_s={toks2 / t_c:.0f} "
        f"roofline_bound_toks_per_s={bound:.0f} "
        f"roofline_gap={bound / (toks2 / t_c):.1f}x")
    row("decode_packed_fused_lockstep", t_f / toks2 * 1e6,
        f"toks_per_s={toks2 / t_f:.0f} "
        f"roofline_bound_toks_per_s={bound:.0f} "
        f"roofline_gap={bound / (toks2 / t_f):.1f}x "
        f"speedup_vs_chained={t_c / t_f:.2f}x")

    _fused_kernel_rows()
    _sharded_rows()


# ------------------------------------------------- fused step vs scan rows
# Kernel-level launch-amortisation curve: T separate fused-step calls vs
# ONE fused_brds_lstm_scan launch covering the same T tokens. The scan
# keeps (c, h) in VMEM scratch across the token axis; its rows carry a
# weights_fit_vmem flag (both packed families within a 16 MiB working
# budget — the regime where the single launch also never re-reads weights
# from HBM between tokens).

def _fused_kernel_rows():
    from repro.core.packing import pack
    from repro.core.sparsity import row_balanced_mask
    from repro.kernels import fused_brds_lstm_step, fused_brds_lstm_scan

    cfg = bench_lstm_cfg()
    X, H = cfg.input_size, cfg.hidden
    R = 4 * H
    kx, kh, kb, ks, kc, kh0 = jax.random.split(jax.random.key(2), 6)
    wx = jax.random.normal(kx, (R, X), jnp.float32)
    wh = jax.random.normal(kh, (R, H), jnp.float32)
    sx = pack(wx, row_balanced_mask(wx, 0.875))
    sh = pack(wh, row_balanced_mask(wh, 0.75))
    bias = jax.random.normal(kb, (R,), jnp.float32)
    wbytes = sum(int(x.nbytes) for x in jax.tree.leaves((sx, sh)))
    fits = int(wbytes <= 16 * 2 ** 20)
    h0 = jax.random.normal(kh0, (B, H), jnp.float32)
    c0 = jax.random.normal(kc, (B, H), jnp.float32)
    # Pallas path on purpose (interpret on CPU): one pallas_call for the
    # whole scan vs T step launches is the structural difference being
    # measured; the ref twins of step and scan are the same eager ops.
    for T in smoke((1, 8), (1, 8, 32)):
        xs = jax.random.normal(ks, (T, B, X), jnp.float32)

        def steps():
            c, h = c0, h0
            for t in range(T):
                c, h = fused_brds_lstm_step(sx, xs[t], sh, h, bias, c)
            return h

        t_s = _time(steps)
        row(f"fused_step_T{T}", t_s / (B * T) * 1e6,
            f"toks_per_s={B * T / t_s:.0f} launches={T}")
        t_c = _time(
            lambda: fused_brds_lstm_scan(sx, xs, sh, h0, bias, c0))
        row(f"fused_scan_T{T}", t_c / (B * T) * 1e6,
            f"toks_per_s={B * T / t_c:.0f} launches=1 "
            f"weights_fit_vmem={fits} "
            f"speedup_vs_steps={t_s / t_c:.2f}x")


# ------------------------------------------------------------- sharded rows
# jax locks the device count at first init, so the sharded measurements run
# in a child process with XLA_FLAGS=--xla_force_host_platform_device_count=8
# (same pattern as tests/test_distributed.py); the parent re-emits the
# child's CSV rows so they land in BENCH_decode_throughput.json too. The
# child is pinned to the CPU (JAX_PLATFORMS=cpu): the parent has touched
# JAX, so on a chip machine it holds the chip, and its rows say so.

_MESHES = ((1, 8), (2, 4))


def _sharded_child():
    from repro.launch.mesh import make_host_mesh

    cfg = bench_lstm_cfg()
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    with use_backend("ref"):
        toks = B * G
        for d, m in _MESHES:
            eng = ServeEngine(model, cfg, max_len=P + G, batch=B,
                              sparsity=lstm_policy(0.875, 0.75),
                              mesh=make_host_mesh(d, m))
            packed, _ = eng.prepare(params)
            t = _time(lambda: eng.generate(packed, prompt, G))
            row(f"decode_packed_sharded_mesh{d}x{m}", t / toks * 1e6,
                f"toks_per_s={toks / t:.0f} devices=8 platform="
                f"{jax.devices()[0].platform}")


def _sharded_rows():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH", "")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.decode_throughput",
         "--sharded-child"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError("sharded decode benchmark child failed:\n"
                           + out.stderr[-2000:])
    for line in out.stdout.splitlines():
        parts = line.split(",", 2)
        if len(parts) == 3 and parts[0].startswith("decode_packed_sharded"):
            row(parts[0], float(parts[1]), parts[2])


if __name__ == "__main__":
    if "--sharded-child" in sys.argv:
        _sharded_child()
    else:
        main()
