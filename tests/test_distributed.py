"""Distributed correctness on simulated multi-device meshes.

jax locks the device count at first init, so each scenario runs in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count set.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """(data=2, model=2) sharded train step == single-device step."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.training import (OptConfig, init_state, make_train_step,
                                jit_train_step)
    from repro.launch.mesh import make_host_mesh

    cfg = smoke_config('llama3.2-3b')
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    oc = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    st = init_state(oc, params)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    batch = {'tokens': tokens, 'labels': tokens}

    p_ref, st_ref, m_ref = jax.jit(make_train_step(model, cfg, oc))(
        params, st, batch, jnp.int32(0))

    mesh = make_host_mesh(data=2, model=2)
    batch_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    with mesh:
        step = jit_train_step(mesh, model, cfg, oc, batch_abs, donate=False)
        p_sh, st_sh, m_sh = step(params, st, batch, jnp.int32(0))
    assert abs(float(m_ref['loss']) - float(m_sh['loss'])) < 1e-4, \\
        (float(m_ref['loss']), float(m_sh['loss']))
    d = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
    assert d < 2e-3, d
    print('parity ok', d)
    """)


def test_compressed_psum_matches_exact():
    """int8 compressed all-reduce ≈ exact mean across 8 shards; error
    feedback keeps the running sum unbiased."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.training.compression import compressed_psum
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ('data',))
    g = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)), jnp.float32)

    def f(gl, res):
        mean, new_res = compressed_psum(gl[0], 'data', res[0])
        return mean[None], new_res[None]

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P('data'), P('data')),
                       out_specs=(P('data'), P('data')))
    res = jnp.zeros((8, 64), jnp.float32)
    mean_c, res = sm(g, res)
    exact = jnp.mean(g, axis=0)
    # every shard holds the same mean; compare with exact
    err = float(jnp.abs(mean_c[0] - exact).max())
    scale = float(jnp.abs(g).max()) / 127.0
    assert err <= scale + 1e-6, (err, scale)
    print('compressed psum ok', err)
    """)


def test_elastic_restore_across_mesh_sizes():
    """Checkpoint written under an 8-device mesh restores onto a 4-device
    mesh (elastic scale-down) with identical values."""
    _run("""
    import os, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.training import CheckpointManager
    from repro.training.train_loop import param_shardings
    from repro.launch.mesh import make_host_mesh

    cfg = smoke_config('qwen3-0.6b')
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    tmp = tempfile.mkdtemp()
    mesh8 = make_host_mesh(data=2, model=4)
    sh8 = param_shardings(mesh8, model)
    p8 = jax.tree.map(lambda a, s: jax.device_put(a, s), params, sh8)
    ck = CheckpointManager(tmp, async_save=False)
    ck.save(3, p8)

    mesh4 = make_host_mesh(data=2, model=2)
    sh4 = param_shardings(mesh4, model)
    from repro.training.fault import elastic_restore
    p4, meta = elastic_restore(ck, params, sh4)
    assert meta['step'] == 3
    d = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(params), jax.tree.leaves(p4)))
    assert d == 0.0, d
    print('elastic restore ok')
    """, devices=8)


def test_decode_step_sharded_matches_host():
    """Sharded decode (split-KV cache) == unsharded decode."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.training.train_loop import param_shardings
    from repro.serving.engine import cache_shardings

    cfg = smoke_config('llama3.2-3b')
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S, MAX = 4, 12, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    lp, cache = model.prefill(params, tokens, MAX)
    lg_ref, _ = model.decode_step(params, cache, tokens[:, :1], S)

    mesh = make_host_mesh(data=2, model=4)
    p_sh = param_shardings(mesh, model)
    c_sh = cache_shardings(mesh, model, B, MAX)
    from jax.sharding import NamedSharding, PartitionSpec as P
    with mesh:
        fn = jax.jit(model.decode_step,
                     in_shardings=(p_sh, c_sh,
                                   NamedSharding(mesh, P('data')),
                                   NamedSharding(mesh, P())))
        lg_sh, _ = fn(params, cache, tokens[:, :1], S)
    err = float(jnp.abs(lg_ref - lg_sh).max())
    assert err < 1e-3, err
    print('sharded decode ok', err)
    """)
