"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, output shapes + no NaNs; decode path consistency vs full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config, ARCH_NAMES, get_arch, SHAPES, runnable
from repro.models import build_model
from repro.training import OptConfig, init_state, make_train_step


def _batch_for(cfg, B, S, rng):
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    extra = None
    if cfg.encdec:
        extra = jax.random.normal(rng, (B, 16, cfg.d_model), dtype=jnp.float32)
        batch["frames"] = extra
    elif cfg.num_patches:
        extra = jax.random.normal(rng, (B, cfg.num_patches, cfg.d_model),
                                  dtype=jnp.float32)
        batch["patch_embeds"] = extra
    return batch, extra


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_and_train_step(name):
    cfg = smoke_config(name)
    model = build_model(cfg)
    rng = jax.random.key(0)
    params = model.init(rng)
    B, S = 2, 32
    batch, extra = _batch_for(cfg, B, S, rng)
    if cfg.encdec:
        logits, aux = model.forward(params, batch["tokens"], batch["frames"])
    elif cfg.num_patches:
        logits, aux = model.forward(params, batch["tokens"],
                                    batch["patch_embeds"])
    else:
        logits, aux = model.forward(params, batch["tokens"])
    assert logits.shape == (B, S, model.vocab_padded)
    assert bool(jnp.isfinite(logits).all()), "NaN/inf in logits"
    oc = OptConfig(lr=1e-3, total_steps=10, warmup_steps=1)
    st = init_state(oc, params)
    step = jax.jit(make_train_step(model, cfg, oc))
    params, st, metrics = step(params, st, batch, jnp.int32(0))
    assert bool(jnp.isfinite(metrics["loss"])), "NaN loss"
    assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_matches_forward(name):
    cfg = smoke_config(name)
    if cfg.moe:
        cfg = cfg.with_(capacity_factor=8.0)   # no-drop → paths identical
    model = build_model(cfg)
    rng = jax.random.key(1)
    params = model.init(rng)
    B, S = 2, 24
    batch, extra = _batch_for(cfg, B, S, rng)
    tokens = batch["tokens"]
    if cfg.encdec:
        full, _ = model.forward(params, tokens, batch["frames"])
        lp, cache = model.prefill(params, tokens[:, :S - 3], S,
                                  extra=batch["frames"])
    elif cfg.num_patches:
        full, _ = model.forward(params, tokens, batch["patch_embeds"])
        lp, cache = model.prefill(params, tokens[:, :S - 3], S,
                                  extra=batch["patch_embeds"])
    else:
        full, _ = model.forward(params, tokens)
        lp, cache = model.prefill(params, tokens[:, :S - 3], S)
    errs = [float(jnp.abs(lp[:, -1] - full[:, S - 4]).max())]
    for i in range(3):
        lg, cache = model.decode_step(params, cache,
                                      tokens[:, S - 3 + i:S - 2 + i], S - 3 + i)
        errs.append(float(jnp.abs(lg[:, 0] - full[:, S - 3 + i]).max()))
    assert max(errs) < 5e-4, f"decode diverges from forward: {errs}"


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_matches_assignment(name):
    """The registered full configs carry the exact assigned hyperparams."""
    spec = {
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "nemotron-4-340b": (96, 18432, 96, 8, 73728, 256000),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    cfg = get_arch(name)
    L, d, H, kv, ff, V = spec[name]
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (L, d, H, kv, ff, V)


def test_long_500k_applicability():
    """Only sub-quadratic archs run long_500k; 8 N/A cells documented."""
    na = [n for n in ARCH_NAMES
          if not runnable(get_arch(n), SHAPES["long_500k"])[0]]
    assert len(na) == 8
    assert "rwkv6-7b" not in na and "recurrentgemma-9b" not in na


def test_brds_masked_training_on_transformer():
    """BRDS dual-ratio masks freeze pruned transformer weights."""
    from repro.training import brds_masks, sparsity_report
    from repro.training.masked import apply_masks, _path_str
    cfg = smoke_config("minitron-8b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    masks = brds_masks(params, 0.875, 0.5)
    rep = sparsity_report(params, masks)
    assert 0.5 < rep["sparsity"] < 0.875
    params = apply_masks(params, masks)
    oc = OptConfig(lr=1e-2, total_steps=10, warmup_steps=1)
    st = init_state(oc, params)
    step = jax.jit(make_train_step(model, cfg, oc, masks=masks))
    rng = jax.random.key(2)
    batch, _ = _batch_for(cfg, 2, 16, rng)
    for i in range(2):
        params, st, _ = step(params, st, batch, jnp.int32(i))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        ps = _path_str(path)
        if ps in masks:
            assert bool(jnp.all(jnp.where(masks[ps], True, leaf == 0))), \
                f"pruned weights drifted in {ps}"


@pytest.mark.parametrize("name", ["llama3.2-3b", "recurrentgemma-9b"])
def test_int8_kv_cache_close_to_bf16(name):
    """Beyond-paper: int8 KV cache (BRDS quantization axis) stays within
    quantization tolerance of the bf16 decode path."""
    cfg = smoke_config(name)
    model = build_model(cfg)
    modelq = build_model(cfg.with_(kv_quant=True))
    params = model.init(jax.random.key(0))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    lp, c = model.prefill(params, toks[:, :S - 1], S)
    lpq, cq = modelq.prefill(params, toks[:, :S - 1], S)
    lg, _ = model.decode_step(params, c, toks[:, S - 1:], S - 1)
    lgq, _ = modelq.decode_step(params, cq, toks[:, S - 1:], S - 1)
    rel = float(jnp.abs(lg - lgq).max() / (jnp.abs(lg).max() + 1e-9))
    assert rel < 0.08, rel
