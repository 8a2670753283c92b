"""The Granite 4.0-H hybrid (Mamba-2 + NoPE attention) at ``smoke_config``
size on the CPU, against the plain reference ``bench/reference/
granite_hybrid.py`` on the benchmark's seeded weight recipe: forward,
prefill then cached decode, bucketed ``length=`` prefill, packed decode,
the Mamba-2 state kernel in interpret mode, and greedy serving through the
continuous-batching scheduler.

Tolerances. The program and the reference both compute in float32 and
differ only in the order of their sums (the SSM read-out, the packed
gather, attention's blocks), which moves a logit by ~1e-6 of its scale at
these widths; ``TOL`` (2e-5 of the largest |logit|) leaves ten times that.
A bfloat16 computation moves the logits by ~1e-2 of their scale and fails
it (``test_bf16_reference_fails_the_tolerance``). Paths that must agree
row for row — bucketed against exact prefill, packed against masked
dense — are held to 1e-5 absolute on states and logits of size O(10).
Those two are also run on a RoPE model with qk_norm and padded heads
(``ROPE``), whose attention takes the same paths.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import hybrid_lm  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402
from repro.configs import smoke_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import (ContinuousBatchingEngine, ServeEngine,  # noqa: E402
                           prefill_accepts_length)
from repro.sparse import transformer_policy  # noqa: E402

NAME = "granite-4.0-h-micro"
TOL = 2e-5
ROW_TOL = 1e-5


def bench_cfg(arch) -> dict:
    """The benchmark configuration's keys at the smoke widths."""
    kinds = {"mamba": "mamba", "attn": "attention"}
    return {
        "arch": NAME, "num_hidden_layers": arch.num_layers,
        "hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
        "vocab_size": arch.vocab_size,
        "num_attention_heads": arch.num_heads,
        "num_key_value_heads": arch.num_kv_heads,
        "layer_types": [kinds[k] for k in arch.block_pattern]
        * (arch.num_layers // len(arch.block_pattern)),
        "mamba_n_heads": arch.mamba_heads,
        "mamba_d_head": arch.mamba_head_dim,
        "mamba_d_state": arch.mamba_d_state,
        "mamba_n_groups": arch.mamba_groups, "mamba_d_conv": arch.conv_width,
        "rms_norm_eps": arch.norm_eps,
        "attention_multiplier": arch.attention_multiplier,
        "embedding_multiplier": arch.embedding_multiplier,
        "residual_multiplier": arch.residual_multiplier,
        "logits_scaling": arch.logits_scaling,
        "sparsity": {"spar_a": 0.75, "spar_b": 0.5},
    }


@pytest.fixture(scope="module")
def setup():
    arch = smoke_config(NAME).with_(num_layers=10)     # one whole period
    cfg = bench_cfg(arch)
    model = build_model(hybrid_lm.model_config(cfg))
    params = hybrid_lm.make_params(cfg, model.cfg, 3)
    rparams = hybrid_lm.reference_params(params, cfg)
    return cfg, model, params, rparams


@pytest.fixture(scope="module")
def jitted(setup):
    """(prefill, decode_step), compiled once for the module."""
    _, model, _, _ = setup
    return (jax.jit(model.prefill, static_argnames="max_len"),
            jax.jit(model.decode_step))


@pytest.fixture(scope="module")
def packed(setup):
    """ServeEngine.prepare's packed weights and its report."""
    _, model, params, _ = setup
    eng = ServeEngine(model, None, max_len=32, batch=2,
                      sparsity=transformer_policy(0.75, 0.5))
    return eng.prepare(params)


# a RoPE model with qk_norm and head padding (4 query heads padded to 6)
# takes the same length= prefill and packed projections as the hybrid
ROPE = "qwen3-0.6b"


@pytest.fixture(scope="module", params=[NAME, ROPE])
def served(request):
    """(model, dense weights, (prefill, decode_step) jitted,
    ServeEngine.prepare's (packed, report)) for each model the length= and
    packed paths are checked on."""
    if request.param == NAME:
        _, model, params, _ = request.getfixturevalue("setup")
        return (model, params, request.getfixturevalue("jitted"),
                request.getfixturevalue("packed"))
    model = build_model(smoke_config(ROPE).with_(pad_heads_to=6))
    params = model.init(jax.random.key(6))
    eng = ServeEngine(model, None, max_len=32, batch=2,
                      sparsity=transformer_policy(0.75, 0.5))
    return (model, params, (jax.jit(model.prefill, static_argnames="max_len"),
                            jax.jit(model.decode_step)), eng.prepare(params))


def _ref_logits(rparams, tokens, spec, dtype):
    hs = ref.hidden(rparams, tokens, spec, dtype)
    return ref.logits(rparams, hs, spec, dtype)


_REF = {}


def ref_logits(cfg, rparams, tokens, dtype=jnp.float32):
    """The reference's logits at every position (float32 at HIGHEST)."""
    key = jnp.dtype(dtype).name
    if key not in _REF:
        _REF[key] = jax.jit(lambda p, t: _ref_logits(
            p, t, hybrid_lm.spec(cfg), dtype))
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF[key](rparams, jnp.asarray(tokens)))


def tokens_for(cfg, B, S, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], size=(B, S)).astype(np.int32)


def assert_close(got, want, tol=TOL):
    err = float(np.max(np.abs(np.asarray(got) - want)))
    assert err <= tol * float(np.max(np.abs(want))), err


def test_config_is_registered_at_published_values():
    from repro.configs import get_arch
    c = get_arch(NAME)
    assert (c.num_layers, c.d_model, c.d_ff, c.vocab_size, c.num_heads,
            c.num_kv_heads, c.head_dim) == (40, 2048, 8192, 100352, 32, 8,
                                            64)
    assert (c.mamba_heads, c.mamba_head_dim, c.mamba_d_state,
            c.mamba_groups, c.conv_width) == (64, 64, 128, 1, 4)
    assert c.block_pattern == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert (c.embedding_multiplier, c.attention_multiplier,
            c.residual_multiplier, c.logits_scaling, c.norm_eps) == (
        12.0, 0.015625, 0.22, 8.0, 1e-5)
    assert not c.use_rope and c.tie_embeddings
    smoke = smoke_config(NAME)
    assert smoke.block_pattern == c.block_pattern
    assert smoke.num_layers % len(c.block_pattern) == 0


def test_forward_matches_reference(setup):
    cfg, model, params, rparams = setup
    toks = tokens_for(cfg, 2, 24)
    logits, _ = jax.jit(model.forward)(params, jnp.asarray(toks))
    assert_close(logits[..., :cfg["vocab_size"]],
                 ref_logits(cfg, rparams, toks))


def test_bf16_reference_fails_the_tolerance(setup):
    cfg, _, _, rparams = setup
    toks = tokens_for(cfg, 2, 24)
    want = ref_logits(cfg, rparams, toks)
    low = ref_logits(cfg, rparams, toks, jnp.bfloat16)
    err = float(np.max(np.abs(low - want)))
    assert err > 10 * TOL * float(np.max(np.abs(want))), err


def test_prefill_then_decode_matches_reference(setup, jitted):
    cfg, model, params, rparams = setup
    pf, step = jitted
    B, S, P = 2, 24, 8
    toks = tokens_for(cfg, B, S, seed=1)
    want = ref_logits(cfg, rparams, toks)
    lp, cache = pf(params, jnp.asarray(toks[:, :P]), max_len=32)
    got = [lp[:, 0]]
    for t in range(P, S):
        lg, cache = step(params, cache, jnp.asarray(toks[:, t:t + 1]),
                         jnp.full((B,), t, jnp.int32))
        got.append(lg[:, 0])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    assert_close(got[..., :cfg["vocab_size"]], want[:, P - 1:])


def test_length_prefill_matches_exact_prefill(served):
    model, params, jitted, _ = served
    assert prefill_accepts_length(model)
    pf = jitted[0]
    W, lengths = 16, (5, 11)
    toks = tokens_for({"vocab_size": model.cfg.vocab_size}, 2, W, seed=2)
    lg, cache = pf(params, jnp.asarray(toks), max_len=32,
                   length=jnp.asarray(lengths, jnp.int32))
    for b, n in enumerate(lengths):
        lr, cr = pf(params, jnp.asarray(toks[b:b + 1, :n]), max_len=32)
        np.testing.assert_allclose(lg[b], lr[0], atol=ROW_TOL)
        for j, (blk, blk_r) in enumerate(zip(cache["blocks"],
                                             cr["blocks"])):
            mix, mix_r = blk["mix"], blk_r["mix"]
            if "ssm" in mix:
                np.testing.assert_allclose(mix["ssm"][:, b], mix_r["ssm"][:, 0],
                                           atol=ROW_TOL)
                np.testing.assert_allclose(mix["conv"][:, b],
                                           mix_r["conv"][:, 0], atol=ROW_TOL)
            else:
                for kv in ("k", "v"):
                    np.testing.assert_allclose(mix[kv][:, b, :n],
                                               mix_r[kv][:, 0, :n],
                                               atol=ROW_TOL)


def test_packed_decode_matches_masked_dense(served):
    model, params, jitted, (packed, report) = served
    assert model.supports_packed_decode
    leaves = jax.tree_util.tree_flatten_with_path(
        packed, is_leaf=lambda v: hasattr(v, "deltas"))[0]
    kinds = {str(p[-1]) for p, v in leaves if hasattr(v, "deltas")}
    want = {"['wq']", "['wk']", "['wv']", "['wo']", "['w_down']"}
    if "mamba" in model.cfg.block_pattern:
        want |= {"['w_inproj']", "['w_outproj']"}
    assert want <= kinds
    assert report["gather_visit_share"]
    pruned, _ = transformer_policy(0.75, 0.5).compile(params).prune(params)
    B, P, S = 2, 8, 12
    toks = jnp.asarray(tokens_for({"vocab_size": model.cfg.vocab_size},
                                  B, S, seed=3))
    pf, step = jitted
    (lp, cp), (ld, cd) = (pf(packed, toks[:, :P], max_len=32),
                          pf(pruned, toks[:, :P], max_len=32))
    np.testing.assert_allclose(lp, ld, atol=ROW_TOL)
    for t in range(P, S):
        pos = jnp.full((B,), t, jnp.int32)
        lp, cp = step(packed, cp, toks[:, t:t + 1], pos)
        ld, cd = step(pruned, cd, toks[:, t:t + 1], pos)
        np.testing.assert_allclose(lp, ld, atol=ROW_TOL)


def test_mamba2_ssm_step_interpret_matches_twin():
    from repro.kernels import ops
    from repro.kernels.ref import mamba2_ssm_step_ref
    B, H, P, N, G = 3, 8, 16, 32, 2
    k = jax.random.split(jax.random.key(4), 7)
    state = jax.random.normal(k[0], (B, H, P, N))
    x = jax.random.normal(k[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)))
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    Bm = jax.random.normal(k[4], (B, G, N))
    Cm = jax.random.normal(k[5], (B, G, N))
    D = jax.random.normal(k[6], (H,))
    s, y = ops.mamba2_ssm_step(state, x, dt, A, Bm, Cm, D, backend="pallas")
    sr, yr = mamba2_ssm_step_ref(state, x, dt, A, Bm, Cm, D)
    # the same products and sums; only the read-out's lane sum may
    # associate differently
    np.testing.assert_allclose(s, sr, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, yr, rtol=1e-5, atol=1e-5)


def test_greedy_serving_matches_reference_greedy(setup, packed):
    """Served tokens are the reference's greedy picks, token by token (the
    reference runs the whole sequence, right-padded to a fixed width, which
    a causal model's earlier positions do not see)."""
    cfg, model, _, rparams = setup
    sched = ContinuousBatchingEngine(model, packed[0], slots=2, max_len=32,
                                     chunk=2)
    prompts = [tokens_for(cfg, 1, n, seed=10 + n)[0] for n in (5, 6, 7)]
    uids = [sched.submit(p, 5) for p in prompts]
    got = sched.run()
    W = 24
    for uid, p in zip(uids, prompts):
        seq = list(p)
        for _ in range(5):
            row = np.zeros((2, W), np.int32)
            row[0, :len(seq)] = seq
            seq.append(int(np.argmax(
                ref_logits(cfg, rparams, row)[0, len(seq) - 1,
                                              :cfg["vocab_size"]])))
        assert list(got[uid]) == seq[len(p):], uid


def test_models_that_cannot_mask_keep_the_exact_length_path():
    for name in ("recurrentgemma-9b", "rwkv6-7b", "granite-moe-1b-a400m"):
        model = build_model(smoke_config(name))
        assert not prefill_accepts_length(model), name
        sched = ContinuousBatchingEngine(model, model.init(jax.random.key(0)),
                                         slots=2, max_len=16, chunk=2)
        assert not sched._length_aware, name


def test_rwkv_packed_projection_unchanged():
    """RWKV's packed ``_proj`` now goes through ``kernels.ops.rb_project``;
    on the CPU it gives bitwise what its own gather (B·S, rows, K) gave."""
    from repro.core.packing import pack_from_dense
    from repro.models import recurrent as R
    k = jax.random.split(jax.random.key(5), 2)
    w = jax.random.normal(k[0], (48, 4, 8))                # (d, H, Dk)
    x = jax.random.normal(k[1], (2, 3, 48))
    packed = pack_from_dense(w.reshape(48, -1).T, 0.5)     # rows = H·Dk
    cols = jnp.cumsum(packed.deltas.astype(jnp.int32), axis=1)
    g = jnp.take(x.reshape(6, 48), cols, axis=1)
    old = jnp.einsum("brk,rk->br", g, packed.values).reshape(2, 3, -1)
    np.testing.assert_array_equal(R._proj(x, packed), old)
