"""Packed or dense: the choice ``ServeEngine.prepare`` makes per projection
leaf of a transformer-family model (``kernels.ops.serve_dense``, through
``TransformerLM.dense_served``).

The rule reads only the leaf's shape, the engine's slot batch and the
device kind. On the chip's numbers ("TPU v5 lite") every projection of
``granite_4_h_micro`` goes dense at the cell's 32 slots; at one row a
density-1/4 leaf stays packed and a density-1/2 leaf does not; a kind with
no measured gather rate (the CPU here) keeps every leaf packed. The
chip's choice is forced here by standing in its device kind: the
dense-served hybrid then serves the packed path's greedy tokens, its
logits agree with the packed path and with the plain reference
``bench/reference/granite_hybrid.py``, and every dot of its float32 step
runs at HIGHEST.
"""
import json
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
from repro.core.sparsity import keep_count  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import ContinuousBatchingEngine, ServeEngine  # noqa: E402
from repro.sparse import transformer_policy  # noqa: E402

V5E = "TPU v5 lite"
CONFIG = ROOT / "bench" / "configs" / "granite_4_h_micro.json"
# (rows, source columns, ratio) of each projection of granite_4_h_micro at
# its published widths; ``test_table_is_the_configs_projections`` holds it
# to the model's prunable leaves
GRANITE = {
    "mamba/w_inproj": (8512, 2048, 0.5), "mamba/w_outproj": (2048, 4096, 0.5),
    "mlp/w_gate": (8192, 2048, 0.75), "mlp/w_up": (8192, 2048, 0.75),
    "mlp/w_down": (2048, 8192, 0.75), "attn/wq": (2048, 2048, 0.5),
    "attn/wk": (512, 2048, 0.5), "attn/wv": (512, 2048, 0.5),
    "attn/wo": (2048, 2048, 0.5)}
# the smoke widths of one whole period (ten layers)
TINY = dict(hidden_size=64, intermediate_size=128, vocab_size=256,
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=16)
ROW_TOL = 1e-5


def granite_cfg(**widths) -> dict:
    cfg = json.loads(CONFIG.read_text())
    cfg.update(widths)
    return cfg


def granite_plan(cfg):
    model = build_model(hybrid_lm.model_config(cfg))
    sp = cfg["sparsity"]
    policy = transformer_policy(sp["spar_a"], sp["spar_b"])
    return model, policy.compile(model.abstract_params())


def _leaf(path: str) -> str:
    return "/".join(path.split("/")[-2:])


# ------------------------------------------------------------------ rule

def test_table_is_the_configs_projections():
    _, plan = granite_plan(granite_cfg())
    got = {_leaf(p): (s.d_out, s.d_in, s.rule.ratio)
           for p, s in plan.sites.items()}
    assert got == GRANITE


@pytest.mark.parametrize("leaf", sorted(GRANITE))
def test_every_granite_projection_serves_dense_at_32_slots(leaf):
    rows, ncols, ratio = GRANITE[leaf]
    K = keep_count(ncols, ratio)
    assert ops.serve_dense(rows, ncols, K, 32, V5E)
    # with room to spare: the gather's work at 32 rows is ≥ 19× the time
    # of the dense bytes at either density
    g = ops.GATHER_S_PER_ENTRY_ROW[V5E]
    assert 32 * K * g * 819e9 / (ncols * 4) > 19


def test_model_picks_every_granite_leaf_at_32_slots():
    model, plan = granite_plan(granite_cfg())
    assert model.dense_served(plan, 32, V5E) == frozenset(plan.sites)


@pytest.mark.parametrize("leaf", sorted(GRANITE))
def test_one_row_keeps_quarter_density_leaves_packed(leaf):
    rows, ncols, ratio = GRANITE[leaf]
    dense = ops.serve_dense(rows, ncols, keep_count(ncols, ratio), 1, V5E)
    assert dense == (ratio == 0.5)          # density 1/2 dense, 1/4 packed


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_a_kind_with_no_rate_keeps_every_leaf_packed(kind):
    model, plan = granite_plan(granite_cfg())
    assert not model.dense_served(plan, 32, kind)
    assert not any(ops.serve_dense(r, c, keep_count(c, q), 4096, kind)
                   for r, c, q in GRANITE.values())


def test_the_rule_reads_the_leaf_dtype():
    """Narrower dense values are fewer bytes: a leaf just short of dense
    in float32 goes dense in bfloat16."""
    g, bw = ops.GATHER_S_PER_ENTRY_ROW[V5E], 819e9
    rows, ncols, K = 64, 4096, 1024
    n = int(ncols * 4 / bw / (K * g))       # just under the float32 point
    assert not ops.serve_dense(rows, ncols, K, n, V5E)
    assert ops.serve_dense(rows, ncols, K, n, V5E, itemsize=2)


# -------------------------------------------------- the served hybrid

@pytest.fixture(scope="module")
def hybrid():
    cfg = granite_cfg(**TINY)
    model = build_model(hybrid_lm.model_config(cfg))
    params = hybrid_lm.make_params(cfg, model.cfg, 7)
    return cfg, model, params


def _prepare(model, params, batch, kind=None, monkeypatch=None):
    if kind is not None:
        monkeypatch.setattr(ops, "device_kind", lambda: kind)
    eng = ServeEngine(model, None, max_len=32, batch=batch,
                      sparsity=transformer_policy(0.75, 0.5))
    return eng.prepare(params)


@pytest.fixture(scope="module")
def served(hybrid):
    """(packed, its report), (dense-served, its report) for two slots: the
    CPU's choice and the chip's."""
    _, model, params = hybrid
    with pytest.MonkeyPatch.context() as mp:
        chip = _prepare(model, params, 2, V5E, mp)
    return _prepare(model, params, 2), chip


def _packed_leaves(tree):
    return [v for v in jax.tree.leaves(
        tree, is_leaf=lambda v: hasattr(v, "deltas")) if hasattr(v, "deltas")]


def test_report_reads_zero_visits_for_dense_leaves(served):
    (packed, rep_p), (dense, rep_d) = served
    assert _packed_leaves(packed) and not _packed_leaves(dense)
    assert rep_p["dense_served"] == 0.0
    assert min(rep_p["gather_visit_share"].values()) > 0.0
    assert rep_d["dense_served"] == 1.0
    assert set(rep_d["gather_visit_share"]) == set(
        rep_p["gather_visit_share"])
    assert set(rep_d["gather_visit_share"].values()) == {0.0}
    assert rep_d["packed_bytes"] == rep_d["dense_bytes"]


def test_one_slot_serves_a_mix(hybrid, monkeypatch):
    """At one row on the chip's numbers the MLP (density 1/4) stays
    packed and the mixers (1/2) go dense: the report says which."""
    _, model, params = hybrid
    tree, rep = _prepare(model, params, 1, V5E, monkeypatch)
    shares = rep["gather_visit_share"]
    for path, share in shares.items():
        assert (share > 0.0) == ("/mlp/" in path), path
    kinds = {type(v).__name__ for v in _packed_leaves(tree)}
    assert kinds == {"RowBalancedSparse"}
    assert 0.0 < rep["dense_served"] < 1.0


def test_dense_served_matches_packed_and_reference(hybrid, served):
    cfg, model, _ = hybrid
    (packed, _), (dense, _) = served
    pf = jax.jit(model.prefill, static_argnames="max_len")
    step = jax.jit(model.decode_step)
    B, P, S = 2, 6, 12
    toks = np.random.default_rng(8).integers(
        0, cfg["vocab_size"], size=(B, S)).astype(np.int32)
    runs = []
    for w in (packed, dense):
        lg, cache = pf(w, jnp.asarray(toks[:, :P]), max_len=32)
        out = [lg[:, 0]]
        for t in range(P, S):
            lg, cache = step(w, cache, jnp.asarray(toks[:, t:t + 1]),
                             jnp.full((B,), t, jnp.int32))
            out.append(lg[:, 0])
        runs.append(np.stack([np.asarray(o) for o in out], 1)
                    [..., :cfg["vocab_size"]])
    np.testing.assert_allclose(runs[1], runs[0], atol=ROW_TOL)
    _, _, params = hybrid
    rparams = hybrid_lm.reference_params(params, cfg)
    spec = hybrid_lm.spec(cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(
            rparams, ref.hidden(rparams, jnp.asarray(toks), spec,
                                jnp.float32), spec, jnp.float32))
    np.testing.assert_allclose(runs[1], want[:, P - 1:], atol=ROW_TOL)


def test_dense_served_greedy_tokens_match_packed(hybrid, served):
    cfg, model, _ = hybrid
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (5, 7, 4)]
    got = []
    for w, _ in served:
        sched = ContinuousBatchingEngine(model, w, slots=2, max_len=32,
                                         chunk=2)
        uids = [sched.submit(p, 6) for p in prompts]
        out = sched.run()
        got.append([list(out[u]) for u in uids])
    assert got[0] == got[1]


# ------------------------------------------------------------ precision

def _dots(jaxpr):
    """Every dot_general in a jaxpr and the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _dots(inner)


def _precisions(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [e.params["precision"] for e in _dots(jaxpr)]


def test_dense_served_step_runs_every_dot_at_highest(hybrid, served):
    """Each dense-served projection of the float32 model, like attention
    and the tied head, is a dot_general at HIGHEST."""
    _, model, _ = hybrid
    _, (dense, _) = served
    cache = model.init_cache(2, 32)
    precs = _precisions(model.decode_step, dense, cache,
                        jnp.zeros((2, 1), jnp.int32),
                        jnp.full((2,), 3, jnp.int32))
    hi = jax.lax.Precision.HIGHEST
    # nine projections in the scanned period, attention's two, the head
    assert len(precs) >= 12
    assert all(p == (hi, hi) for p in precs), precs


def test_bf16_dense_projection_keeps_default_precision():
    """A bfloat16 model's dense dots stay at the backend's default: the
    float32 precision is the model's, not the dispatch's."""
    from repro.models import layers as L
    x = jnp.ones((2, 3, 16), jnp.bfloat16)
    w = jnp.ones((16, 8), jnp.bfloat16)
    assert _precisions(L.project, x, w) == [None]
    hi = jax.lax.Precision.HIGHEST
    assert _precisions(lambda a, b: L.project(a, b, hi), x, w) == [(hi, hi)]
