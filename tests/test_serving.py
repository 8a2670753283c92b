"""The unified decode runtime: DecodeStep conformance, dense↔packed serving
parity, the on-device scan loop vs the old per-token Python loop, sampling,
and continuous-batching admission/eviction under ragged request lengths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model, LSTMModel, LSTMConfig
from repro.serving import (ServeEngine, ContinuousBatchingEngine,
                           SamplingConfig, conforms, sample)
from repro.sparse import lstm_policy, use_backend


@pytest.fixture(scope="module")
def lstm():
    cfg = LSTMConfig("t", input_size=16, hidden=32, num_layers=2,
                     vocab_size=50)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def transformer():
    cfg = smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_decode_contract_conformance(lstm, transformer):
    """Every served family implements cache_defs/prefill/decode_step."""
    from repro.models import EncDecLM
    from repro.configs import get_arch
    assert conforms(lstm[1])
    assert conforms(transformer[1])
    assert conforms(EncDecLM(smoke_config("seamless-m4t-medium")))
    assert not conforms(object())


def test_lstm_dense_vs_packed_serving_parity(lstm):
    """BRDS-packed params produce the same greedy tokens as dense through
    the engine — the packed rb kernels are the serve-time datapath."""
    cfg, model, params = lstm
    plan = lstm_policy(0.6, 0.4, backend="ref").compile(params)
    pruned, masks = plan.prune(params)
    packed, report = plan.pack(pruned, masks)
    assert report["packed_bytes"] < report["dense_bytes"]
    prompt = jax.random.randint(jax.random.key(1), (3, 7), 0, cfg.vocab_size)
    with use_backend("ref"):
        eng = ServeEngine(model, cfg, max_len=20, batch=3)
        out_dense = np.asarray(eng.generate(pruned, prompt, 5))
        out_packed = np.asarray(eng.generate(packed, prompt, 5))
    np.testing.assert_array_equal(out_dense, out_packed)


def test_lstm_head_dot_pins_full_precision(lstm):
    """The dense head dot is lowered at HIGHEST precision in prefill and
    decode, so on a TPU a row's logits do not depend on its batch."""
    cfg, model, params = lstm
    prompt = jnp.zeros((3, 4), jnp.int32)
    cache = model.init_cache(3, 8)
    texts = [
        jax.jit(model.prefill, static_argnames=("max_len",))
        .lower(params, prompt, max_len=8).as_text(),
        jax.jit(model.decode_step)
        .lower(params, cache, prompt[:, :1], 0).as_text(),
    ]
    for text in texts:
        head = [ln for ln in text.splitlines()
                if "dot_general" in ln and f"x{cfg.vocab_size}xf32>" in ln]
        assert head and all("precision = [HIGHEST, HIGHEST]" in ln
                            for ln in head), head


def test_engine_prepare_packs_lstm(lstm):
    """prepare() on a packed-decode model prunes AND packs."""
    from repro.core.packing import RowBalancedSparse
    cfg, model, params = lstm
    eng = ServeEngine(model, cfg, max_len=16, batch=2,
                      sparsity=lstm_policy(0.5, 0.5, backend="ref"))
    prepared, report = eng.prepare(params)
    assert isinstance(prepared["layers"][0]["w_x"], RowBalancedSparse)
    assert report["sparsity"] > 0.4
    prompt = jax.random.randint(jax.random.key(2), (2, 4), 0, cfg.vocab_size)
    with use_backend("ref"):
        out = eng.generate(prepared, prompt, 3)
    assert out.shape == (2, 3)


def test_scan_loop_matches_python_loop_and_single_dispatch(transformer):
    """The on-device scan decode reproduces the old per-token host loop
    greedily, while tracing decode_step once (no per-token host round
    trips — a Python loop would call it `steps` times)."""
    cfg, model, params = transformer
    calls = {"n": 0}
    real_step = model.decode_step

    def counting_step(p, cache, toks, pos):
        calls["n"] += 1
        return real_step(p, cache, toks, pos)

    model.decode_step = counting_step
    try:
        eng = ServeEngine(model, cfg, max_len=24, batch=2)
        prompt = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                    cfg.vocab_size)
        steps = 6
        out = np.asarray(eng.generate(params, prompt, steps))
    finally:
        model.decode_step = real_step
    assert calls["n"] == 1, "decode loop is not on-device"

    lp, cache = model.prefill(params, prompt, 24)
    ref = []
    for i in range(steps):
        nxt = jnp.argmax(lp[:, -1], -1)[:, None].astype(jnp.int32)
        ref.append(np.asarray(nxt))
        lp, cache = model.decode_step(params, cache, nxt, prompt.shape[1] + i)
    np.testing.assert_array_equal(out, np.concatenate(ref, axis=1))


def test_eos_stops_per_sequence(lstm):
    cfg, model, params = lstm
    prompt = jax.random.randint(jax.random.key(3), (2, 5), 0, cfg.vocab_size)
    eng = ServeEngine(model, cfg, max_len=20, batch=2)
    greedy = np.asarray(eng.generate(params, prompt, 6))
    eos = int(greedy[0, 2])                 # force an early stop on row 0
    out = np.asarray(eng.generate(
        params, prompt, 6,
        sampling=SamplingConfig(eos_id=eos, pad_id=-7)))
    row0 = out[0]
    hit = np.argmax(row0 == eos)
    assert row0[hit] == eos
    assert (row0[hit + 1:] == -7).all()     # padding after EOS
    # a row that never hits EOS keeps generating
    for r in range(2):
        if eos not in greedy[r]:
            assert -7 not in out[r]


def test_encdec_serves_through_engine():
    """The enc-dec joins the contract via extra= (no special-case engine
    branching)."""
    from repro.models import EncDecLM
    cfg = smoke_config("seamless-m4t-medium")
    model = EncDecLM(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, cfg, max_len=20, batch=2)
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 0, cfg.vocab_size)
    frames = jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model),
                               dtype=jnp.float32)
    out = eng.generate(params, prompt, 4, extra=frames)
    assert out.shape == (2, 4)
    assert int(out.max()) < cfg.vocab_size


def test_sampling_modes():
    rng = jax.random.key(0)
    logits = jax.random.normal(rng, (4, 32)) * 3
    greedy = sample(rng, logits, SamplingConfig())
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(jnp.argmax(logits, -1)))
    # top_k=1 is greedy regardless of temperature
    k1 = sample(rng, logits, SamplingConfig(temperature=2.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))
    # top-k sampling only ever emits top-k ids
    topk = 4
    allowed = np.asarray(jax.lax.top_k(logits, topk)[1])
    for i in range(20):
        s = sample(jax.random.key(i), logits,
                   SamplingConfig(temperature=1.0, top_k=topk))
        for b in range(4):
            assert int(s[b]) in allowed[b]


def test_top_p_sampling():
    """Nucleus sampling emits only ids inside the smallest top-p mass."""
    rng = jax.random.key(0)
    # row 0: one dominant token (p≈0.97) → top_p=0.5 must always pick it;
    # row 1: near-uniform → top_p≈1 keeps everything
    logits = jnp.stack([
        jnp.concatenate([jnp.array([6.0]), jnp.zeros(31)]),
        jnp.linspace(0.0, 0.1, 32),
    ])
    seen1 = set()
    for i in range(25):
        s = sample(jax.random.key(i), logits,
                   SamplingConfig(temperature=1.0, top_p=0.5))
        assert int(s[0]) == 0
        seen1.add(int(s[1]))
    assert len(seen1) > 1          # row 1's nucleus is wide at p=0.5
    # the nucleus is the prob-sorted prefix: with top_p=0.3 on row 1,
    # only the highest-probability ids (the tail of the linspace) survive
    probs = np.asarray(jax.nn.softmax(logits[1]))
    order = np.argsort(-probs)
    keep = order[np.cumsum(probs[order]) - probs[order] < 0.3]
    for i in range(25):
        s = sample(jax.random.key(100 + i), logits,
                   SamplingConfig(temperature=1.0, top_p=0.3))
        assert int(s[1]) in set(int(k) for k in keep)
    # top_p composes with top_k, greedy path ignores it, validation works
    s = sample(rng, logits, SamplingConfig(temperature=1.0, top_k=2,
                                           top_p=0.9))
    assert s.shape == (2,)
    np.testing.assert_array_equal(
        np.asarray(sample(rng, logits, SamplingConfig(top_p=0.5))),
        np.asarray(jnp.argmax(logits, -1)))
    with pytest.raises(ValueError):
        SamplingConfig(top_p=-0.1)


@pytest.mark.parametrize("family", ["lstm", "transformer", "hybrid"])
def test_continuous_batching_matches_lockstep(family, lstm, transformer,
                                              request):
    """Ragged prompts through 2 shared slots reproduce per-request lockstep
    decode exactly (per-slot cache positions, incl. windowed attention and
    recurrent state); slots admit from the queue and evict on completion."""
    if family == "hybrid":                  # RG-LRU + local attention
        cfg = smoke_config("recurrentgemma-9b")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
    else:
        cfg, model, params = lstm if family == "lstm" else transformer
    vocab = cfg.vocab_size
    with use_backend("ref"):
        sched = ContinuousBatchingEngine(model, params, slots=2, max_len=24,
                                         chunk=4)
        prompts, budgets = {}, {}
        for i, (plen, gen) in enumerate([(5, 6), (9, 3), (3, 7), (7, 5)]):
            p = jax.random.randint(jax.random.key(10 + i), (1, plen), 0,
                                   vocab)
            uid = sched.submit(p, gen)
            prompts[uid], budgets[uid] = p, gen
        assert sched.pending == 4           # nothing admitted before step()
        fin = sched.step()                  # admits 2, decodes one chunk
        assert sched.pending == 2
        assert len(sched.active_slots) + len(fin) == 2
        results = {f.uid: f.tokens for f in fin}
        results.update(sched.run())
        assert sched.pending == 0 and not sched.active_slots
        eng = ServeEngine(model, cfg, max_len=24, batch=1)
        for uid, p in prompts.items():
            want = np.asarray(eng.generate(params, p, budgets[uid]))[0]
            np.testing.assert_array_equal(results[uid], want)


def test_scheduler_budget_and_capacity(lstm):
    """Budgets are capped by cache capacity; oversize prompts are rejected."""
    cfg, model, params = lstm
    sched = ContinuousBatchingEngine(model, params, slots=1, max_len=12,
                                     chunk=4)
    with pytest.raises(ValueError):
        sched.submit(jnp.zeros((1, 12), jnp.int32), 4)
    uid = sched.submit(jax.random.randint(jax.random.key(0), (1, 8), 0,
                                          cfg.vocab_size), 100)
    results = sched.run()
    assert len(results[uid]) == 4           # 12 - 8 capacity, not 100


def test_packed_continuous_batching(lstm):
    """The scheduler serves SparsityPlan.pack'd LSTM params."""
    cfg, model, params = lstm
    plan = lstm_policy(0.6, 0.4, backend="ref").compile(params)
    pruned, masks = plan.prune(params)
    packed, _ = plan.pack(pruned, masks)
    with use_backend("ref"):
        sched = ContinuousBatchingEngine(model, packed, slots=2, max_len=16,
                                         chunk=4)
        uids = [sched.submit(jax.random.randint(jax.random.key(i), (1, 3 + i),
                                                0, cfg.vocab_size), 4)
                for i in range(3)]
        results = sched.run()
        eng = ServeEngine(model, cfg, max_len=16, batch=1)
        for i, uid in enumerate(uids):
            p = jax.random.randint(jax.random.key(i), (1, 3 + i), 0,
                                   cfg.vocab_size)
            want = np.asarray(eng.generate(packed, p, 4))[0]
            np.testing.assert_array_equal(results[uid], want)


def test_slot_reuse_resets_position_and_eos(lstm):
    """Evict-then-readmit into the SAME slot: the readmitted request must
    start from its own prompt's cache position with fresh EOS state (a
    slot whose previous occupant hit EOS mid-chunk must not bleed its
    done flag or cache position into the next occupant)."""
    cfg, model, params = lstm
    eng = ServeEngine(model, cfg, max_len=24, batch=1)
    p_a = jax.random.randint(jax.random.key(20), (1, 5), 0, cfg.vocab_size)
    p_b = jax.random.randint(jax.random.key(21), (1, 9), 0, cfg.vocab_size)
    greedy_a = np.asarray(eng.generate(params, p_a, 8))[0]
    # EOS := the first token of A's greedy stream that has not appeared
    # before it, inside the first chunk but not at its end (chunk=4
    # below) — so A stops exactly there, mid-chunk, whatever the stream
    stop = next(i for i in range(1, 3) if greedy_a[i] not in greedy_a[:i])
    eos = int(greedy_a[stop])
    sampling = SamplingConfig(eos_id=eos)

    sched = ContinuousBatchingEngine(model, params, slots=1, max_len=24,
                                     chunk=4, sampling=sampling)
    uid_a = sched.submit(p_a, 8)
    uid_b = sched.submit(p_b, 6)
    fin = sched.step()                      # A admitted alone (1 slot)
    assert [f.uid for f in fin] == [uid_a]  # EOS inside the first chunk
    assert sched._slot_uid[0] is None       # slot 0 evicted...
    results = {fin[0].uid: fin[0].tokens}
    results.update(sched.run())             # ...and reused by B

    # B decoded from ITS position with fresh EOS state: exact lockstep
    # parity (same eos_id so any natural EOS matches too)
    want_b = np.asarray(eng.generate(params, p_b, 6, sampling=sampling))[0]
    np.testing.assert_array_equal(results[uid_b], want_b)
    # A's tokens end at EOS and the readmit reset the slot's accounting
    assert int(results[uid_a][-1]) == eos
    assert len(results[uid_a]) == stop + 1
    assert sched.slot_steps[0] >= p_b.shape[1]  # restarted at B's join


def test_pack_preserves_zero_survivors(lstm):
    """Satellite regression: a surviving weight that is exactly zero must
    stay in the packed representation (w != 0 packing dropped it and broke
    the per-row nnz balance)."""
    cfg, model, params = lstm
    pruned, masks = model.prune(params, 0.5, 0.5)
    # zero one SURVIVING w_x weight (simulates retraining through zero)
    m0 = np.asarray(masks["layers/0/w_x"])
    r, c = np.argwhere(m0)[0]
    layers = [dict(lp) for lp in pruned["layers"]]
    layers[0]["w_x"] = layers[0]["w_x"].at[r, c].set(0.0)
    pruned = {**pruned, "layers": layers}
    # mask-less fallback keeps rows balanced (top-K re-selection)
    sx = model.pack(pruned)[0]["sx"]
    assert sx.values.shape[1] * 2 == m0.shape[1]
    # packing from the plan's masks keeps the exact zero survivor
    sx = model.pack(pruned, masks)[0]["sx"]
    assert sx.values.shape[1] * 2 == m0.shape[1]
    cols = np.asarray(sx.col_indices())
    assert c in cols[r]


def test_fused_decode_trajectory_parity(lstm):
    """ISSUE 7 parity bar: the single-launch fused decode produces a
    BITWISE-identical trajectory (tokens AND final cache) to the chained
    per-kernel path, end to end through ServeEngine's jitted decode loop."""
    cfg, _, params = lstm
    plan = lstm_policy(0.6, 0.4).compile(params)
    pruned, masks = plan.prune(params)
    packed, _ = plan.pack(pruned, masks)
    prompt = jax.random.randint(jax.random.key(3), (3, 6), 0, cfg.vocab_size)
    outs = {}
    for fused in (False, True):
        model = LSTMModel(cfg, fused=fused)
        assert model._use_fused is fused
        eng = ServeEngine(model, cfg, max_len=20, batch=3)
        outs[fused] = eng.generate(packed, prompt, 6, return_state=True)
    toks_c, state_c = outs[False]
    toks_f, state_f = outs[True]
    np.testing.assert_array_equal(np.asarray(toks_f), np.asarray(toks_c))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        state_f["cache"], state_c["cache"])


def test_fused_decode_trajectory_parity_delta_quant(lstm):
    """Fused-vs-chained bitwise trajectory parity holds when the policy
    layers on temporal deltas and int8 weights (the full BRDS stack)."""
    from repro.quant import QuantConfig
    from repro.sparse import DeltaGateConfig
    cfg, _, params = lstm
    policy = lstm_policy(0.6, 0.4, delta=DeltaGateConfig(0.05, 0.05),
                         quant=QuantConfig("int8"))
    prompt = jax.random.randint(jax.random.key(4), (2, 5), 0, cfg.vocab_size)
    outs = {}
    for fused in (False, True):
        eng = ServeEngine(LSTMModel(cfg, fused=fused), cfg, max_len=16,
                          batch=2, sparsity=policy)
        prepared, _ = eng.prepare(params)
        outs[fused] = np.asarray(eng.generate(prepared, prompt, 4))
    np.testing.assert_array_equal(outs[True], outs[False])
