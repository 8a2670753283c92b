"""The hybrid serving path the ``granite_*`` cells drive, and the check of
what it served.

Set-up: dense float32 weights from the seed in the program's layout
(``TransformerLM``), ``ServeEngine.prepare`` under the paper's dual-ratio
``transformer_policy`` (prune and pack), one ``ContinuousBatchingEngine``
on the packed weights with bucketed ``length=`` prefill, and a warm-up of
exactly the shapes the cell's traffic uses. The dense copy waits on the
host while the window runs.

Check: once the window has closed and the scheduler is freed, a sample of
the requests it finished (drawn from the seed, the longest among them)
runs through the plain reference (``bench/reference/granite_hybrid.py``)
as prompt plus served tokens, over the same dense weights; the number
compared is the widest gap by which a served token's logit lies below
the reference's best at its position.
"""
from __future__ import annotations

import functools
import json

import numpy as np

from . import lm
from .cost import keep_count
from .weights import _row_balanced


def model_config(cfg: dict):
    """The program's architecture for this configuration: the registered
    model ``cfg["arch"]`` at the configuration's depth, widths, layer
    pattern and scalars, in float32."""
    from repro.configs import get_arch
    kinds = {"mamba": "mamba", "attention": "attn"}
    L = cfg["num_hidden_layers"]
    return get_arch(cfg["arch"]).with_(
        num_layers=L, d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        block_pattern=tuple(kinds[k] for k in cfg["layer_types"][:L]),
        mamba_heads=cfg["mamba_n_heads"], mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_groups=cfg["mamba_n_groups"], conv_width=cfg["mamba_d_conv"],
        norm_eps=cfg["rms_norm_eps"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], dtype="float32")


def spec(cfg: dict) -> dict:
    """The scalars and widths the reference reads."""
    keys = ("embedding_multiplier", "residual_multiplier", "logits_scaling",
            "attention_multiplier", "rms_norm_eps", "num_attention_heads",
            "num_key_value_heads", "mamba_n_groups")
    out = {k: cfg[k] for k in keys}
    out.update(head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
               mamba_heads=cfg["mamba_n_heads"],
               mamba_head_dim=cfg["mamba_d_head"],
               mamba_d_state=cfg["mamba_d_state"],
               mamba_groups=cfg["mamba_n_groups"])
    return out


# ------------------------------------------------------------- weights

OUT_GAIN = 20.0     # the closing projection of each residual branch


def _layer(key, cfg: dict, kind: str):
    import jax
    import jax.numpy as jnp
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    sa, sb = cfg["sparsity"]["spar_a"], cfg["sparsity"]["spar_b"]
    ks = iter(jax.random.split(key, 16))
    f32 = jnp.float32

    def sparse(d_in, d_out, spar, gain=1.0):
        """(d_in, d_out), exactly k non-zeros per output column."""
        return gain * _row_balanced(next(ks), (d_out, d_in),
                                    keep_count(d_in, spar)).T

    def norm():
        return 0.1 * jax.random.normal(next(ks), (1, d), f32)

    out = {"norm1": {"w": norm()}, "norm2": {"w": norm()},
           "mlp": {"w_gate": sparse(d, ff, sa)[None],
                   "w_up": sparse(d, ff, sa)[None],
                   "w_down": sparse(ff, d, sa, OUT_GAIN)[None]}}
    if kind == "attn":
        Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        Dh = d // Hq
        out["attn"] = {
            "wq": sparse(d, Hq * Dh, sb).reshape(1, d, Hq, Dh),
            "wk": sparse(d, Hkv * Dh, sb).reshape(1, d, Hkv, Dh),
            "wv": sparse(d, Hkv * Dh, sb).reshape(1, d, Hkv, Dh),
            "wo": sparse(Hq * Dh, d, sb, OUT_GAIN).reshape(1, Hq, Dh, d)}
        return out
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G, W = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    d_inner = H * P
    conv_dim = d_inner + 2 * G * N
    u = lambda lo, hi, n: jax.random.uniform(next(ks), (1, n), f32, lo, hi)
    dt0 = jnp.exp(u(np.log(1e-3), np.log(0.1), H))
    out["mamba"] = {
        "w_inproj": sparse(d, d_inner + conv_dim + H, sb)[None],
        "conv_w": 0.5 * jax.random.normal(next(ks), (1, W, conv_dim), f32),
        "conv_b": 0.1 * jax.random.normal(next(ks), (1, conv_dim), f32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),   # softplus⁻¹(dt0)
        "A_log": jnp.log(u(1.0, 16.0, H)),
        "D": 1.0 + 0.1 * jax.random.normal(next(ks), (1, H), f32),
        "norm": 0.1 * jax.random.normal(next(ks), (1, d_inner), f32),
        "w_outproj": sparse(d_inner, d, sb, OUT_GAIN)[None]}
    return out


def _make(key, cfg: dict, pattern: tuple):
    import jax
    import jax.numpy as jnp
    L = cfg["num_hidden_layers"]
    ks = jax.random.split(key, L + 2)
    V, d = cfg["vocab_size"], cfg["hidden_size"]
    layers = [_layer(ks[2 + i], cfg, pattern[i % len(pattern)])
              for i in range(L)]
    P = len(pattern)
    return {
        "embed": {"table": jax.random.normal(ks[0], (V, d), jnp.float32)
                  / cfg["embedding_multiplier"]},
        "final_norm": {"w": 0.1 * jax.random.normal(ks[1], (d,),
                                                    jnp.float32)},
        # position j of the pattern, stacked over the periods
        "blocks": tuple(
            jax.tree.map(lambda *a: jnp.concatenate(a), *layers[j::P])
            for j in range(P)),
    }


_JITS: dict = {}


def _jitted(fn, **static):
    """One jitted ``fn`` per set of static arguments, kept for the process:
    runs of several seeds in one process (``bench/calibrate.py``, the
    tests) trace and compile it once."""
    import jax
    key = (fn.__qualname__, json.dumps(static, sort_keys=True, default=str))
    if key not in _JITS:
        _JITS[key] = jax.jit(functools.partial(fn, **static))
    return _JITS[key]


def make_params(cfg: dict, arch, seed: int):
    """Dense float32 weights in the program's layout (each position of the
    layer pattern stacked over the periods), made on the device in one
    call. Prunable leaves are already row-balanced sparse at the family
    ratios, so pruning by magnitude keeps exactly these entries."""
    import jax
    fn = _jitted(_make, cfg=cfg, pattern=arch.block_pattern)
    return fn(jax.random.key(seed))


def reference_params(params, cfg: dict):
    """The program's dense tree → the reference's plain layout, layers in
    order: (in, out) matrices, HF norm weights (1 + the program's
    offsets)."""
    d = cfg["hidden_size"]
    hf = lambda w: 1.0 + w
    blocks = params["blocks"]
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        blk = blocks[i % len(blocks)]
        b = {k: {n: v[i // len(blocks)] for n, v in grp.items()}
             for k, grp in blk.items()}
        lp = {"input_norm": hf(b["norm1"]["w"]),
              "post_norm": hf(b["norm2"]["w"]), "mlp": b["mlp"]}
        if "attn" in b:
            a = b["attn"]
            lp["mixer"] = {"q": a["wq"].reshape(d, -1),
                           "k": a["wk"].reshape(d, -1),
                           "v": a["wv"].reshape(d, -1),
                           "o": a["wo"].reshape(-1, d)}
        else:
            m = b["mamba"]
            lp["mixer"] = {"in_proj": m["w_inproj"], "conv_w": m["conv_w"],
                           "conv_b": m["conv_b"], "dt_bias": m["dt_bias"],
                           "A_log": m["A_log"], "D": m["D"],
                           "norm": hf(m["norm"]),
                           "out_proj": m["w_outproj"]}
        layers.append(lp)
    return {"embed": params["embed"]["table"],
            "final_norm": hf(params["final_norm"]["w"]), "layers": layers}


# ------------------------------------------------------------- serving

def prepared(cfg: dict, seed: int, *, batch: int, max_len: int):
    """(dense weights on the host, the engine's model, its packed weights,
    the prepare report)."""
    import jax
    from repro.models import build_model
    from repro.serving import ServeEngine
    from repro.sparse import transformer_policy
    arch = model_config(cfg)
    dense = make_params(cfg, arch, seed)
    sp = cfg["sparsity"]
    eng = ServeEngine(build_model(arch), None, max_len=max_len, batch=batch,
                      sparsity=transformer_policy(sp["spar_a"], sp["spar_b"]))
    packed, report = eng.prepare(dense)
    # the device copy goes with its last reference; the leaves that serve
    # unpruned (norms, conv, the embedding) live on in ``packed``
    return jax.device_get(dense), eng.model, packed, report


scheduler = lm.scheduler
warm = lm.warm
sample = lm.sample


def trace_facts(run, records, tr: dict) -> dict:
    """What the per-layer readers need of the traced window: tokens
    served, first tokens and their prompts, the positions attention
    attended over for them, the decode steps and the prefill rows per
    bucket that the scheduler's spans show dispatched in it, and the
    batch of each kind of program."""
    t0, t1 = run.trace_window()
    facts = lm.trace_facts(run, records, tr)
    context = 0
    for r in records:
        p, seen = r.prompt_len, 0
        for t, n in r.harvests:
            if t0 <= t < t1:
                # output i is read off position p + i - 1, over p + i
                # positions (the first off the prompt's last, over p);
                # the prompt's other positions over 1 … p - 1
                context += n * (p + seen) + n * (n - 1) // 2
                if seen == 0:
                    context += p * (p - 1) // 2
            seen += n
    spans = [s for s in run.spans if t0 <= s["t"] < t1]
    dispatches = sum(s["name"] == "sched.dispatch" for s in spans)
    rows: dict[int, int] = {}
    for s in spans:
        if s["name"] == "sched.prefill" and "bucket" in s["args"]:
            a = s["args"]
            rows[a["bucket"]] = rows.get(a["bucket"], 0) + a["batch"]
    facts.update(attn_context=context, decode_steps=dispatches * tr["chunk"],
                 prefill_requests=rows)
    return facts


def served_gap(dense, chosen, prompts: dict, cfg: dict, *,
               control: bool = False, block: int = 128):
    """(widest gap of a served token, widest gap of the token the
    bfloat16 control puts first) over the chosen requests."""
    import jax
    import jax.numpy as jnp
    from bench.reference import granite_hybrid as ref

    seqs, tgts, starts = [], [], []
    for r in chosen:
        p, s = prompts[r.uid], np.asarray(r.tokens, np.int32)
        seqs.append(np.concatenate([p, s[:-1]]))
        tgts.append(np.concatenate([np.zeros(len(p) - 1, np.int32), s]))
        starts.append(len(p) - 1)
    # widths in steps of 1024 positions: few reference programs per process
    T = max(len(x) for x in seqs)
    T += (-T) % 1024
    N = len(seqs)
    tokens = np.zeros((N, T), np.int32)
    targets = np.zeros((N, T), np.int32)
    valid = np.zeros((N, T), bool)
    for i, (x, t, s0) in enumerate(zip(seqs, tgts, starts)):
        tokens[i, :len(x)] = x
        targets[i, :len(t)] = t
        valid[i, s0:len(t)] = True
    params = jax.device_put(reference_params(dense, cfg))
    fn = _jitted(ref.served_gaps, spec=spec(cfg), control=control,
                 block=block)
    with jax.default_matmul_precision("highest"):
        gap, ctl = fn(params, jnp.asarray(tokens), jnp.asarray(targets),
                      jnp.asarray(valid))
    return float(jnp.max(gap)), float(jnp.max(ctl))


def check(run, dense, records, prompts: dict):
    """Add the served-path checks to ``run``; with ``run.control`` also
    read the bfloat16 control on the same requests."""
    chosen = sample(records, run.traffic["check_requests"], run.seed)
    short = sum(len(r.tokens) != r.budget for r in chosen)
    served = sum(len(r.tokens) for r in chosen)
    if chosen:
        gap, ctl = served_gap(dense, chosen, prompts, run.cfg,
                              control=run.control)
    else:
        gap, ctl = float("inf"), float("inf")
    run.facts["checked"] = {"requests": len(chosen), "tokens": served}
    run.check("logit_gap", gap)
    run.check("short_requests", short)
    if run.control:
        run.control_readings["logit_gap"] = ctl

