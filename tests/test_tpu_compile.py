"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode lowers kernels through XLA and never reaches the TPU's
Pallas compiler (Mosaic), so it cannot catch a primitive Mosaic has no
lowering for, a gather across vregs, a misaligned block or a VMEM
overflow. These tests compile each decode kernel at the paper's published
widths — ``lstm_ptb`` (X=H=1500) and ``lstm_timit`` (X=153, H=1024), B=8
(and B=1, 4 at ``lstm_ptb``; the fused step also at the served batches,
B=32 and B=64, where the gather's dynamic source-chunk loop runs four and
eight batch groups),
at the serve defaults Spar_x=0.75 / Spar_h=0.5 — for one chip of a
``v5e:2x2`` topology that is described, not attached, and assert that
each compiled program holds the Mosaic kernel (``tpu_custom_call``).
Each kernel also names its op itself: compiled under a jitted wrapper of
another name, the custom call still carries the kernel's own public
name, so a profile finds the kernel whatever function wraps it.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers import every
test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import block_rows_for
from repro.core.sparsity import keep_count
from repro.kernels import fused_step
from repro.kernels.delta_rb_spmv import delta_rb_dual_spmv
from repro.kernels.lstm_gates import lstm_gates
from repro.kernels.rb_spmv import rb_dual_spmv
from repro.kernels.rb_spmv_q8 import rb_dual_parts_q8

T = 8
SPAR_X, SPAR_H = 0.75, 0.5
WIDTHS = {"lstm_ptb": (1500, 1500), "lstm_timit": (153, 1024)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(arch):
    """Kernel operand shapes at ``arch``'s widths, rows padded to the
    kernel block as ``core.packing.pad_packed`` pads them."""
    X, H = WIDTHS[arch]
    R = 4 * H
    block = block_rows_for(R)
    Rp = R + (-R) % block
    return dict(X=X, H=H, R=Rp, block=block, Kx=keep_count(X, SPAR_X),
                Kh=keep_count(H, SPAR_H))


def _args(sh, kind, B):
    """(function, operand (shape, dtype) list) for one kernel kind."""
    X, H, R, Kx, Kh = sh["X"], sh["H"], sh["R"], sh["Kx"], sh["Kh"]
    f32, i8, i16 = jnp.float32, jnp.int8, jnp.int16
    kw = dict(block_rows=sh["block"], interpret=False)
    fam = lambda K, vdt=f32: [((R, K), vdt), ((R, K), i16)]
    fam_q8 = lambda K: [((R, K), i8), ((R, K), i16), ((R,), f32)]
    if kind == "dual_spmv":
        return (functools.partial(rb_dual_spmv, **kw),
                fam(Kx) + [((B, X), f32)] + fam(Kh)
                + [((B, H), f32), ((R,), f32)])
    if kind == "delta_dual_spmv":
        return (functools.partial(delta_rb_dual_spmv, **kw),
                fam(Kx) + [((B, X), f32)] * 2 + fam(Kh)
                + [((B, H), f32)] * 2 + [((B, R), f32)])
    if kind == "q8_dual_parts":
        return (functools.partial(rb_dual_parts_q8, **kw),
                fam_q8(Kx) + [((B, X), i8)] + fam_q8(Kh) + [((B, H), i8)])
    if kind == "lstm_gates":
        Hp = -(-H // 128) * 128        # kernels.ops.lstm_gates' padding
        block = next(b for b in (512, 256, 128) if Hp % b == 0)
        return (functools.partial(lstm_gates, block=block,
                                  interpret=False),
                [((B, Hp), f32)] * 5)
    if kind == "fused_step":
        return (functools.partial(fused_step.fused_brds_lstm_step, **kw),
                fam(Kx) + [((B, X), f32)] + fam(Kh)
                + [((B, H), f32), ((R,), f32), ((B, H), f32)])
    if kind == "fused_delta_step":
        return (functools.partial(fused_step.fused_brds_delta_lstm_step,
                                  **kw),
                fam(Kx) + [((B, X), f32)] * 2 + fam(Kh)
                + [((B, H), f32)] * 2
                + [((B, R), f32), ((R,), f32), ((B, H), f32)])
    if kind == "fused_q8_step":
        return (functools.partial(fused_step.fused_brds_lstm_step_q8, **kw),
                fam_q8(Kx) + [((B, X), i8)] + fam_q8(Kh)
                + [((B, H), i8), ((R,), f32), ((B, H), f32)])
    if kind == "fused_delta_q8_step":
        return (functools.partial(fused_step.fused_brds_delta_lstm_step_q8,
                                  **kw),
                fam_q8(Kx) + [((B, X), i8)] + fam_q8(Kh)
                + [((B, H), i8), ((B, R), f32), ((R,), f32), ((B, H), f32)])
    if kind == "fused_scan":
        return (functools.partial(fused_step.fused_brds_lstm_scan, **kw),
                fam(Kx) + [((T, B, X), f32)] + fam(Kh)
                + [((B, H), f32), ((R,), f32), ((B, H), f32)])
    if kind == "fused_delta_scan":
        return (functools.partial(fused_step.fused_brds_delta_lstm_scan,
                                  theta_x=0.05, theta_h=0.05, **kw),
                fam(Kx) + [((T, B, X), f32)] + fam(Kh)
                + [((B, H), f32)] * 2 + [((B, X), f32), ((B, H), f32),
                                         ((B, R), f32), ((R,), f32)])
    raise ValueError(kind)


KINDS = ("dual_spmv", "delta_dual_spmv", "q8_dual_parts", "lstm_gates",
         "fused_step", "fused_delta_step", "fused_q8_step",
         "fused_delta_q8_step", "fused_scan", "fused_delta_scan")


# B=8 is the lockstep batch; B=1 the scheduler's exact-length prefill and
# B=4 its slot batch — a batch below one 8-row sublane tile lays rows out
# differently, and Mosaic has refused such layouts where B=8 compiled.
CASES = ([(arch, 8) for arch in sorted(WIDTHS)]
         + [("lstm_ptb", 1), ("lstm_ptb", 4)])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("arch,B", CASES)
def test_kernel_compiles_for_v5e(one_chip, no_compile_cache, arch, B, kind):
    fn, operands = _args(_shapes(arch), kind, B)
    structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
               for s, d in operands]
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch,B", [("lstm_ptb", 32), ("lstm_timit", 64)])
def test_fused_step_compiles_at_served_batch(one_chip, no_compile_cache,
                                             arch, B):
    test_kernel_compiles_for_v5e(one_chip, no_compile_cache, arch, B,
                                 "fused_step")


def _custom_call_names(hlo: str) -> list[str]:
    """The instruction name of each custom call in compiled HLO text."""
    return re.findall(r"(?m)^\s*(?:ROOT )?%(\S+) = .*custom-call\(", hlo)


@pytest.mark.parametrize("kind", KINDS)
def test_kernel_names_its_op_under_any_wrapper(one_chip, no_compile_cache,
                                               kind):
    """The kernel, unwrapped from its own jit and compiled inside a jitted
    function of another name, gives its op the kernel's public name (the
    fused step's op is ``fused_brds_lstm_step``)."""
    fn, operands = _args(_shapes("lstm_timit"), kind, 8)
    raw = functools.partial(fn.func.__wrapped__, **fn.keywords)

    def some_other_wrapper(*a):
        return raw(*a)

    structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
               for s, d in operands]
    hlo = jax.jit(some_other_wrapper).lower(*structs).compile().as_text()
    ops = _custom_call_names(hlo)
    assert ops, "no custom call in the compiled program"
    name = fn.func.__name__
    if kind == "fused_step":
        assert name == "fused_brds_lstm_step"
    assert any(re.fullmatch(rf"{name}(\.\d+)?", op) for op in ops), ops
    assert not any(op.startswith("some_other_wrapper") for op in ops), ops


# The granite_h_decode cell's kernels at its published widths: each packed
# projection (rows, K, source columns) at the rows per call and the row
# block ``kernels.ops.rb_project`` runs it at, and the Mamba-2 state step
# at the cell's 32 slots.
HYBRID = {"mamba_in": (8512, 1024, 2048), "mamba_out": (2048, 2048, 4096),
          "mlp_up": (8192, 512, 2048), "mlp_down": (2048, 2048, 8192),
          "attn_q": (2048, 1024, 2048), "attn_kv": (512, 1024, 2048)}


@pytest.mark.parametrize("proj", sorted(HYBRID))
def test_hybrid_projection_compiles_for_v5e(one_chip, no_compile_cache,
                                            proj):
    from repro.kernels import ops
    from repro.kernels.rb_spmv import rb_spmv
    R, K, X = HYBRID[proj]
    block = block_rows_for(R, ops.project_block_rows(K))
    Rp = R + (-R) % block
    fn = functools.partial(rb_spmv, block_rows=block, interpret=False)
    structs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
               (((Rp, K), jnp.float32), ((Rp, K), jnp.int16),
                ((ops.project_rows(X), X), jnp.float32))]
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba2_ssm_step_compiles_for_v5e(one_chip, no_compile_cache):
    from repro.kernels.mamba2_ssm import mamba2_ssm_step
    B, H, P, N, G = 32, 64, 64, 128, 1
    f32 = jnp.float32
    structs = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip) for s in
               ((B, H, P, N), (B, H, P), (B, H), (H,), (B, G, N), (B, G, N),
                (H,))]
    fn = functools.partial(mamba2_ssm_step, interpret=False)
    hlo = jax.jit(fn, donate_argnums=0).lower(*structs).compile().as_text()
    ops = _custom_call_names(hlo)
    assert any(re.fullmatch(r"mamba2_ssm_step(\.\d+)?", op) for op in ops), ops


def test_dense_served_hybrid_decode_chunk_compiles_for_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """The granite_h_decode cell's decode chunk at its 32 slots and
    published widths, each projection served as the rule picks on the
    chip (all dense): it compiles, holds no gather (``rb_spmv``) and keeps
    the Pallas state step of each of the period's nine Mamba layers."""
    import json
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench.lib import hybrid_lm
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serving import runtime
    from repro.serving.sampling import SamplingConfig
    from repro.sparse import transformer_policy

    cfg = json.loads((root / "bench" / "configs"
                      / "granite_4_h_micro.json").read_text())
    model = build_model(hybrid_lm.model_config(cfg))
    abstract = model.abstract_params()
    plan = transformer_policy(0.75, 0.5).compile(abstract)
    dense = model.dense_served(plan, 32, "TPU v5 lite")
    assert dense == frozenset(plan.sites)
    served, _ = plan.pack(abstract, abstract=True, dense=dense)
    # the model's own kernels, compiled, where the CPU would take twins
    monkeypatch.setattr(ops, "model_backend", lambda: None)
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    B, max_len = 32, 4096

    def chunk(params, cache, logits, pos, rng, done, budget):
        return runtime.decode_loop(model, params, cache, logits, pos, rng,
                                   1, SamplingConfig(), done=done,
                                   budget=budget, limit=max_len)

    on_chip = lambda t: jax.tree.map(                      # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)
    args = on_chip((
        served, jax.eval_shape(lambda: model.init_cache(B, max_len)),
        jax.ShapeDtypeStruct((B, 1, model.vocab_padded), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.eval_shape(lambda: jax.random.key(0)),
        jax.ShapeDtypeStruct((B,), jnp.bool_),
        jax.ShapeDtypeStruct((B,), jnp.int32)))
    hlo = jax.jit(chunk, donate_argnums=(1,)).lower(
        *args).compile().as_text()
    ops_ = _custom_call_names(hlo)
    assert not any(op.startswith("rb_spmv") for op in ops_), ops_
    assert any(re.fullmatch(r"mamba2_ssm_step(\.\d+)?", op)
               for op in ops_), ops_
