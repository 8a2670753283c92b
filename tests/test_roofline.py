"""The HLO roofline analyzer: loop-aware flop/collective accounting,
validated against a hand-computable compiled function."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import roofline


def test_shape_parsing():
    assert roofline.shape_bytes("bf16[16,4096]{1,0}") == 16 * 4096 * 2
    assert roofline.shape_bytes("f32[8]{0}") == 32
    assert roofline.shape_bytes("(f32[4,4]{1,0}, s32[2]{0})") == 64 + 8
    assert roofline.shape_elems("f32[3,5]{1,0}") == 15
    assert roofline.shape_bytes("pred[]") == 1


def test_scan_trip_count_multiplies_flops():
    """A scan of N matmuls must report ≈ N × the single-matmul flops —
    the exact failure mode of raw cost_analysis this module exists to fix."""
    N, M = 12, 128

    def one(x, w):
        return x @ w

    def scanned(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
        return y

    x = jnp.zeros((M, M), jnp.float32)
    w = jnp.zeros((M, M), jnp.float32)
    ws = jnp.zeros((N, M, M), jnp.float32)

    t1 = jax.jit(one).lower(x, w).compile().as_text()
    tN = jax.jit(scanned).lower(x, ws).compile().as_text()
    f1 = roofline.analyze_hlo(t1, 1).flops_hlo
    fN = roofline.analyze_hlo(tN, 1).flops_hlo
    assert f1 == pytest.approx(2 * M ** 3, rel=0.01)
    assert fN == pytest.approx(N * 2 * M ** 3, rel=0.05), (fN, N * f1)


def test_int8_dots_counted_at_int8_peak():
    """Quantized dots — s8 operands (TPU builds) or the s32-accumulator
    form XLA CPU normalizes them to — land in the int8 bucket and are
    costed at the int8 peak, not the bf16 peak; float dots stay in the
    bf16 bucket. Keeps the quant benchmark's derived GOPS honest."""
    from repro import hw

    def program(dot_line):
        return "\n".join([
            "ENTRY %main (a: s8[64,128], b: s8[128,32]) -> f32[64,32] {",
            "  %a = s8[64,128]{1,0} parameter(0)",
            "  %b = s8[128,32]{1,0} parameter(1)",
            "  %e = s32[64,128]{1,0} convert(%a)",
            "  %f = s32[128,32]{1,0} convert(%b)",
            dot_line,
            "  %c = f32[64,128]{1,0} convert(%a)",
            "  %d = f32[128,32]{1,0} convert(%b)",
            "  ROOT %r = f32[64,32]{1,0} dot(f32[64,128]{1,0} %c, "
            "f32[128,32]{1,0} %d), lhs_contracting_dims={1}, "
            "rhs_contracting_dims={0}",
            "}",
        ])

    one_dot = 2 * 64 * 32 * 128
    for qdot in (
        # pre-optimization / TPU form: s8 operands into the MXU
        "  %q = s32[64,32]{1,0} dot(s8[64,128]{1,0} %a, s8[128,32]{1,0} "
        "%b), lhs_contracting_dims={1}, rhs_contracting_dims={0}",
        # XLA-CPU normalized form: convert→s32 dot (operand signal gone,
        # integer accumulator type remains)
        "  %q = s32[64,32]{1,0} dot(s32[64,128]{1,0} %e, s32[128,32]{1,0} "
        "%f), lhs_contracting_dims={1}, rhs_contracting_dims={0}",
    ):
        rep = roofline.analyze_hlo(program(qdot), 1)
        assert rep.flops_hlo == pytest.approx(2 * one_dot)
        assert rep.flops_int8 == pytest.approx(one_dot)
        t = rep.terms(hbm_bytes_per_chip=0, chips=1)
        pk = hw.peaks(hw.TARGET_KIND)
        expect = one_dot / pk.bf16_flops + one_dot / pk.int8_ops
        assert t["compute_s"] == pytest.approx(expect)


def test_quantized_ref_decode_lands_in_int8_bucket():
    """End to end: the compiled q8 reference SpMV (the formulation the
    dry-run/roofline path analyzes) is classified as integer dot flops."""
    import numpy as np
    from repro.core import pack_from_dense
    from repro.quant import quantize_packed
    from repro.kernels import ops as K
    rng = np.random.default_rng(0)
    s = pack_from_dense(
        jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32)), 0.75)
    q = quantize_packed(s, "int8")
    x = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
    hlo = jax.jit(lambda xx: K.rb_spmv_q8(q, xx, backend="ref")) \
        .lower(x).compile().as_text()
    rep = roofline.analyze_hlo(hlo, 1)
    assert rep.flops_int8 > 0
    assert rep.flops_int8 == pytest.approx(rep.flops_hlo)


def test_known_trip_regex():
    line = ('%while.345 = (s32[]) while(%t), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"24"},"other":1}')
    m = roofline._KNOWN_TRIP.search(line)
    assert m and int(m.group(1)) == 24


def test_replica_group_parsing():
    assert roofline._group_size("replica_groups={{0,1,2,3}}", 8) == 4
    assert roofline._group_size("replica_groups=[16,16]<=[256]", 8) == 16
    assert roofline._group_size("no groups here", 8) == 8


def test_model_flops_sanity():
    """6ND for dense training; MoE counts active params only."""
    from repro.configs import get_arch, SHAPES
    arch = get_arch("llama3.2-3b")
    mf = roofline.model_flops(arch, SHAPES["train_4k"])
    # llama3.2-3b ≈ 3.6B params, 1.05M tokens → 6ND ≈ 2.3e16 ± attention
    assert 1.5e16 < mf["total"] < 4e16
    moe = get_arch("qwen3-moe-235b-a22b")
    mfm = roofline.model_flops(moe, SHAPES["train_4k"])
    assert mfm["n_active"] < 0.25 * mfm["n_params"]


def test_analytic_hbm_decode_dominated_by_weights_and_cache():
    from repro.configs import get_arch, SHAPES
    arch = get_arch("llama3.2-3b")
    hbm = roofline.analytic_hbm_bytes(arch, SHAPES["decode_32k"], 256)
    # 3B bf16 params ≈ 6.4e9 bytes; kv cache 128seq × 32k × 28L × 2 × 8 × 128
    assert hbm["global_total"] > 6e9
    assert hbm["weights"] == pytest.approx(6.4e9 / 256, rel=0.3)
