"""The packed gather at sources wider than one 128-lane chunk (nx > 1).

Per 8-row tile and 128-lane K-chunk, ``gather_dot`` visits the
``window_chunks`` source chunks from the tile's smallest ``col >> 7``
where every K-chunk of the tile fits that window, and where one does not,
each K-chunk's chunks from its smallest ``col >> 7`` to its largest. These cases build column layouts by hand — every K-chunk of
a tile in one source chunk, uniform sorted columns (the benchmark's
weights), tiles that mix narrow and spanning rows, columns in random
order, windows that end at the last chunk, block-padding rows — and check
the kernels bitwise (``==``, interpret mode): against the ``ref`` oracle
on integer-valued data, where every summation order gives the same float,
and the fused steps against the chained kernels on random floats.
``gather_visit_share`` is checked on layouts whose share is known, and in
the report ``ServeEngine.prepare`` returns.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import RowBalancedSparse, pad_packed
from repro.kernels import ref
from repro.kernels.rb_spmv import gather_visit_share, window_chunks

LAYOUTS = ("one_chunk", "random", "mixed", "shuffled", "last_chunks")
# K per source width: at X=1500 (12 chunks) K=375 opens a 6-chunk window;
# at X=300 (3 chunks) K=200 gives a window of all 3, the static visit.
K_FOR = {1500: 375, 300: 200, 136: 68}


def _full_chunks(X):
    return [c for c in range(-(-X // 128)) if 128 * c + 128 <= X]


def _chunked_row(rng, X, K, chunks):
    """K ascending columns such that K-chunk i lies in source chunk
    ``chunks[i]``: all 128 columns of each but the last, the rest drawn
    from the last."""
    cols = [np.arange(128 * c, 128 * c + 128) for c in chunks[:-1]]
    last = np.arange(128 * chunks[-1], min(X, 128 * chunks[-1] + 128))
    cols.append(np.sort(rng.choice(last, K - 128 * (len(chunks) - 1),
                                   replace=False)))
    return np.concatenate(cols)


def _layout(rng, R, X, K, layout):
    """(R, K) absolute columns. ``one_chunk``: each tile draws one source
    chunk per K-chunk for all its rows; ``random``: uniform sorted subsets;
    ``mixed``: each tile's rows alternate narrow (``one_chunk``) and
    ``shuffled``; ``shuffled``: uniform subsets in random order (every
    K-chunk spans nearly every source chunk); ``last_chunks``: the
    ``one_chunk`` rows on the highest source chunks, so a window runs past
    the last chunk."""
    m = -(-K // 128)
    full = _full_chunks(X)
    rows = []
    for r in range(R):
        if r % 8 == 0:
            if layout == "last_chunks":
                tile = full[-m:]
            else:
                tile = sorted(rng.choice(full, m, replace=False))
        narrow = _chunked_row(rng, X, K, tile)
        spread = rng.permutation(rng.choice(X, K, replace=False))
        if layout in ("one_chunk", "last_chunks"):
            rows.append(narrow)
        elif layout == "random":
            rows.append(np.sort(rng.choice(X, K, replace=False)))
        elif layout == "mixed":
            rows.append(spread if r % 2 else narrow)
        else:
            rows.append(spread)
    return np.stack(rows)


def _packed(cols, X, values):
    deltas = np.diff(cols, axis=1, prepend=0).astype(np.int16)
    return RowBalancedSparse(values=jnp.asarray(values),
                             deltas=jnp.asarray(deltas), ncols=X)


def _family(rng, R, X, layout, *, integer=False):
    K = K_FOR[X]
    cols = _layout(rng, R, X, K, layout)
    if integer:
        vals = rng.integers(-4, 5, (R, K)).astype(np.float32)
    else:
        vals = rng.normal(size=(R, K)).astype(np.float32) / np.sqrt(K)
    return _packed(cols, X, vals)


def test_window_is_narrower_than_the_source_only_where_it_pays():
    assert window_chunks(12, 375) == 6 and window_chunks(12, 750) == 4
    assert window_chunks(8, 512) == 4           # lstm_timit W_h
    assert window_chunks(2, 38) == 2            # lstm_timit W_x: all
    assert window_chunks(3, 200) == 3 and window_chunks(1, 60) == 1


# 44 rows → a 48-row kernel block: the last tile holds 4 rows of the
# matrix and 4 zero rows appended by pad_packed (column 0, chunk 0).
R_SPMV = 44
# Interpret mode compiles each shape for seconds, growing with B and the
# chunk count: every layout runs at B=3 at both widths, and the batches
# that lay rows out differently (1; 8, one sublane tile; 32, two batch
# groups) run on the mixed layout.
SPMV_CASES = ([(layout, 3, X) for layout in LAYOUTS for X in (300, 1500)]
              + [("mixed", B, 1500) for B in (1, 8, 32)])


@pytest.mark.parametrize("layout,B,X", SPMV_CASES)
def test_rb_spmv_bitwise_at_wide_source(layout, B, X):
    from repro.kernels import rb_spmv
    rng = np.random.default_rng([LAYOUTS.index(layout), B, X])
    s = _family(rng, R_SPMV, X, layout, integer=True)
    x = jnp.asarray(rng.integers(-4, 5, (B, X)).astype(np.float32))
    got = rb_spmv(s, x, block_rows=48)
    padded = rb_spmv(pad_packed(s, 48), x, block_rows=48)
    want = ref.rb_spmv_ref(s, x)
    assert pad_packed(s, 48).pad == 4
    assert got.shape == want.shape == (B, R_SPMV)
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(padded == want))


def _gates_split(z, H, c):
    from repro.kernels import lstm_gates
    return lstm_gates(z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:], c, pwl=False)


# H = 136: two source chunks for W_h (a static visit beside a windowed
# W_x at X=1500); 4H = 544 rows pad to 576 at block_rows=64, so the last
# block holds 32 zero rows.
H_FUSED = 136
# (B=32, two batch groups, runs in the rb_spmv cases: the fused step
# shares their gather and compiles twice as long.)
FUSED_CASES = ([(layout, 3, 1500) for layout in LAYOUTS]
               + [("mixed", 3, 300), ("mixed", 1, 1500), ("random", 8, 1500)])


@pytest.mark.parametrize("layout,B,X", FUSED_CASES)
def test_fused_step_bitwise_vs_chained_at_wide_source(layout, B, X):
    from repro.kernels import fused_brds_lstm_step, rb_dual_spmv
    rng = np.random.default_rng([LAYOUTS.index(layout), B, X, 1])
    H = H_FUSED
    sx = _family(rng, 4 * H, X, layout)
    sh = _family(rng, 4 * H, H, "shuffled")
    x = jnp.asarray(rng.normal(size=(B, X)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(B, H)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, H)), jnp.float32)
    b = jnp.asarray(0.1 * rng.normal(size=(4 * H,)), jnp.float32)
    z = rb_dual_spmv(sx, x, sh, h, b, block_rows=64)
    cc, hc = _gates_split(z, H, c)
    cf, hf = fused_brds_lstm_step(pad_packed(sx, 64), x, pad_packed(sh, 64),
                                  h, b, c, block_rows=64)
    assert bool(jnp.all(cf == cc)) and bool(jnp.all(hf == hc))


def test_fused_delta_step_bitwise_vs_chained_at_wide_source():
    from repro.kernels import delta_rb_dual_spmv, fused_brds_delta_lstm_step
    from repro.sparse.temporal import delta_threshold
    rng = np.random.default_rng(7)
    B, X, H = 3, 1500, H_FUSED
    sx = _family(rng, 4 * H, X, "mixed")
    sh = _family(rng, 4 * H, H, "shuffled")
    rand = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    b, c, m0 = rand(4 * H), rand(B, H), rand(B, 4 * H)
    dx, fx, _ = delta_threshold(rand(B, X), jnp.zeros((B, X)), 0.5)
    dh, fh, _ = delta_threshold(rand(B, H), jnp.zeros((B, H)), 0.5)
    fx, fh = fx.astype(jnp.float32), fh.astype(jnp.float32)
    mc = delta_rb_dual_spmv(sx, dx, fx, sh, dh, fh, m0, block_rows=64)
    cc, hc = _gates_split(mc + b[None, :], H, c)
    cf, hf, mf = fused_brds_delta_lstm_step(sx, dx, fx, sh, dh, fh, m0, b,
                                            c, block_rows=64)
    assert bool(jnp.all(mf == mc))
    assert bool(jnp.all(cf == cc)) and bool(jnp.all(hf == hc))


def test_fused_q8_step_bitwise_vs_chained_at_wide_source():
    from repro.kernels import fused_brds_lstm_step_q8, rb_dual_spmv_q8
    from repro.quant import quantize_packed
    rng = np.random.default_rng(8)
    B, X, H = 3, 1500, H_FUSED
    sx = quantize_packed(_family(rng, 4 * H, X, "mixed"), "int8")
    sh = quantize_packed(_family(rng, 4 * H, H, "shuffled"), "int8")
    rand = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, h, c, b = rand(B, X), rand(B, H), rand(B, H), rand(4 * H)
    kw = dict(act_scale_x=0.04, act_scale_h=0.03, block_rows=64)
    z = rb_dual_spmv_q8(sx, x, sh, h, b, **kw)
    cc, hc = _gates_split(z, H, c)
    cf, hf = fused_brds_lstm_step_q8(sx, x, sh, h, b, c, **kw)
    assert bool(jnp.all(cf == cc)) and bool(jnp.all(hf == hc))


def test_q8_and_delta_spmv_bitwise_vs_ref_at_wide_source():
    """Integer codes are exact, and integer-valued deltas sum exactly, so
    both single-family kernels meet their oracles bit for bit."""
    from repro.kernels import delta_rb_spmv, rb_spmv_q8
    from repro.quant import quantize_packed
    rng = np.random.default_rng(9)
    B, X = 3, 1500
    s = _family(rng, R_SPMV, X, "mixed", integer=True)
    d = jnp.asarray(rng.integers(-4, 5, (B, X)).astype(np.float32))
    fired = jnp.asarray(rng.random((B, X)) < 0.5, jnp.float32)
    got = delta_rb_spmv(s, d, fired, block_rows=48)
    assert bool(jnp.all(got == ref.delta_rb_spmv_ref(s, d, fired)))
    q = quantize_packed(s, "int8")
    x = jnp.asarray(rng.normal(size=(B, X)), jnp.float32)
    got, want = (rb_spmv_q8(q, x, act_scale=0.02, block_rows=48,
                            backend=backend) for backend in ("pallas", "ref"))
    assert bool(jnp.all(got == want))


# ------------------------------------------------------ gather_visit_share

@pytest.mark.parametrize("layout", ["one_chunk", "last_chunks", "random"])
def test_visit_share_of_fitting_tiles_is_window_over_nx(layout):
    """Tiles whose K-chunks fit the window visit 6 of 12 chunks each."""
    s = _family(np.random.default_rng(1), 40, 1500, layout)
    assert gather_visit_share(s.deltas, 1500) == 6 / 12


@pytest.mark.parametrize("X,layout", [(1500, "shuffled"), (1500, "mixed"),
                                      (300, "one_chunk"), (300, "random")])
def test_visit_share_of_spanning_tiles_or_narrow_sources_is_one(X, layout):
    s = _family(np.random.default_rng(2), 40, X, layout)
    assert gather_visit_share(s.deltas, X) == 1.0


def test_visit_share_counts_padding_rows_and_stacked_layers():
    """A partial last tile pads with zero rows (column 0, chunk 0), so a
    tile whose K-chunks lie in chunks 6, 7 and 8 no longer fits the window
    and visits chunks 0–6, 0–7 and 0–8: 24 of 36; stacked layers average
    over all their tiles."""
    rng = np.random.default_rng(3)
    row = _chunked_row(rng, 1500, 375, [6, 7, 8])
    ones = np.ones((8, 375), np.float32)
    s4 = _packed(np.tile(row, (4, 1)), 1500, ones[:4])
    assert gather_visit_share(s4.deltas, 1500) == 24 / 36
    s8 = _packed(np.tile(row, (8, 1)), 1500, ones)
    assert gather_visit_share(s8.deltas, 1500) == 0.5
    spanning = _packed(np.tile(rng.choice(1500, 375, replace=False),
                               (8, 1)), 1500, ones)
    stacked = jnp.stack([s8.deltas, spanning.deltas])
    assert gather_visit_share(stacked, 1500) == 0.75


def test_prepare_report_carries_visit_share_per_packed_leaf():
    from repro.models import LSTMConfig, LSTMModel
    from repro.serving import ServeEngine
    from repro.sparse import lstm_policy
    cfg = LSTMConfig("t", input_size=1500, hidden=136, num_layers=2,
                     vocab_size=50)
    model = LSTMModel(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, cfg, max_len=16, batch=2,
                      sparsity=lstm_policy(0.75, 0.5, backend="ref"))
    packed, report = eng.prepare(params)
    leaves = {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  packed, is_leaf=lambda v: isinstance(v, RowBalancedSparse)
              )[0] if isinstance(leaf, RowBalancedSparse)}
    shares = report["gather_visit_share"]
    assert leaves and set(shares) == leaves
    for path, share in shares.items():
        layer, name = path.split("/")[1:]
        leaf = packed["layers"][int(layer)][name].logical()
        assert share == gather_visit_share(leaf.deltas, leaf.ncols)
    assert shares["layers/0/w_x"] < 1.0        # 1500 wide: windowed
    assert shares["layers/1/w_x"] == 1.0       # 136 wide: all chunks
