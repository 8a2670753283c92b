"""Operations and bytes of the Granite hybrid's work, computed from the
configuration's shapes alone (they never read the program).

Packed projections: every Mamba in/out, attention q/k/v/o and MLP
gate/up/down matrix, rows = output units, K = kept columns per row at its
family's ratio (MLP family A, mixers family B), stored as float32 values
and the narrowest signed index that holds a column (``cost.index_bytes``).
The least a projection call over n activation rows must move is its
packed weights once plus the activations in and out; it requires a
multiply and an add per kept weight per row.

The SSM state update of one Mamba layer for a batch of B slots reads and
writes the float32 state (B × heads × head_dim × d_state) and reads its
inputs (x, dt, B, C) and writes y; per state element it requires the
decay multiply, the input's two multiplies and an add, and the read-out's
multiply and add.
"""
from __future__ import annotations

from .cost import F32, index_bytes, keep_count


def layer_kinds(cfg: dict) -> list[str]:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def projections(cfg: dict) -> list[tuple[int, int, int]]:
    """(rows, ncols, K) of every packed projection of the model."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    sa, sb = cfg["sparsity"]["spar_a"], cfg["sparsity"]["spar_b"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = d // Hq
    d_inner = H * P
    fam_b = lambda rows, ncols: (rows, ncols, keep_count(ncols, sb))
    fam_a = lambda rows, ncols: (rows, ncols, keep_count(ncols, sa))
    out = []
    for kind in layer_kinds(cfg):
        if kind == "mamba":
            out += [fam_b(2 * d_inner + 2 * G * N + H, d),
                    fam_b(d, d_inner)]
        else:
            out += [fam_b(Hq * Dh, d), fam_b(Hkv * Dh, d),
                    fam_b(Hkv * Dh, d), fam_b(d, Hq * Dh)]
        out += [fam_a(ff, d), fam_a(ff, d), fam_a(d, ff)]
    return out


def proj_call_bytes(p: tuple, n: int) -> int:
    rows, ncols, K = p
    return rows * K * (F32 + index_bytes(ncols)) + n * (ncols + rows) * F32


def proj_call_flops(p: tuple, n: int) -> int:
    rows, _, K = p
    return 2 * n * rows * K


def packed_need_s(cfg: dict, n: int, bw: float, peak: float) -> float:
    """Least seconds the packed projections of one pass over n activation
    rows (a decode step at batch n, or a prefill of n positions) need:
    per projection the larger of its bytes over the bandwidth and its
    operations over the peak."""
    return sum(max(proj_call_bytes(p, n) / bw, proj_call_flops(p, n) / peak)
               for p in projections(cfg))


def ssm_elems(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def ssm_call_bytes(cfg: dict, batch: int) -> int:
    """One layer's state update at ``batch`` slots."""
    H, P, N = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
               cfg["mamba_d_state"])
    G = cfg["mamba_n_groups"]
    return batch * F32 * (2 * H * P * N + 2 * H * P + H + 2 * G * N)


def ssm_call_flops(cfg: dict, batch: int) -> int:
    return 6 * batch * ssm_elems(cfg)


def n_mamba(cfg: dict) -> int:
    return sum(k == "mamba" for k in layer_kinds(cfg))


def n_attention(cfg: dict) -> int:
    return sum(k != "mamba" for k in layer_kinds(cfg))


def ssm_need_s(cfg: dict, batch: int, bw: float, peak: float) -> float:
    """Least seconds the state updates of one step of every Mamba layer
    need at ``batch`` slots."""
    return n_mamba(cfg) * max(ssm_call_bytes(cfg, batch) / bw,
                              ssm_call_flops(cfg, batch) / peak)


def token_flops(cfg: dict) -> int:
    """Operations one position requires apart from attention over its
    context: the packed projections' kept entries, the SSM update of each
    Mamba layer, and the tied head."""
    return (sum(proj_call_flops(p, 1) for p in projections(cfg))
            + n_mamba(cfg) * ssm_call_flops(cfg, 1)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops(cfg: dict, context: int) -> int:
    """Operations of every attention layer's scores and weighted sum over
    ``context`` attended positions in all (summed over positions)."""
    d = cfg["hidden_size"]
    return n_attention(cfg) * 4 * d * context
