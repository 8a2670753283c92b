"""Operations and bytes of the work, computed from shapes alone.

A configuration file gives the widths and the two sparsity ratios; these
functions give what one fused BRDS-LSTM step call must move and compute,
and the operations a token or a frame requires. They never read the
program: a later change to the program cannot change the yardstick.

Packed layout counted: per weight family, values (float32) and relative
column indices (the narrowest signed int that holds them) at K kept
columns per row, over the 4H gate rows padded to a multiple of the
kernel's row block (256 rows, or all rows rounded up to 8 where there
are fewer).
"""
from __future__ import annotations

F32 = 4
BLOCK_ROWS = 256


def index_bytes(ncols: int) -> int:
    """Width of a column index: the narrowest signed int holding ncols-1."""
    return 1 if ncols - 1 <= 127 else 2 if ncols - 1 <= 32767 else 4


def keep_count(ncols: int, sparsity: float) -> int:
    """Kept entries per row: prune the smallest ``sparsity`` share."""
    return max(1, min(ncols, ncols - int(round(sparsity * ncols))))


def layer_dims(cfg: dict) -> list[dict]:
    """Per layer: input width, hidden, kept columns of W_x and W_h, and
    padded gate rows."""
    m, sp = cfg["model"], cfg["sparsity"]
    H = m["hidden"]
    rows = 4 * H
    block = min(BLOCK_ROWS, -(-rows // 8) * 8)
    padded = rows + (-rows) % block
    out = []
    for i in range(m["num_layers"]):
        x_in = m["input_size"] if i == 0 else H
        out.append({"x": x_in, "h": H, "rows": rows, "padded_rows": padded,
                    "kx": keep_count(x_in, sp["spar_x"]),
                    "kh": keep_count(H, sp["spar_h"])})
    return out


def step_call_bytes(d: dict, batch: int) -> int:
    """HBM bytes one fused step call must move: both packed families,
    the bias, x, h and c in, c and h out."""
    weights = d["padded_rows"] * (d["kx"] * (F32 + index_bytes(d["x"]))
                                  + d["kh"] * (F32 + index_bytes(d["h"])))
    bias = d["padded_rows"] * F32
    acts = batch * (d["x"] + 2 * d["h"]) * F32 + 2 * batch * d["h"] * F32
    return weights + bias + acts


def step_call_flops(d: dict, batch: int) -> int:
    """Operations one fused step call requires: a multiply and an add per
    kept weight per row of the batch (true rows, not padding)."""
    return 2 * batch * d["rows"] * (d["kx"] + d["kh"])


def lstm_flops_per_step(cfg: dict) -> int:
    """Operations the packed LSTM layers require for one token or frame."""
    return sum(step_call_flops(d, 1) for d in layer_dims(cfg))


def head_flops(cfg: dict) -> int:
    """Operations of the output head for one position."""
    m = cfg["model"]
    return 2 * m["hidden"] * (m.get("vocab_size") or m.get("num_classes"))
