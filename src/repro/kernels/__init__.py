"""Pallas TPU kernels for the BRDS framework.

Each kernel ships with a pure-jnp oracle in ref.py; ops.py holds the jit'd
public wrappers (compiled on TPU; Pallas interpret mode elsewhere, decided
once by ``ops.interpret_mode``).
"""
from .ops import (
    rb_spmv,
    rb_dual_spmv,
    rb_spmv_q8,
    rb_dual_spmv_q8,
    delta_rb_spmv,
    delta_rb_dual_spmv,
    delta_rb_dual_spmv_q8,
    lstm_gates,
    fused_brds_lstm_step,
    fused_brds_delta_lstm_step,
    fused_brds_lstm_step_q8,
    fused_brds_delta_lstm_step_q8,
    fused_brds_lstm_scan,
    fused_brds_delta_lstm_scan,
    flash_attention,
    decode_attention,
    interpret_mode,
    model_backend,
    rb_project,
    mamba2_ssm_step,
)
from . import ref
