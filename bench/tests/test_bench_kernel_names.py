"""The kernel patterns of ``names.json`` against the op names the program's
kernels give themselves (each ``pallas_call`` passes its public function's
name, which the chip's profile shows as ``%<name>.<n> = ...``): a kernel
metric finds its kernel, and no other kernel of the program."""
import importlib

import pytest

import bench_tiny  # noqa: F401
from bench.lib import readers


def _kernels(module: str, *names: str) -> list:
    mod = importlib.import_module(f"repro.kernels.{module}")
    return [getattr(mod, n) for n in names]


KERNELS = (
    _kernels("decode_attention", "decode_attention")
    + _kernels("delta_rb_spmv", "delta_rb_spmv", "delta_rb_dual_spmv")
    + _kernels("flash_attention", "flash_attention")
    + _kernels("fused_step", "fused_brds_lstm_step",
               "fused_brds_delta_lstm_step", "fused_brds_lstm_step_q8",
               "fused_brds_delta_lstm_step_q8", "fused_brds_lstm_scan",
               "fused_brds_delta_lstm_scan")
    + _kernels("lstm_gates", "lstm_gates")
    + _kernels("rb_spmv", "rb_spmv", "rb_dual_spmv")
    + _kernels("rb_spmv_q8", "rb_spmv_q8", "rb_dual_parts_q8"))

# the program kernel each kernel metric of names.json reads
METRIC_KERNELS = {"rb_step": KERNELS[4]}


def _label(name: str) -> str:
    # an op's label as the trace reduction builds it: name, then HLO text
    return (f"{name}.3 %{name}.3 = (f32[32,1500]{{1,0}}) "
            f"custom-call(f32[32,1500]{{1,0}} %p.1), "
            f'custom_call_target="tpu_custom_call"')


def test_metric_kernels_cover_names_json():
    assert set(METRIC_KERNELS) == set(readers.names()["kernels"])


@pytest.mark.parametrize("metric", sorted(METRIC_KERNELS))
@pytest.mark.parametrize("kernel", KERNELS, ids=lambda f: f.__name__)
def test_kernel_pattern_matches_its_kernel_alone(metric, kernel):
    match = readers.matcher(readers.names()["kernels"][metric])
    assert bool(match(_label(kernel.__name__))) == \
        (kernel is METRIC_KERNELS[metric])
