"""chip_smoke.py rehearsed on the CPU at tiny widths (Pallas interpret mode).

The script's phases run here exactly as on the chip, on a small LSTM, so a
wrong path, argument or check is found before any chip time is spent. The
script itself must refuse to run without a TPU, and must fail in a
directory that holds nothing else of the repository.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    from repro.models import LSTMConfig
    return LSTMConfig("tiny", input_size=24, hidden=40, vocab_size=64)


def test_one_chip_phases_at_tiny_width(smoke, tiny):
    st = smoke.lockstep(tiny, seed=0, batch=4, prompt_len=8, gen=6)
    assert st["tokens"].shape == (4, 6)
    smoke.scheduler(tiny, st, slots=2, requests=4, gen=6)
    smoke.variants(tiny, seed=0, batch=2, prompt_len=6, gen=3)


def test_four_chip_phase_on_forced_host_devices():
    code = f"""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.models import LSTMConfig
    cfg = LSTMConfig("tiny", input_size=24, hidden=40, vocab_size=64)
    smoke.four_chips(cfg, seed=0, batch=4, prompt_len=8, gen=6)
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "packed params span 4 devices" in out.stdout
    assert "token agreement 1.0000" in out.stdout


def _run(script, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "platform=cpu" in out.stdout


def test_fails_alone_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(tmp_path / "chip_smoke.py", tmp_path, PYTHONPATH="")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
