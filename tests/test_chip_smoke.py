"""``chip_smoke.py`` rehearsed on the host at small sizes: its phases and
their references agree, and without a TPU it refuses to report success."""
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "not a TPU" in out.err


def test_join_phase_agrees_with_scalar_interpreter(chip_smoke):
    out = chip_smoke.join_phase(n_triggers=10, events_each=300, seed=3)
    assert out["backends"] == ["numpy"]  # auto, with no TPU present
    assert out["fires"] == out["reference_fires"] == 10
    assert out["calls"] > 0 and out["events"] > 0


def test_serve_phase_agrees_with_teacher_forced_forward(chip_smoke):
    out = chip_smoke.serve_phase(get_config("llama3.2-3b", smoke=True),
                                 n_requests=4, max_batch=2, new_tokens=4,
                                 prompt_len=8, seed=1, timeout=120)
    assert out["served"] == 4 and out["batches"] == 2
    assert out["within_tol"] == out["tokens"] == 16
