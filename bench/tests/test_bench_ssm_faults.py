"""The tiny ssm cell, held to the mamba2-1.3b cell's limits: a run with the
timed path broken underneath is not correct (the planted faults of
``test_bench_faults.py``, and the gate applied after the norm)."""
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from test_bench_faults import (SECONDS, _half_batch, _no_exchange,  # noqa: E402
                               _unchanged)
from test_bench_ssm import ssm_cell  # noqa: E402


def _gate_after_norm(monkeypatch):
    from repro.models import layers, mamba

    def norm_then_gate(params, y, z, groups, eps=1e-5):
        return layers.rmsnorm(params, y, eps) * jax.nn.silu(z)

    monkeypatch.setattr(mamba, "gated_rmsnorm", norm_then_gate)
    jax.clear_caches()  # the Mamba stack is compiled once per shape


@pytest.fixture(autouse=True)
def _fresh_programs():
    """No compiled Mamba stack outlives the fault it was traced under."""
    yield
    jax.clear_caches()


@pytest.mark.parametrize(
    "plant", [_unchanged, _half_batch, _no_exchange, _gate_after_norm],
    ids=["state_unchanged", "half_batch", "no_exchange", "gate_after_norm"])
def test_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    result = run.run_cell(ssm_cell(), 2**31 + 11, SECONDS, False)
    assert result["correct"] is False, result["check"]
