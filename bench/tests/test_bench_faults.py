"""A run with the timed path broken underneath comes out not correct: the
step that returns its state unchanged, half of the batch left out of the
loss, and the exchange of cut jacobians left out.  The look for a chip is
skipped; everything else is a whole run at a tiny size, held to the
smollm-360m cell's limits."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from bench_cells import tiny_cell  # noqa: E402

SECONDS = 20.0  # a tiny eager step takes seconds on a loaded CPU


def _unchanged(monkeypatch):
    from repro.optim import AdamW

    monkeypatch.setattr(AdamW, "update", lambda self, p, g, s: (p, s))


def _half_batch(monkeypatch):
    from repro.models.backbone import lm_loss
    from repro.models.split_program import TokenLMSplitProgram

    def loss_fn(self, logits, labels):
        h = labels.shape[0] // 2
        return lm_loss(logits[:h], labels[:h])

    monkeypatch.setattr(TokenLMSplitProgram, "loss_fn", loss_fn)


def _no_exchange(monkeypatch):
    from repro.transport.base import TowerWorker

    backward = TowerWorker._backward

    def dropped(self, request):
        return backward(self, dict(request,
                                   jac=jnp.zeros_like(request["jac"])))

    monkeypatch.setattr(TowerWorker, "_backward", dropped)


@pytest.mark.parametrize("plant", [_unchanged, _half_batch, _no_exchange],
                         ids=["state_unchanged", "half_batch", "no_exchange"])
def test_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    result = run.run_cell(tiny_cell(), 2**31 + 11, SECONDS, False)
    assert result["correct"] is False, result["check"]
