"""The plain dense reference against the program at the ``.reduced()`` size
of two registered configurations (tied embeddings; untied with grouped
key/value heads): the loss, the gradients of both partitions, and one
updated step."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
from reference import common, dense  # noqa: E402

OPT = {"learning_rate": 3e-4, "warmup": 20, "schedule_steps": 1000,
       "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0}


def _close(got, want, rtol):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=rtol * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("arch_name,family", [("smollm-360m", dense),
                                              ("starcoder2-3b", dense)])
def test_reference_matches_the_program(arch_name, family):
    from repro.configs.base import get_arch
    from repro.models.split_program import get_program
    from repro.optim import AdamW
    from repro.optim.schedules import linear_warmup_cosine

    cfg = get_arch(arch_name).reduced()
    arch = dataclasses.asdict(cfg)
    weights = family.make_weights(arch, jax.random.PRNGKey(3))
    harness.check_layout(cfg, weights)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, cfg.vocab_size, (2, 17)).astype(np.int32)
    tokens, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    program = get_program(cfg)
    loss, tower_grads, server_grads, _ = program.protocol_step(
        weights["towers"], weights["server"],
        program.features({"tokens": tokens}), labels)
    ref_loss, ref_grads = jax.value_and_grad(family.loss_fn)(
        weights, tokens, labels, arch)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    _close(server_grads, ref_grads["server"], 1e-4)
    _close(tower_grads, ref_grads["towers"], 1e-4)

    opt = AdamW(learning_rate=linear_warmup_cosine(3e-4, 20, 1000),
                weight_decay=0.1, grad_clip_norm=1.0)
    stepped, _ = opt.update(weights["server"], server_grads,
                            opt.init(weights["server"]))
    ref_stepped, _, _ = common.adamw(weights["server"], ref_grads["server"],
                                     common.adamw_init(weights["server"]), OPT)
    _close(stepped, ref_stepped, 1e-5)
