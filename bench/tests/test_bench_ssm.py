"""The plain Mamba-2 reference against the program, the SSD scan's
operation count and the ``ssd.scan_s`` reader on a small recorded trace.
No chip needed."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
from bench_cells import tiny_cell  # noqa: E402
from reference import common, ssm  # noqa: E402
from test_bench_reference import OPT, _close  # noqa: E402
from test_bench_spans import _load, _reader  # noqa: E402

CELL = "mamba2-1.3b.train-b1s2048-w1"
SSM = {"name": "tiny-ssm", "family": "ssm", "num_layers": 3, "d_model": 64,
       "num_heads": 0, "num_kv_heads": 0, "d_ff": 0, "vocab_size": 256,
       "head_dim": 0, "norm_eps": 1e-5, "tie_embeddings": True,
       "ssm": {"d_state": 16, "expand": 2, "head_dim": 16, "n_groups": 1,
               "conv_width": 4, "chunk_size": 8},
       "vertical": {"num_clients": 2, "tower_layers": 1, "merge": "avg"}}


def ssm_cell():
    return tiny_cell(SSM, limits_of=CELL)


def _reduced_arch():
    from repro.configs.base import get_arch

    return dataclasses.asdict(get_arch("mamba2-1.3b").reduced())


@pytest.mark.parametrize("arch,seq", [(SSM, 16), (None, 64)],
                         ids=["tiny", "mamba2-1.3b-reduced"])
def test_reference_matches_the_program(arch, seq):
    from repro.models.split_program import get_program
    from repro.optim import AdamW
    from repro.optim.schedules import linear_warmup_cosine

    arch = arch or _reduced_arch()
    cfg = harness.arch_config(arch)
    weights = ssm.make_weights(arch, jax.random.PRNGKey(3))
    harness.check_layout(cfg, weights)
    rng = np.random.default_rng(0)
    rows = rng.integers(0, cfg.vocab_size, (2, seq + 1)).astype(np.int32)
    tokens, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    program = get_program(cfg)
    loss, tower_grads, server_grads, _ = program.protocol_step(
        weights["towers"], weights["server"],
        program.features({"tokens": tokens}), labels)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(ssm.loss_fn)(
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


def test_cell_weights_match_the_program_layout():
    doc = json.loads((HERE.parent / "configs" / "mamba2-1.3b.json").read_text())
    arch = doc["arch"]
    weights = jax.eval_shape(lambda k: ssm.make_weights(arch, k),
                             jax.random.PRNGKey(0))
    harness.check_layout(harness.arch_config(arch), weights)


def test_reference_recurrence_matches_a_loop_over_time():
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    B, S, H, P, N = 1, 12, 2, 3, 4
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, 1, N))
    Cm = jax.random.normal(ks[4], (B, S, 1, N))
    got = ssm.recurrence(x, dt, A, Bm, Cm)
    x, dt, A, Bm, Cm = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm))
    h = np.zeros((H, P, N))
    for t in range(S):
        for i in range(H):
            h[i] = (np.exp(dt[0, t, i] * A[i]) * h[i]
                    + dt[0, t, i] * np.outer(x[0, t, i], Bm[0, t, 0]))
            np.testing.assert_allclose(got[0, t, i], h[i] @ Cm[0, t, 0],
                                       rtol=1e-4, atol=1e-5)


def test_ssd_scan_cost_hand_count():
    # one chunk of 4 steps, 2 heads of 3, state 5, 1 group: per token
    # 2 * 2 * (4*5 + 4*3 + 2*5*3) = 248; bytes: x and y 2*8*2*3, dt 8*2,
    # B and C 2*8*5, state 2*3*5, A 2, f32
    got = ssm.ssd_scan_cost(1, 8, 2, 3, 5, 1, 4)
    assert got["flops"] == 8 * 248
    assert got["bytes"] == 4 * (96 + 16 + 80 + 30 + 2)


def test_ssm_flops_per_token_hand_count():
    # towers (x2, 1 layer): width 32, d_inner 64, 4 heads of 16, state 16,
    #   chunk 8: proj_in 2*32*32 + in_proj 2*32*(128+32+4)
    #   + out_proj 2*64*32 + scan 2*4*(8*16 + 8*16 + 2*16*16)
    #   + proj_out 2*32*64                                     = 26880
    # server (x2 layers): in_proj 2*64*(256+32+8) + out_proj 2*128*64
    #   + scan 2*8*(8*16 + 8*16 + 2*16*16)                     = 66560
    # head 2*64*256                                            = 32768
    forward = 2 * 26880 + 2 * 66560 + 32768
    assert ssm.flops_per_token(SSM, 16) == pytest.approx(3 * forward)


def test_ssd_scan_reader_on_a_recorded_trace():
    read = _reader("ssd.scan_s")
    ctx = {"arch": SSM, "mix": {"batch": 1}}
    # the server's inner chunk loop (300 ns, not the layer loop around it),
    # a tower's (100), the second step's (200) and 100 ns of the loop the
    # window's end cuts, over the window's 2 steps
    assert read({**ctx, "trace": _load("trace_ssd.json")}) == \
        pytest.approx(700e-9 / 2)
    assert read({**ctx, "trace": None}) is None
    # a trace of the dense program: no chunk loop, no reading
    assert read({**ctx, "trace": _load("trace_small.json")}) is None
    dense = {"arch": {k: v for k, v in SSM.items() if k != "ssm"},
             "mix": {"batch": 1}, "trace": _load("trace_ssd.json")}
    assert read(dense) is None


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", "2147483701", "--seconds", "1", "--trace", "0"],
        cwd=HERE.parents[1], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no CPU fallback" in proc.stderr
