"""The Mamba-2 block's training path: finite SSD gradients where the
log-decays summed over a chunk are large, the published gated RMSNorm, and
the count of the chunk scan's traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SSMConfig, get_arch
from repro.models import backbone, layers
from repro.models import mamba as mamba_lib
from repro.models.split_program import get_program


def _inputs(B, S, H, P, N, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = decay * (1.0 + jax.random.uniform(ks[1], (B, S, H)))
    A = -1.0 - jax.random.uniform(ks[2], (H,))
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("decay", [0.05, 2.0], ids=["small", "large"])
def test_ssd_gradients_are_finite(decay):
    B, S, H, P, N, chunk = 1, 64, 2, 8, 4, 32
    args = _inputs(B, S, H, P, N, decay)
    a = args[1] * args[2][None, None, :]
    # the largest |dt * A| summed over one chunk
    assert decay < 1 or float(-a.reshape(B, S // chunk, chunk, H)
                              .sum(axis=2).max()) > 100

    def loss(*a):
        y, state = mamba_lib.ssd_chunked(*a, chunk=chunk)
        return jnp.sum(y ** 2) + jnp.sum(state ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    for name, g in zip(("x", "dt", "A", "B", "C"), grads):
        assert bool(jnp.all(jnp.isfinite(g))), name


@pytest.mark.parametrize("groups", [1, 2])
def test_gated_norm_is_rmsnorm_of_the_gated_output(groups):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    d = 32
    y = jax.random.normal(ks[0], (2, 5, d))
    z = jax.random.normal(ks[1], (2, 5, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
    got = mamba_lib.gated_rmsnorm({"scale": scale}, y, z, groups)
    gated = (y * jax.nn.silu(z)).reshape(2, 5, groups, d // groups)
    ones = {"scale": jnp.ones((d // groups,))}
    want = layers.rmsnorm(ones, gated).reshape(2, 5, d) * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the gate goes in before the norm, not after it
    after = layers.rmsnorm({"scale": scale}, y) * jax.nn.silu(z)
    assert float(jnp.max(jnp.abs(got - after))) > 1e-2


def test_decode_step_uses_the_gated_norm_of_the_full_pass():
    cfg = SSMConfig(d_state=8, head_dim=8, chunk_size=4)
    d = 16
    params = mamba_lib.init_mamba(jax.random.PRNGKey(1), d, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, d))
    full, _, _ = mamba_lib.mamba_apply(params, x[:, :4], cfg, d)
    H, P, N = cfg.n_heads(d), cfg.head_dim, cfg.d_state
    ch = cfg.d_inner(d) + 2 * cfg.n_groups * N
    ssm = jnp.zeros((1, H, P, N))
    conv = jnp.zeros((1, cfg.conv_width - 1, ch))
    outs = []
    for t in range(4):
        out, ssm, conv = mamba_lib.mamba_decode_step(
            params, x[:, t:t + 1], ssm, conv, cfg, d)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), full,
                               rtol=2e-4, atol=2e-5)


def test_chunk_scan_traces_once_per_shape_under_jit():
    args = _inputs(1, 16, 2, 4, 4, 0.1)
    fn = jax.jit(lambda *a: mamba_lib.ssd_chunked(*a, chunk=8)[0])
    before = mamba_lib.ssd_traces()
    fn(*args)
    fn(*args)
    assert mamba_lib.ssd_traces() == before + 1
    mamba_lib.ssd_chunked(*args, chunk=8)  # eager: traced on every call
    assert mamba_lib.ssd_traces() == before + 2


def test_eager_server_step_traces_the_ssd_scan_once():
    """Role 0's server step runs eagerly; the Mamba stack inside it is a
    compiled program, so a second step traces no chunk scan."""
    cfg = get_arch("mamba2-1.3b").reduced()
    program = get_program(cfg)
    _, server = program.partition(
        backbone.init_params(cfg, jax.random.PRNGKey(0)))
    merged = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    labels = jnp.zeros((1, 64), jnp.int32)

    def loss(sp, m):
        return program.loss_fn(program.server_fwd(sp, m), labels)

    step = jax.value_and_grad(loss, argnums=(0, 1))
    first, _ = step(server, merged)
    before = mamba_lib.ssd_traces()
    again, _ = step(server, merged)
    assert mamba_lib.ssd_traces() == before
    assert float(again) == float(first)
