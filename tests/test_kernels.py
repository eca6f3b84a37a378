"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
parametrized core cases + hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.merge_pool import merge_pool
from repro.models import mamba as mamba_lib


# ---------------------------------------------------------------------------
# merge_pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("strategy", ["sum", "avg", "max", "mul"])
@pytest.mark.parametrize("k,b,d", [(2, 8, 128), (4, 32, 256), (5, 100, 384)])
def test_merge_pool_matches_ref(k, b, d, strategy, dtype):
    x = jax.random.normal(jax.random.PRNGKey(k * 7 + d), (k, b, d), dtype)
    live = (jax.random.uniform(jax.random.PRNGKey(k * 7 + d + 1), (k,)) > 0.3)
    live = live.at[0].set(True).astype(jnp.float32)
    got = merge_pool(x, live, strategy=strategy, block_b=32, block_d=128,
                     interpret=True)
    want = ref.merge_pool(x, strategy, live)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_merge_pool_matches_ref_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=12, deadline=None)
    @given(
        k=st.integers(2, 5),
        b=st.sampled_from([8, 32, 100]),
        d=st.sampled_from([128, 256, 384]),
        strategy=st.sampled_from(["sum", "avg", "max", "mul"]),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
        seed=st.integers(0, 99),
    )
    def prop(k, b, d, strategy, dtype, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (k, b, d), dtype)
        live = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (k,)) > 0.3)
        live = live.at[0].set(True).astype(jnp.float32)
        got = merge_pool(x, live, strategy=strategy, block_b=32, block_d=128,
                         interpret=True)
        want = ref.merge_pool(x, strategy, live)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol,
            atol=tol
        )

    prop()


@pytest.mark.parametrize("strategy", ["sum", "avg", "max", "mul"])
def test_merge_pool_backward_kernel_matches_autodiff(strategy):
    """The fused Pallas backward (jacobian splitting, paper §3) must equal
    autodiff through the pure-jnp merge."""
    from repro.core import merge as merge_lib

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 128))
    live = jnp.array([1.0, 0.0, 1.0, 1.0])
    w = jax.random.normal(jax.random.PRNGKey(1), (128,))

    gk = jax.grad(lambda t: jnp.sum(
        merge_pool(t, live, strategy=strategy, block_b=16, block_d=128,
                   interpret=True) * w))(x)
    gr = jax.grad(lambda t: jnp.sum(
        merge_lib.merge_stacked(t, strategy, live_mask=live) * w))(x)
    np.testing.assert_allclose(gk, gr, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("strategy", ["sum", "avg", "max", "mul"])
def test_merge_pool_backward_all_strategies_vs_oracle(strategy, dtype):
    """Backward vs the merge_stacked jnp oracle for every strategy,
    including a bf16 stack (the kernel accumulates in f32 and casts the
    jacobian back to the input dtype)."""
    from repro.core import merge as merge_lib

    x = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 128), dtype)
    live = jnp.array([1.0, 0.0, 1.0])
    w = jax.random.normal(jax.random.PRNGKey(4), (128,))

    def k_loss(t):
        out = merge_pool(t, live, strategy=strategy, block_b=16, block_d=128,
                         interpret=True)
        return jnp.sum(out.astype(jnp.float32) * w)

    def r_loss(t):
        out = merge_lib.merge_stacked(t, strategy, live_mask=live)
        return jnp.sum(out.astype(jnp.float32) * w)

    gk, gr = jax.grad(k_loss)(x), jax.grad(r_loss)(x)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        gk.astype(jnp.float32), gr.astype(jnp.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("strategy", ["sum", "avg", "max", "mul"])
def test_merge_pool_all_clients_dropped(strategy):
    """live == 0 everywhere: forward hits the neutral-element edge case
    (max specially zeroes) and every client's jacobian must be zero."""
    from repro.core import merge as merge_lib

    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16, 128))
    live = jnp.zeros((4,))
    w = jax.random.normal(jax.random.PRNGKey(6), (128,))

    got = merge_pool(x, live, strategy=strategy, block_b=16, block_d=128,
                     interpret=True)
    want = ref.merge_pool(x, strategy, live)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    gk = jax.grad(lambda t: jnp.sum(
        merge_pool(t, live, strategy=strategy, block_b=16, block_d=128,
                   interpret=True) * w))(x)
    gr = jax.grad(lambda t: jnp.sum(
        merge_lib.merge_stacked(t, strategy, live_mask=live) * w))(x)
    np.testing.assert_allclose(gk, np.zeros_like(gk), atol=1e-6)
    np.testing.assert_allclose(gk, gr, rtol=1e-6, atol=1e-6)


def test_merge_pool_ragged_tiles():
    """B/D not multiples of the block size exercise tile padding."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 37, 130))
    got = merge_pool(x, strategy="avg", block_b=16, block_d=128, interpret=True)
    np.testing.assert_allclose(got, ref.merge_pool(x, "avg"), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,b,d", [(2, 8, 128), (4, 32, 256), (3, 37, 100)])
def test_merge_pool_concat_matches_ref(k, b, d, dtype):
    """Fused gather-concat (the last merge off the fast path): client k's
    tile lands at columns [k*D, (k+1)*D), dropped clients contribute zero
    columns; D=100 exercises the divisor fallback tile width."""
    x = jax.random.normal(jax.random.PRNGKey(k * 11 + d), (k, b, d), dtype)
    live = (jax.random.uniform(jax.random.PRNGKey(d), (k,)) > 0.3)
    live = live.at[0].set(True).astype(jnp.float32)
    got = merge_pool(x, live, strategy="concat", block_b=16, block_d=128,
                     interpret=True)
    want = ref.merge_pool(x, "concat", live)
    assert got.shape == (b, k * d)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=1e-6,
        atol=1e-6
    )


@pytest.mark.parametrize("k,b,d", [(2, 8, 128), (3, 37, 100)])
def test_merge_pool_concat_backward_matches_autodiff(k, b, d):
    """Concat jacobian splitting: each client gets exactly its own column
    slice of the merged gradient (zeroed when dropped) — must equal
    autodiff through the jnp oracle."""
    from repro.core import merge as merge_lib

    x = jax.random.normal(jax.random.PRNGKey(0), (k, b, d))
    live = jnp.ones((k,)).at[k - 1].set(0.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (k * d,))

    gk = jax.grad(lambda t: jnp.sum(
        merge_pool(t, live, strategy="concat", block_b=16, block_d=128,
                   interpret=True) * w))(x)
    gr = jax.grad(lambda t: jnp.sum(
        merge_lib.merge_stacked(t, "concat", live_mask=live) * w))(x)
    np.testing.assert_allclose(gk[k - 1], np.zeros_like(gk[k - 1]), atol=1e-6)
    np.testing.assert_allclose(gk, gr, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,s,d", [(1, 2, 128, 32), (2, 3, 256, 64)])
def test_flash_matches_ref(b, h, s, d, causal, dtype):
    qkv = jax.random.normal(jax.random.PRNGKey(s + d), (3, b, h, s, d), dtype)
    got = flash_attention(*qkv, causal=causal, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.flash_attention(*qkv, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_flash_matches_ref_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(
        b=st.integers(1, 2),
        h=st.integers(1, 3),
        s=st.sampled_from([128, 256]),
        d=st.sampled_from([32, 64]),
        causal=st.booleans(),
        dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
        seed=st.integers(0, 99),
    )
    def prop(b, h, s, d, causal, dtype, seed):
        qkv = jax.random.normal(jax.random.PRNGKey(seed), (3, b, h, s, d), dtype)
        got = flash_attention(*qkv, causal=causal, block_q=64, block_kv=64,
                              interpret=True)
        want = ref.flash_attention(*qkv, causal=causal)
        tol = 3e-2 if dtype == jnp.bfloat16 else 5e-4
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), rtol=tol,
            atol=tol
        )

    prop()


def test_flash_matches_model_chunked_path():
    """The model's lax-flash (chunked) path is itself the kernel's oracle."""
    from repro.models import attention as attn_lib

    B, S, H, D = 2, 256, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D))
    pos = jnp.arange(S)
    lax_flash = attn_lib.chunked_flash_attention(
        q, k, v, causal=True, q_positions=pos, kv_positions=pos,
        q_chunk=64, kv_chunk=64,
    )
    pallas = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, block_q=64, block_kv=64, interpret=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(pallas, lax_flash, rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

def _ssd_inputs(B, S, H, P, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) * 0.5)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("s,p,n,chunk", [(64, 16, 16, 16), (128, 32, 32, 32)])
def test_ssd_kernel_matches_chunked_model(s, p, n, chunk):
    x, dt, A, Bm, Cm = _ssd_inputs(2, s, 2, p, n, seed=s)
    want_y, want_st = mamba_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    got_y, got_st = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got_y, want_y, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(got_st, want_st, rtol=3e-4, atol=3e-4)


def test_ssd_kernel_matches_chunked_model_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=6, deadline=None)
    @given(
        s=st.sampled_from([64, 128]),
        p=st.sampled_from([16, 32]),
        n=st.sampled_from([16, 32]),
        chunk=st.sampled_from([16, 32]),
        seed=st.integers(0, 99),
    )
    def prop(s, p, n, chunk, seed):
        x, dt, A, Bm, Cm = _ssd_inputs(2, s, 2, p, n, seed)
        want_y, want_st = mamba_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        got_y, got_st = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                                     interpret=True)
        np.testing.assert_allclose(got_y, want_y, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(got_st, want_st, rtol=3e-4, atol=3e-4)

    prop()


def _large_decay_inputs(B, S, H, P, N, seed=0):
    """Log-decays |dt * A| of 2-6 a step: over a chunk of 32 they sum past
    100, where ``exp`` of the negated sums above the diagonal overflows."""
    x, dt, A, Bm, Cm = _ssd_inputs(B, S, H, P, N, seed)
    dt = 1.0 + jax.random.uniform(jax.random.PRNGKey(seed + 1), dt.shape)
    A = -2.0 - jax.random.uniform(jax.random.PRNGKey(seed + 2), A.shape)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("S,chunk,inputs", [
    (32, 8, _ssd_inputs),
    (64, 32, _large_decay_inputs),
], ids=["moderate_decay", "large_decay"])
def test_ssd_chunked_matches_sequential_recurrence(S, chunk, inputs):
    """Ground truth: the exact step-by-step SSM recurrence."""
    B, H, P, N = 1, 2, 8, 4
    x, dt, A, Bm, Cm = inputs(B, S, H, P, N, seed=3)
    y_chunk, state_chunk = mamba_lib.ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)

    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t] * A[None, :])  # (B,H)
        Bt = jnp.repeat(Bm[:, t], H, axis=1)  # (B,H,N)
        Ct = jnp.repeat(Cm[:, t], H, axis=1)
        state = state * decay[..., None, None] + jnp.einsum(
            "bhn,bhp,bh->bhpn", Bt, x[:, t], dt[:, t]
        )
        ys.append(jnp.einsum("bhn,bhpn->bhp", Ct, state))
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_seq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state_chunk, state, rtol=1e-4, atol=1e-4)


def test_ops_dispatch_cpu_uses_ref():
    """On CPU (no TPU) the default path must be the oracle, not Pallas."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    out = ops.merge_pool(x, strategy="max")
    np.testing.assert_allclose(out, ref.merge_pool(x, "max"), rtol=1e-6)
