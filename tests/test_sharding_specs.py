"""Sharding-spec rules: shape compatibility, divisibility, client isolation.

Multi-device checks run in a subprocess with XLA_FLAGS so the main test
process keeps the real single-device topology.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import get_arch
from repro.models import backbone
from repro.sharding import specs as specs_lib


def _fake_mesh(shape, axes):
    """An abstract mesh over fake devices — fine for spec construction."""
    import numpy as np

    devs = np.asarray(jax.devices() * (int(np.prod(shape)) // len(jax.devices()) + 1))
    return Mesh(devs[: int(np.prod(shape))].reshape(shape), axes)


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-32b", "deepseek-moe-16b",
                                  "mamba2-1.3b", "zamba2-7b", "whisper-tiny",
                                  "internvl2-26b", "arctic-480b"])
def test_param_specs_are_shape_compatible(arch):
    cfg = get_arch(arch)
    mesh = _fake_mesh((16, 16), ("data", "model"))
    shapes = jax.eval_shape(
        lambda k: backbone.init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)
    )
    spec_tree = specs_lib.param_specs(cfg, shapes, mesh)

    def check(leaf, spec):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if axes is None:
                continue
            size = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                size *= mesh.shape[a]
            assert dim % size == 0, (leaf.shape, spec)

    jax.tree_util.tree_map(
        check, shapes, spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    # at least the big weights must actually be sharded
    flat = jax.tree_util.tree_leaves_with_path(spec_tree,
                                               is_leaf=lambda x: isinstance(x, P))
    sharded = [s for _, s in flat if any(d is not None for d in s)]
    assert len(sharded) > 5, "suspiciously few sharded params"


def test_vocab_fallback_shards_dmodel():
    cfg = get_arch("mamba2-1.3b")  # vocab 50280 not divisible by 16
    mesh = _fake_mesh((16, 16), ("data", "model"))
    shapes = jax.eval_shape(
        lambda k: backbone.init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)
    )
    spec_tree = specs_lib.param_specs(cfg, shapes, mesh)
    table_spec = spec_tree["embed"]["table"]
    assert table_spec[0] is None and table_spec[1] == "model"


def test_client_factored_mesh_tower_isolation_spec():
    cfg = get_arch("smollm-360m")
    mesh = _fake_mesh((16, 4, 4), ("data", "client", "tp"))
    shapes = jax.eval_shape(
        lambda k: backbone.init_params(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)
    )
    spec_tree = specs_lib.param_specs(cfg, shapes, mesh, vertical_mode="client")
    tower_spec = spec_tree["towers"]["proj_in"]  # (K, d_slice, d_t)
    assert tower_spec[0] == "client", tower_spec
    # tower internals restricted to tp — never the client axis
    def no_client_in_tail(spec):
        for d in tuple(spec)[1:]:
            axes = d if isinstance(d, tuple) else (d,)
            assert "client" not in axes, spec
    jax.tree_util.tree_map(no_client_in_tail, spec_tree["towers"],
                           is_leaf=lambda x: isinstance(x, P))
    # server weights use the full factored model axis
    server_wq = spec_tree["server"]["attn"]["wq"]
    assert ("client", "tp") in tuple(server_wq) or "tp" in tuple(server_wq)


def test_batch_specs():
    mesh = _fake_mesh((16, 16), ("data", "model"))
    shapes = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32),
              "odd": jax.ShapeDtypeStruct((1, 8), jnp.float32)}
    sp = specs_lib.batch_specs(shapes, mesh)
    assert sp["tokens"] == P("data", None)
    assert sp["odd"] == P(None, None)


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import merge as merge_lib

    mesh = jax.make_mesh((2, 4), ("data", "client"))
    x = jnp.arange(4 * 8 * 16, dtype=jnp.float32).reshape(4, 8, 16)

    for strategy, tol in [("sum", 1e-5), ("avg", 1e-5), ("max", 1e-5),
                          ("mul", 1e-2), ("concat", 1e-5)]:
        def local_fn(xk):
            # xk: (1, 8shard?, 16) -> per-client block
            out = merge_lib.merge_collective(xk[0], strategy, "client")
            return out[None]

        # check_vma=False: all_gather+prod / concat outputs are replicated in
        # value but the static varying-axes check cannot prove it
        f = shard_map(local_fn, mesh=mesh,
                      in_specs=P("client", "data", None),
                      out_specs=P(None, "data", None),
                      check_vma=False)
        got = f(x)[0]
        want = merge_lib.merge_stacked(x, strategy)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        print(strategy, "ok")
    print("ALL_OK")
""")


def test_merge_collective_matches_stacked_on_8_devices():
    """The collective realization of each merge == the stacked oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    res = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=300)
    assert "ALL_OK" in res.stdout, res.stdout + res.stderr
