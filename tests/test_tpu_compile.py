"""Compile the chip's main-path programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jaxlib's TPU support,
compiles for a chip that is described, not attached.  That catches what
interpret mode cannot (tile alignment, fast-memory limits, unsupported
lowerings) at no chip time.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch, list_archs
from repro.kernels.merge_pool import merge_pool

K, ROWS, D_MODEL = 4, 2048, 960


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize("strategy", ["avg", "sum", "max", "mul", "concat"])
def test_merge_pool_compiles_for_v5e(one_chip, strategy):
    """Forward and backward kernels at smollm-360m's widths: D=960 for the
    reductions, the K=4 concat cut width 240 (not a multiple of 128)."""
    d = D_MODEL // K if strategy == "concat" else D_MODEL
    x = jax.ShapeDtypeStruct((K, ROWS, d), jnp.float32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((K,), jnp.float32, sharding=one_chip)

    def fwd(x, live):
        return merge_pool(x, live, strategy=strategy)

    def bwd(x, live):
        return jax.grad(lambda t: jnp.sum(fwd(t, live) ** 2))(x)

    for fn in (fwd, bwd):
        compiled = jax.jit(fn).lower(x, live).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_concat_compiles_at_widest_registered_cut(one_chip):
    """concat blocks span the whole K*D row untiled, so their VMEM grows with
    d_model: the widest registered config must still fit."""
    cfg = max((get_arch(n) for n in list_archs()
               if get_arch(n).vertical is not None),
              key=lambda c: c.d_model)
    k = cfg.vertical.num_clients
    x = jax.ShapeDtypeStruct((k, ROWS, cfg.d_model // k), jnp.float32,
                             sharding=one_chip)
    live = jax.ShapeDtypeStruct((k,), jnp.float32, sharding=one_chip)

    def bwd(x, live):
        return jax.grad(lambda t: jnp.sum(
            merge_pool(t, live, strategy="concat") ** 2))(x)

    compiled = jax.jit(bwd).lower(x, live).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_tower_fwd_vjp_compiles_for_v5e(one_chip):
    """One full-width smollm-360m tower (embedding-column slice, 2 blocks,
    cut projection) forward + vjp, from abstract shapes."""
    from repro.models import backbone
    from repro.models.split_program import get_program

    cfg = get_arch("smollm-360m")
    program = get_program(cfg)
    tower = program.tower_fwd(0)
    B, S = 8, 256

    def tower_params():
        params = backbone.init_params(cfg, jax.random.PRNGKey(0))
        return program.partition(params)[0][0]

    tp = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(tower_params))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
    jac = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.float32,
                               sharding=one_chip)

    def fwd_vjp(tp, tokens, jac):
        cut, pullback = jax.vjp(lambda p: tower(p, tokens), tp)
        return cut, pullback(jac)[0]

    compiled = jax.jit(fwd_vjp).lower(tp, tokens, jac).compile()
    cut_shape = compiled.out_info[0].shape
    assert cut_shape == (B, S, cfg.d_model)
