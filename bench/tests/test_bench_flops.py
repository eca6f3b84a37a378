"""Operation and byte counts of the benchmark against hand counts."""
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from reference import dense  # noqa: E402

DENSE = {"name": "tiny-dense", "family": "dense", "num_layers": 3,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
         "vocab_size": 256, "head_dim": 0, "rope_theta": 10000.0,
         "norm_eps": 1e-5, "tie_embeddings": True,
         "vertical": {"num_clients": 2, "tower_layers": 1, "merge": "avg"}}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dense_flops_per_token_hand_count():
    # towers (x2): width 2 heads * 16 = 32, 1 kv head, MLP 64, seq 16
    #   proj_in 2*32*32 + q,k,v,o 2*32*(32+16+16+32) + MLP 3*2*32*64
    #   + attention 2 * 2*16*2*8.5 + proj_out 2*32*64          = 25664
    # server (x2 layers): 2*64*(64+32+32+64) + 3*2*64*128
    #   + 2 * 2*16*4*8.5                                       = 75904
    # head 2*64*256                                            = 32768
    forward = 2 * 25664 + 2 * 75904 + 32768
    assert dense.flops_per_token(DENSE, 16) == pytest.approx(3 * forward)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_calls(sub)


@pytest.mark.parametrize("phase", ["forward", "backward"])
def test_merge_pool_bytes_match_the_kernels_shapes(phase):
    # every operand and result of the program's pallas_call, as traced
    from repro.kernels import ops

    roof = _reader("merge_pool_roofline")
    K, rows, d = 4, 1024, 960
    x = jax.ShapeDtypeStruct((K, rows, d), jnp.float32)
    g = jax.ShapeDtypeStruct((rows, d), jnp.float32)

    def merge(s):
        return ops.merge_pool(s, strategy="avg", use_pallas=True)

    jaxpr = jax.make_jaxpr(lambda s, g: jax.vjp(merge, s)[1](g))(x, g)
    rank = {"forward": 2, "backward": 3}[phase]
    [call] = [e for e in _pallas_calls(jaxpr.jaxpr)
              if e.outvars[0].aval.ndim == rank]
    moved = sum(v.aval.size * v.aval.dtype.itemsize
                for v in call.invars + call.outvars)
    assert roof.cost(K, rows, d, 4)[phase] == (moved, K * rows * d)
    assert moved == {"forward": 19660816, "backward": 39321616}[phase]


def test_merge_pool_roofline_reader_on_the_fixture():
    roof = _reader("merge_pool_roofline")
    doc = json.loads((HERE / "trace_small.json").read_text())
    tr = {k: [tuple(e) for e in doc[k]] for k in ("device", "modules", "host")}
    peak = {"hbm_bytes_per_s": 1e10, "bf16_flops": 1e12}
    arch = {"vertical": {"num_clients": 2, "merge": "avg"}, "d_model": 8}
    mix = {"batch": 1, "seq": 2}
    got = roof.read({"trace": tr, "peak": peak, "arch": arch, "mix": mix})
    # the two jit_merge_pool programs (110 + 70 ns): the forward moves
    # 3 * 16 elements * 4 bytes + the mask's 8 (20 ns at 10 GB/s), the
    # backward 6 * 16 * 4 + 8 (39.2 ns)
    assert got == pytest.approx(100 * (20 + 39.2) / 180)
