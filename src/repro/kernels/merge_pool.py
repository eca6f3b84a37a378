"""Pallas TPU kernel: fused K-client cut-layer merge (the paper's hot spot).

Baseline lowering reads the K stacked client activations from HBM once per
strategy step (and once more for the drop-renormalization); this kernel does
the whole masked reduction in a single VMEM pass per (B, D) tile — K stays
inside the kernel, so HBM traffic is exactly one read of the stack and one
write of the merged tile.

TPU adaptation notes (DESIGN.md §6): tiles are (block_b, block_d) with
block_d a multiple of 128 (lane width) so the VPU reduction over K is fully
vectorized; K is small (2-8 clients, paper §4) and is unrolled.

``concat`` is a gather, not a reduction: each grid step copies a (K, bB, D)
block of the stack into one (bB, K*D) output row block, client i at static
columns [i*D, (i+1)*D) — one read of the stack, one contiguous write,
live-masking fused in.  The row block spans the whole K*D width, so the cut
width D need not be a multiple of the 128-lane tile (smollm-360m's K=4 cut
is 240 wide), and the (K,) live mask sits in SMEM, where the kernel reads
it as scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.0e38


def _merge_kernel(stacked_ref, live_ref, out_ref, *, strategy: str, k: int):
    live = live_ref[...]  # (K,) f32
    total_live = jnp.sum(live)
    n_live = jnp.maximum(total_live, 1.0)

    def neutral(val, l, fill):
        return jnp.where(l > 0, val, jnp.asarray(fill, val.dtype))

    acc = None
    for i in range(k):  # K is small and static: unroll over clients
        blk = stacked_ref[i].astype(jnp.float32)  # (bB, bD)
        l = live[i]
        if strategy in ("sum", "avg"):
            term = blk * l
            acc = term if acc is None else acc + term
        elif strategy == "max":
            term = neutral(blk, l, NEG_INF)
            acc = term if acc is None else jnp.maximum(acc, term)
        else:  # mul
            term = neutral(blk, l, 1.0)
            acc = term if acc is None else acc * term
    if strategy == "avg":
        acc = acc / n_live
    if strategy == "max":
        # all clients dropped -> zeros, not -inf (raw count: n_live is
        # clamped to >=1 for the avg division and would never hit 0 here)
        acc = jnp.where(total_live > 0, acc, jnp.zeros_like(acc))
    out_ref[...] = acc.astype(out_ref.dtype)


def _concat_kernel(live_ref, stacked_ref, out_ref, *, k: int, d: int):
    """Fused gather-concat over one row block: client i's (bB, D) tile lands
    at columns [i*D, (i+1)*D) of the (bB, K*D) output row; dropped clients
    write zeros.  One HBM read of the stack, one contiguous write."""
    for i in range(k):  # static column offsets: lane-unaligned D is fine
        out_ref[:, i * d:(i + 1) * d] = (
            stacked_ref[i].astype(jnp.float32) * live_ref[i]
        ).astype(out_ref.dtype)


def _concat_fwd_call(stacked, live, *, block_b, interpret):
    K, B, D = stacked.shape
    bb = min(block_b, B)
    return pl.pallas_call(
        functools.partial(_concat_kernel, k=K, d=D),
        grid=(pl.cdiv(B, bb),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((K, bb, D), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, K * D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K * D), stacked.dtype),
        interpret=interpret,
    )(live, stacked)


def _concat_bwd_kernel(live_ref, g_ref, dx_ref, *, k: int, d: int):
    """Jacobian splitting for concat: client i's gradient is its own column
    slice of the merged gradient (zeroed when it was dropped)."""
    for i in range(k):
        dx_ref[i] = (g_ref[:, i * d:(i + 1) * d].astype(jnp.float32)
                     * live_ref[i]).astype(dx_ref.dtype)


def _concat_bwd_call(live, g, *, k, block_b, interpret):
    B = g.shape[0]
    D = g.shape[1] // k
    bb = min(block_b, B)
    return pl.pallas_call(
        functools.partial(_concat_bwd_kernel, k=k, d=D),
        grid=(pl.cdiv(B, bb),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, k * D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((k, bb, D), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, B, D), g.dtype),
        interpret=interpret,
    )(live, g)


def _merge_pool_fwd_call(stacked, live, *, strategy, block_b, block_d,
                         interpret):
    if strategy == "concat":
        return _concat_fwd_call(stacked, live, block_b=block_b,
                                interpret=interpret)
    K, B, D = stacked.shape
    bb, bd = min(block_b, B), min(block_d, D)
    grid = (pl.cdiv(B, bb), pl.cdiv(D, bd))
    return pl.pallas_call(
        functools.partial(_merge_kernel, strategy=strategy, k=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bb, bd), lambda i, j: (0, i, j)),
            pl.BlockSpec((K,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((bb, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, D), stacked.dtype),
        interpret=interpret,
    )(stacked, live)


def _merge_bwd_kernel(stacked_ref, live_ref, out_ref, g_ref, dx_ref, *,
                      strategy: str, k: int):
    """Jacobian splitting (paper §3), fused: route the merged gradient back
    to each client in one VMEM pass.
      sum:  dx_k = g * live_k
      avg:  dx_k = g * live_k / n_live
      max:  dx_k = g * [x_k == merged]  (ties split the credit)
      mul:  dx_k = g * merged / x_k  for live clients (masked x_k == 1)
    """
    live = live_ref[...]
    n_live = jnp.maximum(jnp.sum(live), 1.0)
    g = g_ref[...].astype(jnp.float32)
    out = out_ref[...].astype(jnp.float32)
    if strategy == "max":
        # tie count per element so credit SPLITS among argmax holders —
        # matches autodiff through the jnp oracle (ties are common in bf16)
        ties = None
        for i in range(k):
            x = stacked_ref[i].astype(jnp.float32)
            eq = jnp.where((x == out) & (live[i] > 0), 1.0, 0.0)
            ties = eq if ties is None else ties + eq
        ties = jnp.maximum(ties, 1.0)
    for i in range(k):
        l = live[i]
        if strategy == "sum":
            dx = g * l
        elif strategy == "avg":
            dx = g * (l / n_live)
        elif strategy == "max":
            x = stacked_ref[i].astype(jnp.float32)
            dx = jnp.where((x == out) & (l > 0), g / ties, 0.0)
        else:  # mul
            x = jnp.where(live[i] > 0, stacked_ref[i].astype(jnp.float32), 1.0)
            dx = g * (out / x) * l
        dx_ref[i] = dx.astype(dx_ref.dtype)


def _merge_pool_bwd_call(stacked, live, out, g, *, strategy, block_b, block_d,
                         interpret):
    K, B, D = stacked.shape
    bb, bd = min(block_b, B), min(block_d, D)
    grid = (pl.cdiv(B, bb), pl.cdiv(D, bd))
    return pl.pallas_call(
        functools.partial(_merge_bwd_kernel, strategy=strategy, k=K),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bb, bd), lambda i, j: (0, i, j)),
            pl.BlockSpec((K,), lambda i, j: (0,)),
            pl.BlockSpec((bb, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((K, bb, bd), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((K, B, D), stacked.dtype),
        interpret=interpret,
    )(stacked, live, out, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _merge_pool_diff(stacked, live, strategy, block_b, block_d, interpret):
    return _merge_pool_fwd_call(stacked, live, strategy=strategy,
                                block_b=block_b, block_d=block_d,
                                interpret=interpret)


def _fwd(stacked, live, strategy, block_b, block_d, interpret):
    out = _merge_pool_fwd_call(stacked, live, strategy=strategy,
                               block_b=block_b, block_d=block_d,
                               interpret=interpret)
    return out, (stacked, live, out)


def _bwd(strategy, block_b, block_d, interpret, res, g):
    stacked, live, out = res
    if strategy == "concat":
        dx = _concat_bwd_call(live, g.astype(stacked.dtype),
                              k=stacked.shape[0], block_b=block_b,
                              interpret=interpret)
    else:
        dx = _merge_pool_bwd_call(stacked, live, out, g.astype(stacked.dtype),
                                  strategy=strategy, block_b=block_b,
                                  block_d=block_d, interpret=interpret)
    return dx, None  # live mask is non-differentiable


_merge_pool_diff.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("strategy", "block_b", "block_d",
                                             "interpret"))
def merge_pool(stacked, live=None, *, strategy: str = "avg",
               block_b: int = 128, block_d: int = 512, interpret: bool = False):
    """stacked: (K, B, D); live: (K,) float mask (None = all live).

    Result (B, D) for the reductions, (B, K*D) for the fused gather-concat
    (dropped clients contribute zero columns).  Differentiable: the backward
    pass is a second fused Pallas kernel implementing the paper's jacobian
    splitting (§3) — column-slice routing for concat.

    ``block_d`` tiles only the reductions.  ``concat`` blocks span the whole
    K*D row, untiled, so its VMEM use is about 2*2*K*block_b*D*itemsize
    bytes (input and output blocks, double-buffered): 14 MiB at the widest
    registered cut (K*D = 7168, f32, block_b=128)."""
    K, B, D = stacked.shape
    if live is None:
        live = jnp.ones((K,), jnp.float32)
    live = live.astype(jnp.float32)
    return _merge_pool_diff(stacked, live, strategy, block_b, block_d,
                            interpret)
