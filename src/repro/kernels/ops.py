"""Dispatch between the Pallas TPU kernels and their pure-jnp oracles.

On a TPU backend the model path runs the compiled Pallas kernels (``python
chip_smoke.py`` checks that the compiled programs contain them).  On any
other backend the default is the jnp oracle from ``kernels.ref``; tests run
the kernels themselves on the CPU with ``interpret=True``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.merge_pool import merge_pool as _merge_pallas
from repro.kernels.ssd_scan import ssd_chunk_batch as _ssd_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def merge_pool(stacked, live=None, *, strategy="avg", use_pallas=None,
               interpret=False):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas or interpret:
        return _merge_pallas(stacked, live, strategy=strategy,
                             interpret=interpret or not _on_tpu())
    return ref.merge_pool(stacked, strategy, live)


def flash_attention(q, k, v, *, causal=True, use_pallas=None, interpret=False,
                    block_q=512, block_kv=512):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas or interpret:
        return _flash_pallas(q, k, v, causal=causal, block_q=block_q,
                             block_kv=block_kv,
                             interpret=interpret or not _on_tpu())
    return ref.flash_attention(q, k, v, causal=causal)


def ssd_scan(x, dt, A, Bm, Cm, chunk: int, *, use_pallas=None, interpret=False,
             initial_state=None):
    """Full SSD over a sequence using the chunk kernel + host inter-chunk scan.

    x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm/Cm: (B, S, 1, N) (n_groups=1).
    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    a = (dt * A[None, None, :]).astype(jnp.float32)
    xdt = (x * dt[..., None]).astype(jnp.float32)

    # layout: (B, H, nc, Q, ...) flattened to the kernel grid
    xg = xdt.reshape(B, nc, Q, H, P).transpose(0, 3, 1, 2, 4).reshape(-1, Q, P)
    ag = a.reshape(B, nc, Q, H).transpose(0, 3, 1, 2).reshape(-1, Q)
    Bg = jnp.broadcast_to(
        Bm.reshape(B, nc, Q, 1, N), (B, nc, Q, H, N)
    ).transpose(0, 3, 1, 2, 4).reshape(-1, Q, N)
    Cg = jnp.broadcast_to(
        Cm.reshape(B, nc, Q, 1, N), (B, nc, Q, H, N)
    ).transpose(0, 3, 1, 2, 4).reshape(-1, Q, N)

    if use_pallas or interpret:
        y_i, states, decays, cums = _ssd_pallas(
            xg, ag, Bg, Cg, interpret=interpret or not _on_tpu()
        )
    else:
        y_i, states, decays, cums = jax.vmap(ref.ssd_chunk)(xg, ag, Bg, Cg)
        decays = decays.reshape(-1, 1)

    y_i = y_i.reshape(B, H, nc, Q, P)
    states = states.reshape(B, H, nc, P, N)
    decays = decays.reshape(B, H, nc)
    cums = cums.reshape(B, H, nc, Q)

    # inter-chunk recurrence (sequential, tiny): carry (B, H, P, N)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    def step(carry, inp):
        st, dec = inp  # (B,H,P,N), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry  # emit the state *entering* this chunk

    final, prevs = jax.lax.scan(
        step, initial_state,
        (states.transpose(2, 0, 1, 3, 4), decays.transpose(2, 0, 1)),
    )
    prevs = prevs.transpose(1, 2, 0, 3, 4)  # (B, H, nc, P, N)

    # inter-chunk output: y_off[q] = exp(cum_q) * C_q @ state_in
    Cg5 = Cg.reshape(B, H, nc, Q, N)
    y_off = jnp.einsum("bhcqn,bhcpn->bhcqp", Cg5, prevs) * jnp.exp(
        cums
    )[..., None]
    y = (y_i + y_off).reshape(B, H, S // Q, Q, P)
    y = y.transpose(0, 2, 3, 1, 4).reshape(B, S, H, P)
    return y, final
