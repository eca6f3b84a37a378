"""Mamba2 (SSD — state-space duality) block, faithful to arXiv:2405.21060.

Train/prefill path: chunked SSD — intra-chunk quadratic ("attention-like")
term + inter-chunk linear state recurrence, scanned over chunks so peak
memory is O(chunk^2) not O(S^2).  Decode path: exact single-step recurrence
with a conv ring state.  The chunk computation is the oracle for the Pallas
``ssd_scan`` kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models import layers


def init_mamba(key, d_model: int, cfg: SSMConfig, dtype=jnp.float32):
    d_inner = cfg.d_inner(d_model)
    H = cfg.n_heads(d_model)
    G, N, W = cfg.n_groups, cfg.d_state, cfg.conv_width
    d_conv_ch = d_inner + 2 * G * N  # conv runs over [x, B, C]
    d_proj = 2 * d_inner + 2 * G * N + H  # [z, x, B, C, dt]
    k_in, k_conv, k_out, k_dt, k_A = jax.random.split(key, 5)
    return {
        "in_proj": layers.dense_init(k_in, d_model, d_proj, dtype),
        "conv_w": (jax.random.normal(k_conv, (W, d_conv_ch)) / math.sqrt(W)).astype(dtype),
        "conv_b": jnp.zeros((d_conv_ch,), dtype=dtype),
        "A_log": jnp.log(
            jax.random.uniform(k_A, (H,), jnp.float32, 1.0, 16.0)
        ),  # A = -exp(A_log), init in [-16, -1]
        "dt_bias": jnp.log(
            jnp.expm1(jax.random.uniform(k_dt, (H,), jnp.float32, 1e-3, 1e-1))
        ),  # softplus^-1(dt) for dt in [1e-3, 1e-1]
        "D": jnp.ones((H,), dtype=jnp.float32),
        "norm": layers.init_rmsnorm(d_inner, dtype),
        "out_proj": layers.dense_init(k_out, d_inner, d_model, dtype),
    }


def _split_proj(proj, d_inner: int, G: int, N: int, H: int):
    z, x, B, C, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + G * N, 2 * d_inner + 2 * G * N],
        axis=-1,
    )
    return z, x, B, C, dt


def _causal_conv(u, w, b):
    """Depthwise causal conv, u: (B, S, ch), w: (W, ch)."""
    W = w.shape[0]
    pads = [jnp.pad(u, ((0, 0), (W - 1 - i, 0), (0, 0)))[:, : u.shape[1], :] * w[i]
            for i in range(W)]
    return sum(pads) + b


def _segsum_exp(a):
    """a: (..., Q) log-decays -> L: (..., Q, Q) with L[i,j]=exp(sum_{j<t<=i} a_t),
    lower-triangular (i >= j), zero elsewhere.

    The exponent is masked to -inf before the ``exp``: above the diagonal
    ``cum_i - cum_j`` is a sum of NEGATED decays, which overflows ``exp``
    over a long chunk, and the backward would then multiply its zero
    cotangent by inf."""
    Q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]  # (..., i, j) = sum_{j<t<=i}
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


_SSD_TRACES = [0]


def ssd_traces() -> int:
    """How many times ``ssd_chunked`` was traced (its Python body runs only
    then): once per input shape under ``jax.jit``, once per call in an
    eager caller."""
    return _SSD_TRACES[0]


def gated_rmsnorm(params, y, z, groups: int, eps: float = 1e-5):
    """Mamba-2's gated RMSNorm (``norm_before_gate=False``):
    ``rmsnorm(y * silu(z))``, normalized over each group of
    ``d_inner / groups`` columns, in f32."""
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    lead, d = g.shape[:-1], g.shape[-1]
    g = g.reshape(*lead, groups, d // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    g = g.reshape(*lead, d) * params["scale"].astype(jnp.float32)
    return g.astype(y.dtype)


def ssd_chunked(x, dt, A, Bmat, Cmat, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: (B, S, H, P) — inputs per head
    dt: (B, S, H) — positive step sizes
    A: (H,) — negative decay rates
    Bmat/Cmat: (B, S, G, N) — input/output projections (G groups, GQA-style)
    Returns (y: (B, S, H, P), final_state: (B, H, P, N)).

    Each chunk step is recomputed in the backward pass (``jax.checkpoint``):
    only the (B, H, P, N) state between chunks is saved, not the chunk's
    (Q, Q) decay and score matrices.  On the device the scan is one
    ``while`` loop that carries the state.
    """
    _SSD_TRACES[0] += 1
    Bsz, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    # decay per step: a = dt * A  (log-space), input scale dt
    a = (dt * A[None, None, :]).astype(jnp.float32)  # (B, S, H), negative
    xdt = (x * dt[..., None]).astype(jnp.float32)  # (B, S, H, P)

    ac = a.reshape(Bsz, nc, Q, H)
    xc = xdt.reshape(Bsz, nc, Q, H, P)
    Bc = Bmat.reshape(Bsz, nc, Q, G, N).astype(jnp.float32)
    Cc = Cmat.reshape(Bsz, nc, Q, G, N).astype(jnp.float32)

    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    @jax.checkpoint
    def chunk_step(state, inputs):
        a_q, x_q, B_q, C_q = inputs
        cum = jnp.cumsum(a_q, axis=1)
        L = _segsum_exp(jnp.moveaxis(a_q, 1, -1))
        C_rep = jnp.repeat(C_q, rep, axis=2)  # (B,Q,H,N)
        B_rep = jnp.repeat(B_q, rep, axis=2)
        scores = jnp.einsum("bqhn,bkhn->bhqk", C_rep, B_rep)
        y_intra = jnp.einsum("bhqk,bkhp->bqhp", scores * L, x_q)
        y_inter = jnp.einsum("bqhn,bhpn,bqh->bqhp", C_rep, state, jnp.exp(cum))
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)
        new_contrib = jnp.einsum("bqhn,bqhp,bqh->bhpn", B_rep, x_q, decay_to_end)
        full_decay = jnp.exp(cum[:, -1, :])
        new_state = state * full_decay[:, :, None, None] + new_contrib
        return new_state, (y_intra + y_inter).astype(x.dtype)

    xs = (
        jnp.moveaxis(ac, 1, 0),
        jnp.moveaxis(xc, 1, 0),
        jnp.moveaxis(Bc, 1, 0),
        jnp.moveaxis(Cc, 1, 0),
    )
    final_state, ys = jax.lax.scan(chunk_step, initial_state, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    return y, final_state


def mamba_apply(params, x, cfg: SSMConfig, d_model: int):
    """Full-sequence forward. Returns (out, final_ssm_state, conv_tail)."""
    d_inner = cfg.d_inner(d_model)
    H, G, N, W = cfg.n_heads(d_model), cfg.n_groups, cfg.d_state, cfg.conv_width
    P = cfg.head_dim
    Bsz, S, _ = x.shape

    proj = x @ params["in_proj"]
    z, xs, Bm, Cm, dt = _split_proj(proj, d_inner, G, N, H)
    u = jnp.concatenate([xs, Bm, Cm], axis=-1)
    u = jax.nn.silu(_causal_conv(u, params["conv_w"], params["conv_b"]))
    xs, Bm, Cm = jnp.split(u, [d_inner, d_inner + G * N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,H)
    A = -jnp.exp(params["A_log"])  # (H,)
    xh = xs.reshape(Bsz, S, H, P)
    Bm = Bm.reshape(Bsz, S, G, N)
    Cm = Cm.reshape(Bsz, S, G, N)

    y, state = ssd_chunked(xh, dt, A, Bm, Cm, cfg.chunk_size)
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, S, d_inner)
    y = gated_rmsnorm(params["norm"], y, z, G)
    out = (y @ params["out_proj"]).astype(x.dtype)
    conv_tail = jnp.concatenate([xs, Bm.reshape(Bsz, S, G * N), Cm.reshape(Bsz, S, G * N)], axis=-1)[:, -(W - 1):, :]
    return out, state, conv_tail


def mamba_decode_step(params, x, ssm_state, conv_state, cfg: SSMConfig, d_model: int):
    """One-token decode.

    x: (B, 1, d_model); ssm_state: (B, H, P, N); conv_state: (B, W-1, ch).
    Returns (out, new_ssm_state, new_conv_state).
    """
    d_inner = cfg.d_inner(d_model)
    H, G, N, W = cfg.n_heads(d_model), cfg.n_groups, cfg.d_state, cfg.conv_width
    P = cfg.head_dim
    Bsz = x.shape[0]

    proj = x[:, 0, :] @ params["in_proj"]  # (B, d_proj)
    z, xs, Bm, Cm, dt = _split_proj(proj, d_inner, G, N, H)
    u_new = jnp.concatenate([xs, Bm, Cm], axis=-1)  # (B, ch)
    window = jnp.concatenate([conv_state, u_new[:, None, :]], axis=1)  # (B, W, ch)
    u = jnp.einsum("bwc,wc->bc", window, params["conv_w"]) + params["conv_b"]
    u = jax.nn.silu(u)
    xs, Bm, Cm = jnp.split(u, [d_inner, d_inner + G * N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A)  # (B,H)
    xh = xs.reshape(Bsz, H, P).astype(jnp.float32)
    B_rep = jnp.repeat(Bm.reshape(Bsz, G, N), H // G, axis=1).astype(jnp.float32)
    C_rep = jnp.repeat(Cm.reshape(Bsz, G, N), H // G, axis=1).astype(jnp.float32)

    new_state = (
        ssm_state * decay[:, :, None, None]
        + jnp.einsum("bhn,bhp,bh->bhpn", B_rep, xh, dt)
    )
    y = jnp.einsum("bhn,bhpn->bhp", C_rep, new_state)  # (B,H,P)
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(Bsz, d_inner).astype(x.dtype)
    y = gated_rmsnorm(params["norm"], y, z, G)
    out = (y @ params["out_proj"]).astype(x.dtype)[:, None, :]
    return out, new_state, window[:, 1:, :]
