"""Plain reference of the split Mamba-2 language model.

K towers each read the shared token ids through their own column slice of
the embedding table, project to the tower width (d_model / K), run
``tower_layers`` Mamba-2 blocks and project to the cut.  The cuts are
merged, the server runs the remaining blocks, the final RMSNorm and the tied
unembedding.

A block is pre-norm and residual: ``x + mixer(rmsnorm(x))``.  The mixer
(arXiv:2405.21060, the ``Mamba2`` module's defaults) projects to
``[z, x, B, C, dt]``, runs a depthwise causal convolution and SiLU over
``[x, B, C]``, steps the state-space recurrence with ``dt = softplus(dt +
dt_bias)`` and ``A = -exp(A_log)``, adds ``D * x``, applies the gated
RMSNorm ``rmsnorm(y * silu(z))`` over each group of ``d_inner / ngroups``
columns and projects back.  The recurrence is written as its definition,
one time step after another:

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T,    y_t = h_t C_t

per head, B and C shared by the heads of a group; the program's chunked
scan is a blocking of the same sum.  The recurrence keeps its state every
``SEGMENT`` steps and recomputes the steps between in the backward pass,
and each layer is rematerialised, so that the model's backward fits one
chip beside nothing else.

Plain ``jax.numpy`` in the weights' dtype; the caller sets the matmul
precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import (cross_entropy, merge, rmsnorm, silu,
                              truncated_normal)
from reference.dense import cut_width

SEGMENT = 64  # time steps between the recurrence's saved states
GATED_NORM_EPS = 1e-5  # the Mamba2 module's RMSNormGated


def sizes(arch, d_model):
    """The mixer's widths for a block of width ``d_model``."""
    s = arch["ssm"]
    d_inner = s["expand"] * d_model
    return {"d_model": d_model, "d_inner": d_inner,
            "heads": d_inner // s["head_dim"], "head_dim": s["head_dim"],
            "groups": s["n_groups"], "d_state": s["d_state"],
            "conv": s["conv_width"]}


def tower_width(arch):
    return arch["d_model"] // arch["vertical"]["num_clients"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _blocks(key, n, s, dtype):
    """``n`` blocks stacked on a leading axis, with the Mamba2 module's
    initialisation: A in [1, 16], dt log-uniform in [1e-3, 1e-1]."""
    d, di, H = s["d_model"], s["d_inner"], s["heads"]
    gn = s["groups"] * s["d_state"]
    ch, W = di + 2 * gn, s["conv"]
    ks = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(W)
    dt = jnp.exp(jax.random.uniform(ks[3], (n, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "ln": {"scale": jnp.ones((n, d), dtype)},
        "mamba": {
            "in_proj": truncated_normal(ks[0], (n, d, 2 * di + 2 * gn + H),
                                        d, dtype),
            "conv_w": jax.random.uniform(ks[1], (n, W, ch), jnp.float32,
                                         -bound, bound).astype(dtype),
            "conv_b": jax.random.uniform(ks[2], (n, ch), jnp.float32,
                                         -bound, bound).astype(dtype),
            # softplus(dt_bias) = dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(ks[4], (n, H), jnp.float32,
                                                1.0, 16.0)).astype(dtype),
            "D": jnp.ones((n, H), dtype),
            "norm": {"scale": jnp.ones((n, di), dtype)},
            "out_proj": truncated_normal(ks[5], (n, di, d), di, dtype),
        },
    }


def make_weights(arch, key, dtype=jnp.float32):
    """Seeded weights in the program's tree layout (jit with ``arch``
    static): the towers' embedding slices start as column slices of the
    server's table, which also serves as the tied unembedding."""
    v = arch["vertical"]
    K, Lt = v["num_clients"], v["tower_layers"]
    d, V = arch["d_model"], arch["vocab_size"]
    dt = tower_width(arch)
    k_embed, k_server, k_towers = jax.random.split(key, 3)
    table = (jax.random.normal(k_embed, (V, d)) * 0.02).astype(dtype)
    server = {
        "embed": {"table": table},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "server": _blocks(k_server, arch["num_layers"] - Lt, sizes(arch, d),
                          dtype),
    }
    if not arch["tie_embeddings"]:
        server["embed"]["unembed"] = truncated_normal(
            jax.random.fold_in(k_embed, 1), (d, V), d, dtype)
    ds = d // K
    towers = []
    for k in range(K):
        c_in, c_blocks, c_out = jax.random.split(
            jax.random.fold_in(k_towers, k), 3)
        towers.append({
            "proj_in": truncated_normal(c_in, (ds, dt), ds, dtype),
            "blocks": _blocks(c_blocks, Lt, sizes(arch, dt), dtype),
            "proj_out": truncated_normal(c_out, (dt, cut_width(arch)), dt,
                                         dtype),
            "embed_slice": table[:, k * ds:(k + 1) * ds],
        })
    return {"server": server, "towers": towers}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def softplus(x):
    return jnp.logaddexp(x, 0.0)


def causal_conv(u, w, b):
    """Depthwise causal convolution over time; u: (B, S, ch), w: (W, ch),
    w[W - 1] weighing the current step."""
    W, ch = w.shape
    out = jax.lax.conv_general_dilated(
        u, w[:, None, :], window_strides=(1,), padding=[(W - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=ch)
    return out + b


def recurrence(x, dt, A, Bm, Cm):
    """The state-space recurrence, step by step.  x: (B, S, H, P); dt:
    (B, S, H); A: (H,); Bm, Cm: (B, S, G, N).  Returns y: (B, S, H, P)."""
    Bsz, S, H, P = x.shape
    rep = H // Bm.shape[2]
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    seg = math.gcd(S, SEGMENT)
    # time-major, in segments: (S / seg, seg, B, ...)
    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(S // seg, seg, *a.shape[:1],
                                              *a.shape[2:])
               for a in (x, dt, Bh, Ch))
    segment = jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp))
    h0 = jnp.zeros((Bsz, H, P, Bm.shape[3]), x.dtype)
    _, ys = jax.lax.scan(segment, h0, xs)
    return jnp.moveaxis(ys.reshape(S, Bsz, H, P), 0, 1)


def gated_norm(y, z, scale, groups):
    """``rmsnorm(y * silu(z))`` over each of ``groups`` column groups."""
    g = y * silu(z)
    lead, d = g.shape[:-1], g.shape[-1]
    ones = jnp.ones((d // groups,), g.dtype)
    g = rmsnorm(g.reshape(*lead, groups, d // groups), ones, GATED_NORM_EPS)
    return g.reshape(*lead, d) * scale


def mixer(p, x, s):
    Bsz, S, _ = x.shape
    di, H, P = s["d_inner"], s["heads"], s["head_dim"]
    G, N = s["groups"], s["d_state"]
    proj = x @ p["in_proj"]
    z, xbc, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], axis=-1)
    xbc = silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(Bsz, S, H, P)
    y = recurrence(xh, dt, A, Bm.reshape(Bsz, S, G, N),
                   Cm.reshape(Bsz, S, G, N))
    y = (y + p["D"][:, None] * xh).reshape(Bsz, S, di)
    return gated_norm(y, z, p["norm"]["scale"], G) @ p["out_proj"]


def block(p, x, s, arch):
    return x + mixer(p["mamba"], rmsnorm(x, p["ln"]["scale"],
                                         arch["norm_eps"]), s)


def _stack(blocks, x, s, arch):
    layer = jax.checkpoint(lambda h, lp: (block(lp, h, s, arch), None))
    return jax.lax.scan(layer, x, blocks)[0]


def tower(tp, tokens, arch):
    h = tp["embed_slice"][tokens] @ tp["proj_in"]
    h = _stack(tp["blocks"], h, sizes(arch, tower_width(arch)), arch)
    return h @ tp["proj_out"]


def logits_fn(weights, tokens, arch):
    sp = weights["server"]
    cuts = [tower(tp, tokens, arch) for tp in weights["towers"]]
    x = merge(cuts, arch["vertical"]["merge"])
    x = _stack(sp["server"], x, sizes(arch, arch["d_model"]), arch)
    x = rmsnorm(x, sp["final_norm"]["scale"], arch["norm_eps"])
    head = sp["embed"].get("unembed")
    return x @ (sp["embed"]["table"].T if head is None else head)


def loss_fn(weights, tokens, labels, arch):
    return cross_entropy(logits_fn(weights, tokens, arch), labels)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------

def ssd_scan_cost(batch, seq, heads, head_dim, d_state, groups, chunk,
                  itemsize=4):
    """Operations and HBM bytes of one forward chunked SSD scan, from its
    shapes alone, as the program blocks it (chunks of ``chunk`` steps): per
    chunk and head the (Q, Q) scores C B^T over the state, their product
    with x, the carried state's contribution to y and the chunk's
    contribution to the state.  The bytes are the least a fused scan moves:
    x, dt, B and C read, y and the final state written."""
    Q = min(chunk, seq)
    H, P, N = heads, head_dim, d_state
    per_token = 2 * H * (Q * N + Q * P + 2 * N * P)
    elems = (2 * batch * seq * H * P + batch * seq * H
             + 2 * batch * seq * groups * N + batch * H * P * N + H)
    return {"flops": batch * seq * per_token, "bytes": elems * itemsize}


def _block_flops(arch, s, seq):
    """Forward matmul FLOPs per token of one block: the two projections and
    the chunked scan."""
    d, di, H, G, N = (s["d_model"], s["d_inner"], s["heads"], s["groups"],
                      s["d_state"])
    proj = 2 * d * (2 * di + 2 * G * N + H) + 2 * di * d
    scan = ssd_scan_cost(1, seq, H, s["head_dim"], N, G,
                         arch["ssm"]["chunk_size"])["flops"] / seq
    return proj + scan


def flops_per_token(arch, seq):
    """Training FLOPs per token of the split model as built: three times the
    forward matmuls (backward twice the forward), nothing recomputed."""
    v = arch["vertical"]
    K, Lt = v["num_clients"], v["tower_layers"]
    d, V, dt = arch["d_model"], arch["vocab_size"], tower_width(arch)
    towers = K * (2 * (d // K) * dt
                  + Lt * _block_flops(arch, sizes(arch, dt), seq)
                  + 2 * dt * cut_width(arch))
    server = (arch["num_layers"] - Lt) * _block_flops(
        arch, sizes(arch, d), seq)
    head = 2 * d * V
    return 3 * (towers + server + head)
