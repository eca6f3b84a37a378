"""Plain reference of the split llama-style dense language model.

K towers each read the shared token ids through their own column slice of
the embedding table, project to the tower width, run ``tower_layers``
pre-norm blocks (RMSNorm, grouped-query causal attention with rotary
positions, SwiGLU) and project to the cut.  The cuts are merged, the server
runs the remaining blocks, the final RMSNorm and the tied unembedding.

Plain ``jax.numpy`` in the weights' dtype, scanned over the stacked
layers; the caller sets the matmul precision.  Each layer is
rematerialised in the backward pass so that the whole model fits one chip
beside nothing else.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import (cross_entropy, merge, rmsnorm, silu,
                              truncated_normal)


def tower_sizes(arch):
    """Tower block widths: the heads divided among the K towers."""
    K, hd = arch["vertical"]["num_clients"], head_dim(arch)
    heads = max(1, arch["num_heads"] // K)
    kv = max(1, arch["num_kv_heads"] // K)
    while heads % kv:
        kv -= 1
    return {"d_model": heads * hd, "n_heads": heads, "n_kv_heads": kv,
            "head_dim": hd, "d_ff": max(hd, arch["d_ff"] // K)}


def server_sizes(arch):
    return {"d_model": arch["d_model"], "n_heads": arch["num_heads"],
            "n_kv_heads": arch["num_kv_heads"], "head_dim": head_dim(arch),
            "d_ff": arch["d_ff"]}


def head_dim(arch):
    return arch.get("head_dim") or arch["d_model"] // arch["num_heads"]


def cut_width(arch):
    v = arch["vertical"]
    return arch["d_model"] // v["num_clients"] if v["merge"] == "concat" \
        else arch["d_model"]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _blocks(key, n, s, dtype):
    d, hd = s["d_model"], s["head_dim"]
    ks = jax.random.split(key, 7)
    mat = lambda k, a, b: truncated_normal(k, (n, a, b), a, dtype)  # noqa
    return {
        "ln1": {"scale": jnp.ones((n, d), dtype)},
        "attn": {"wq": mat(ks[0], d, s["n_heads"] * hd),
                 "wk": mat(ks[1], d, s["n_kv_heads"] * hd),
                 "wv": mat(ks[2], d, s["n_kv_heads"] * hd),
                 "wo": mat(ks[3], s["n_heads"] * hd, d)},
        "ln2": {"scale": jnp.ones((n, d), dtype)},
        "mlp": {"w_gate": mat(ks[4], d, s["d_ff"]),
                "w_up": mat(ks[5], d, s["d_ff"]),
                "w_down": mat(ks[6], s["d_ff"], d)},
    }


def make_weights(arch, key, dtype=jnp.float32):
    """Seeded weights in the program's tree layout (jit with ``arch``
    static): the towers' embedding slices start as column slices of the
    server's table, which also serves as the tied unembedding."""
    v = arch["vertical"]
    K, Lt = v["num_clients"], v["tower_layers"]
    d, V = arch["d_model"], arch["vocab_size"]
    ts = tower_sizes(arch)
    k_embed, k_server, k_towers = jax.random.split(key, 3)
    table = (jax.random.normal(k_embed, (V, d)) * 0.02).astype(dtype)
    server = {
        "embed": {"table": table},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "server": _blocks(k_server, arch["num_layers"] - Lt,
                          server_sizes(arch), dtype),
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
            "proj_in": truncated_normal(c_in, (ds, ts["d_model"]), ds, dtype),
            "blocks": _blocks(c_blocks, Lt, ts, dtype),
            "proj_out": truncated_normal(c_out, (ts["d_model"], cut_width(arch)),
                                         ts["d_model"], dtype),
            "embed_slice": table[:, k * ds:(k + 1) * ds],
        })
    return {"server": server, "towers": towers}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rope(x, theta):
    """Rotary positions, the rotate-half form; x: (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention(p, x, s, theta):
    B, S, _ = x.shape
    H, Kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    q = rope((x @ p["wq"]).reshape(B, S, H, hd), theta)
    k = rope((x @ p["wk"]).reshape(B, S, Kv, hd), theta)
    v = (x @ p["wv"]).reshape(B, S, Kv, hd)
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = (probs / jnp.sum(probs, axis=-1, keepdims=True)).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * hd)
    return out @ p["wo"]


def block(p, x, s, arch):
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    x = x + attention(p["attn"], rmsnorm(x, p["ln1"]["scale"], eps), s, theta)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    return x + (silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def _stack(blocks, x, s, arch):
    layer = jax.checkpoint(lambda h, lp: (block(lp, h, s, arch), None))
    return jax.lax.scan(layer, x, blocks)[0]


def tower(tp, tokens, arch):
    h = tp["embed_slice"][tokens] @ tp["proj_in"]
    return _stack(tp["blocks"], h, tower_sizes(arch), arch) @ tp["proj_out"]


def logits_fn(weights, tokens, arch):
    sp = weights["server"]
    cuts = [tower(tp, tokens, arch) for tp in weights["towers"]]
    x = merge(cuts, arch["vertical"]["merge"])
    x = _stack(sp["server"], x, server_sizes(arch), arch)
    x = rmsnorm(x, sp["final_norm"]["scale"], arch["norm_eps"])
    head = sp["embed"].get("unembed")
    return x @ (sp["embed"]["table"].T if head is None else head)


def loss_fn(weights, tokens, labels, arch):
    return cross_entropy(logits_fn(weights, tokens, arch), labels)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------

def _block_flops(s, seq):
    """Forward matmul FLOPs per token of one block: the four projections,
    the gated MLP, and causal attention over (seq + 1) / 2 keys on average."""
    d, hd, H, Kv = s["d_model"], s["head_dim"], s["n_heads"], s["n_kv_heads"]
    proj = 2 * d * (2 * H * hd + 2 * Kv * hd)
    mlp = 2 * 3 * d * s["d_ff"]
    attn = 2 * 2 * H * hd * (seq + 1) / 2
    return proj + mlp + attn


def flops_per_token(arch, seq):
    """Training FLOPs per token of the split model as built: three times the
    forward matmuls (backward twice the forward), nothing recomputed."""
    v = arch["vertical"]
    K, Lt = v["num_clients"], v["tower_layers"]
    ts = tower_sizes(arch)
    d, V = arch["d_model"], arch["vocab_size"]
    towers = K * (2 * (d // K) * ts["d_model"] + Lt * _block_flops(ts, seq)
                  + 2 * ts["d_model"] * cut_width(arch))
    server = (arch["num_layers"] - Lt) * _block_flops(server_sizes(arch), seq)
    head = 2 * d * V
    return 3 * (towers + server + head)
