"""Transformer blocks and stacks for every assigned architecture family.

Blocks are *scannable*: params for L homogeneous layers are stacked on a
leading axis and the stack runs under ``jax.lax.scan`` (one traced layer —
compile time stays flat in depth, which matters for 64-81 layer archs).

Families:
  dense   — pre-norm GQA attention + SwiGLU/GELU MLP (llama/starcoder style)
  moe     — attention + MoE FFN (deepseek fine-grained / arctic dense-residual)
  ssm     — Mamba2 (SSD) blocks, attention-free
  hybrid  — Mamba2 blocks with a *weight-shared* attention block every N
            layers (zamba2)
  audio   — whisper-style encoder-decoder (conv/mel frontend stubbed)
  vlm     — internvl-style: stubbed vision embeddings prepended to text

The vertical-SplitNN towers (the paper's technique) are built from the same
blocks at width d_model/K and are vmapped over the client axis — zero
cross-client communication below the cut by construction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
from repro.models import attention as attn_lib
from repro.models import layers, mamba, moe as moe_lib


# ---------------------------------------------------------------------------
# block dims
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockDims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    qk_norm: bool = False
    rope_theta: Optional[float] = 10000.0
    norm_eps: float = 1e-5
    mlp: str = "swiglu"  # "swiglu" | "gelu"
    norm: str = "rms"  # "rms" | "ln"

    @staticmethod
    def from_arch(cfg: ArchConfig) -> "BlockDims":
        return BlockDims(
            d_model=cfg.d_model,
            n_heads=cfg.num_heads,
            n_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim(),
            d_ff=cfg.d_ff,
            qk_norm=cfg.qk_norm,
            rope_theta=None if cfg.family == "audio" else cfg.rope_theta,
            norm_eps=cfg.norm_eps,
            mlp="gelu" if cfg.family == "audio" else "swiglu",
            norm="ln" if cfg.family == "audio" else "rms",
        )

    def scaled(self, k: int) -> "BlockDims":
        """Tower dims: width/heads divided by the client count."""
        heads = max(1, self.n_heads // k)
        kv = max(1, self.n_kv_heads // k)
        while heads % kv:
            kv -= 1
        return BlockDims(
            d_model=heads * self.head_dim,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=self.head_dim,
            d_ff=max(self.head_dim, self.d_ff // k),
            qk_norm=self.qk_norm,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            mlp=self.mlp,
            norm=self.norm,
        )


def _init_norm(d, kind, dtype):
    return layers.init_rmsnorm(d, dtype) if kind == "rms" else layers.init_layernorm(d, dtype)


def _norm(params, x, kind, eps):
    return layers.rmsnorm(params, x, eps) if kind == "rms" else layers.layernorm(params, x, eps)


# ---------------------------------------------------------------------------
# dense block
# ---------------------------------------------------------------------------

def init_dense_block(key, dims: BlockDims, dtype=jnp.float32, cross: bool = False):
    ks = jax.random.split(key, 3)
    p = {
        "ln1": _init_norm(dims.d_model, dims.norm, dtype),
        "attn": attn_lib.init_attention(
            ks[0], dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            qk_norm=dims.qk_norm, dtype=dtype,
        ),
        "ln2": _init_norm(dims.d_model, dims.norm, dtype),
        "mlp": (
            layers.init_gated_mlp(ks[1], dims.d_model, dims.d_ff, dtype)
            if dims.mlp == "swiglu"
            else layers.init_gelu_mlp(ks[1], dims.d_model, dims.d_ff, dtype)
        ),
    }
    if cross:
        p["ln_cross"] = _init_norm(dims.d_model, dims.norm, dtype)
        p["cross"] = attn_lib.init_attention(
            ks[2], dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            qk_norm=False, dtype=dtype,
        )
    return p


def _mlp_apply(p, x, kind):
    return layers.gated_mlp(p, x) if kind == "swiglu" else layers.gelu_mlp(p, x)


def dense_block_apply(
    p, x, dims: BlockDims, *, causal=True, positions=None,
    window=None, cross_kv=None, return_kv=False,
):
    """Full-sequence forward.  cross_kv: (enc_out_k, enc_out_v, positions)."""
    h = _norm(p["ln1"], x, dims.norm, dims.norm_eps)
    attn_out, kv = attn_lib.attention_apply(
        p["attn"], h, n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
        head_dim=dims.head_dim, causal=causal, positions=positions,
        rope_theta=dims.rope_theta, window=window,
    )
    x = x + attn_out
    if cross_kv is not None and "cross" in p:
        h = _norm(p["ln_cross"], x, dims.norm, dims.norm_eps)
        c_out, _ = attn_lib.attention_apply(
            p["cross"], h, n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
            head_dim=dims.head_dim, causal=False, positions=positions,
            rope_theta=None, kv_override=cross_kv,
        )
        x = x + c_out
    h = _norm(p["ln2"], x, dims.norm, dims.norm_eps)
    out = x + _mlp_apply(p["mlp"], h, dims.mlp)
    if return_kv:
        return out, kv
    return out


def dense_stack_prefill(stacked, x, dims: BlockDims, *, positions,
                        causal=True, window=None):
    """Full-sequence forward that also returns per-layer K/V for cache fill.

    Returns (x, ks, vs) with ks/vs: (L, B, S, Kv, hd).
    """
    def body(h, lp):
        h, (k, v) = dense_block_apply(lp, h, dims, causal=causal,
                                      positions=positions, window=window,
                                      return_kv=True)
        return h, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, stacked)
    return x, ks, vs


def dense_block_decode(
    p, x, cache_k, cache_v, index, kv_positions, dims: BlockDims, *,
    window=None, ring=False, position=None, cross_cache=None,
    decode_chunks=None, chunk_sharding=None, kv_scales=None,
):
    """One-token decode.
    Returns (x, new_k, new_v, new_kv_positions, new_kv_scales)."""
    h = _norm(p["ln1"], x, dims.norm, dims.norm_eps)
    attn_out, nk, nv, npos, nsc = attn_lib.decode_attention_apply(
        p["attn"], h, cache_k, cache_v, index,
        n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim,
        rope_theta=dims.rope_theta, position=position, window=window,
        ring=ring, kv_positions=kv_positions,
        decode_chunks=decode_chunks, chunk_sharding=chunk_sharding,
        kv_scales=kv_scales,
    )
    x = x + attn_out
    if cross_cache is not None and "cross" in p:
        ck, cv = cross_cache
        h = _norm(p["ln_cross"], x, dims.norm, dims.norm_eps)
        c_out, _, _, _, _ = attn_lib.decode_attention_apply(
            p["cross"], h, ck, cv, index,
            n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
            head_dim=dims.head_dim, rope_theta=None, position=position,
            cross=True,
        )
        x = x + c_out
    h = _norm(p["ln2"], x, dims.norm, dims.norm_eps)
    return x + _mlp_apply(p["mlp"], h, dims.mlp), nk, nv, npos, nsc


def cross_kv_from_encoder(p, enc_out, dims: BlockDims):
    """Precompute K/V of encoder output for every decoder cross-attn layer."""
    B, S, _ = enc_out.shape
    k = (enc_out @ p["cross"]["wk"]).reshape(B, S, dims.n_kv_heads, dims.head_dim)
    v = (enc_out @ p["cross"]["wv"]).reshape(B, S, dims.n_kv_heads, dims.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------

def init_moe_block(key, dims: BlockDims, moe_cfg: MoEConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": _init_norm(dims.d_model, dims.norm, dtype),
        "attn": attn_lib.init_attention(
            k1, dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim,
            qk_norm=dims.qk_norm, dtype=dtype,
        ),
        "ln2": _init_norm(dims.d_model, dims.norm, dtype),
        "moe": moe_lib.init_moe(k2, dims.d_model, dims.d_ff, moe_cfg, dtype),
    }


def moe_block_apply(p, x, dims: BlockDims, moe_cfg: MoEConfig, *,
                    positions=None, window=None):
    h = _norm(p["ln1"], x, dims.norm, dims.norm_eps)
    attn_out, _ = attn_lib.attention_apply(
        p["attn"], h, n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
        head_dim=dims.head_dim, causal=True, positions=positions,
        rope_theta=dims.rope_theta, window=window,
    )
    x = x + attn_out
    h = _norm(p["ln2"], x, dims.norm, dims.norm_eps)
    moe_out, aux = moe_lib.moe_apply(p["moe"], h, moe_cfg)
    return x + moe_out, aux


def moe_block_decode(p, x, cache_k, cache_v, index, kv_positions,
                     dims: BlockDims, moe_cfg: MoEConfig, *,
                     window=None, ring=False, position=None,
                     decode_chunks=None, chunk_sharding=None):
    h = _norm(p["ln1"], x, dims.norm, dims.norm_eps)
    attn_out, nk, nv, npos, _ = attn_lib.decode_attention_apply(
        p["attn"], h, cache_k, cache_v, index,
        n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim,
        rope_theta=dims.rope_theta, position=position, window=window,
        ring=ring, kv_positions=kv_positions,
        decode_chunks=decode_chunks, chunk_sharding=chunk_sharding,
    )
    x = x + attn_out
    h = _norm(p["ln2"], x, dims.norm, dims.norm_eps)
    moe_out, _ = moe_lib.moe_apply(p["moe"], h, moe_cfg)
    return x + moe_out, nk, nv, npos


# ---------------------------------------------------------------------------
# Mamba block (pre-norm residual wrapper around repro.models.mamba)
# ---------------------------------------------------------------------------

def init_mamba_block(key, d_model: int, ssm_cfg: SSMConfig, dtype=jnp.float32):
    return {
        "ln": layers.init_rmsnorm(d_model, dtype),
        "mamba": mamba.init_mamba(key, d_model, ssm_cfg, dtype),
    }


def mamba_block_apply(p, x, ssm_cfg: SSMConfig, d_model: int, eps: float):
    h = layers.rmsnorm(p["ln"], x, eps)
    out, state, conv_tail = mamba.mamba_apply(p["mamba"], h, ssm_cfg, d_model)
    return x + out, state, conv_tail


def mamba_block_decode(p, x, ssm_state, conv_state, ssm_cfg: SSMConfig,
                       d_model: int, eps: float):
    h = layers.rmsnorm(p["ln"], x, eps)
    out, ns, nc = mamba.mamba_decode_step(
        p["mamba"], h, ssm_state, conv_state, ssm_cfg, d_model
    )
    return x + out, ns, nc


# ---------------------------------------------------------------------------
# stacks (scan over layers)
# ---------------------------------------------------------------------------

def init_stacked(init_one, key, n: int):
    """vmap an init function over n layer keys -> stacked params."""
    if n == 0:
        return None
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)



def _maybe_checkpoint(body, remat):
    """remat: False | True (full) | "dots" (save dot/collective outputs —
    the backward pass re-runs elementwise work but NOT the TP matmuls, so
    their all-reduces are not re-issued)."""
    if not remat:
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(body)

def dense_stack_apply(stacked, x, dims: BlockDims, *, causal=True,
                      positions=None, window=None, cross_kv=None,
                      remat=False):
    def body(h, lp):
        return (
            dense_block_apply(lp, h, dims, causal=causal, positions=positions,
                              window=window, cross_kv=cross_kv),
            None,
        )

    body = _maybe_checkpoint(body, remat)
    x, _ = jax.lax.scan(body, x, stacked)
    return x


def dense_stack_decode(stacked, x, cache_k, cache_v, index, kv_positions,
                       dims: BlockDims, *, window=None, ring=False,
                       position=None, cross_caches=None,
                       decode_chunks=None, chunk_sharding=None,
                       kv_scales=None):
    """cache_k/v: (L, B, S, Kv, hd); cross_caches: (L, ...) pair or None;
    kv_scales: (k_scale, v_scale) each (L, B, S, Kv, 1) for int8 caches."""
    quant = kv_scales is not None

    def body(h, xs):
        cc, sc = None, None
        if cross_caches is not None:
            lp, ck, cv, xk, xv = xs
            cc = (xk, xv)
        elif quant:
            lp, ck, cv, ks, vs = xs
            sc = (ks, vs)
        else:
            lp, ck, cv = xs
        h, nk, nv, npos, nsc = dense_block_decode(
            lp, h, ck, cv, index, kv_positions, dims, window=window,
            ring=ring, position=position, cross_cache=cc,
            decode_chunks=decode_chunks, chunk_sharding=chunk_sharding,
            kv_scales=sc,
        )
        if nsc is None:
            nsc = (jnp.zeros((), h.dtype),) * 2  # scan needs uniform pytrees
        return h, (nk, nv, npos, nsc)

    xs = (stacked, cache_k, cache_v)
    if cross_caches is not None:
        xs = xs + tuple(cross_caches)
    elif quant:
        xs = xs + tuple(kv_scales)
    x, (nk, nv, npos, nsc) = jax.lax.scan(body, x, xs)
    # kv positions are identical across layers — keep layer 0's
    if quant:
        return x, nk, nv, npos[0], nsc
    return x, nk, nv, npos[0], None


def moe_stack_apply(stacked, x, dims: BlockDims, moe_cfg: MoEConfig, *,
                    positions=None, window=None, remat=False):
    def body(carry, lp):
        h, aux = carry
        h, a = moe_block_apply(lp, h, dims, moe_cfg, positions=positions,
                               window=window)
        return (h, aux + a), None

    body = _maybe_checkpoint(body, remat)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def moe_stack_decode(stacked, x, cache_k, cache_v, index, kv_positions,
                     dims: BlockDims, moe_cfg: MoEConfig, *, window=None,
                     ring=False, position=None,
                     decode_chunks=None, chunk_sharding=None):
    def body(h, xs):
        lp, ck, cv = xs
        h, nk, nv, npos = moe_block_decode(
            lp, h, ck, cv, index, kv_positions, dims, moe_cfg,
            window=window, ring=ring, position=position,
            decode_chunks=decode_chunks, chunk_sharding=chunk_sharding,
        )
        return h, (nk, nv, npos)

    x, (nk, nv, npos) = jax.lax.scan(body, x, (stacked, cache_k, cache_v))
    return x, nk, nv, npos[0]


@functools.lru_cache(maxsize=None)
def _mamba_stack_program(ssm_cfg: SSMConfig, d_model: int, eps: float, remat):
    """The scan over a stack of Mamba blocks, compiled once per
    configuration and input shape."""
    def mamba_stack(stacked, x):
        def body(h, lp):
            h, _, _ = mamba_block_apply(lp, h, ssm_cfg, d_model, eps)
            return h, None

        x, _ = jax.lax.scan(_maybe_checkpoint(body, remat), x, stacked)
        return x

    return jax.jit(mamba_stack)


def mamba_stack_apply(stacked, x, ssm_cfg: SSMConfig, d_model: int, eps: float,
                      remat=False):
    """A stack of Mamba blocks as one compiled program.  An eager caller
    dispatches it and its backward without tracing the layers' chunked SSD
    scans again (``mamba.ssd_traces``); inside a compiled caller, such as
    role 0's server step, it is part of that program.  The span
    ``ssd.scan`` covers the dispatch, or the tracing in a compiled caller."""
    program = _mamba_stack_program(ssm_cfg, d_model, eps, remat)
    with jax.profiler.TraceAnnotation("ssd.scan", seq=x.shape[1],
                                      width=d_model):
        return program(stacked, x)


def mamba_stack_decode(stacked, x, ssm_states, conv_states, ssm_cfg: SSMConfig,
                       d_model: int, eps: float):
    """ssm_states: (L, B, H, P, N); conv_states: (L, B, W-1, ch)."""
    def body(h, xs):
        lp, ss, cs = xs
        h, ns, nc = mamba_block_decode(lp, h, ss, cs, ssm_cfg, d_model, eps)
        return h, (ns, nc)

    x, (ns, nc) = jax.lax.scan(body, x, (stacked, ssm_states, conv_states))
    return x, ns, nc


# ---------------------------------------------------------------------------
# hybrid (zamba2): super-blocks of N mamba layers + one SHARED attn block
# ---------------------------------------------------------------------------

def hybrid_layout(n_layers: int, every: int) -> tuple[int, int]:
    """Returns (n_super_blocks, n_trailing_mamba_layers)."""
    return n_layers // every, n_layers % every


def hybrid_stack_apply(mamba_super, mamba_tail, shared_attn, x,
                       ssm_cfg: SSMConfig, dims: BlockDims, *, positions=None,
                       window=None, remat=False):
    """mamba_super: (n_super, every, ...) stacked; shared_attn: one block."""
    def super_body(h, lp_group):
        h = mamba_stack_apply(lp_group, h, ssm_cfg, dims.d_model, dims.norm_eps,
                              remat=remat)
        h = dense_block_apply(shared_attn, h, dims, causal=True,
                              positions=positions, window=window)
        return h, None

    super_body = _maybe_checkpoint(super_body, remat)
    if mamba_super is not None:
        x, _ = jax.lax.scan(super_body, x, mamba_super)
    if mamba_tail is not None:
        x = mamba_stack_apply(mamba_tail, x, ssm_cfg, dims.d_model, dims.norm_eps,
                              remat=remat)
    return x


def hybrid_stack_decode(mamba_super, mamba_tail, shared_attn, x,
                        ssm_super, conv_super, attn_k, attn_v,
                        ssm_tail, conv_tail, index, kv_positions,
                        ssm_cfg: SSMConfig, dims: BlockDims, *,
                        window=None, ring=False, position=None):
    """ssm_super: (n_super, every, B, H, P, N); attn_k: (n_super, B, S, Kv, hd)."""
    def super_body(h, xs):
        lp_group, ss, cs, ck, cv = xs
        h, ns, nc = mamba_stack_decode(lp_group, h, ss, cs, ssm_cfg,
                                       dims.d_model, dims.norm_eps)
        h, nk, nv, npos, _ = dense_block_decode(
            shared_attn, h, ck, cv, index, kv_positions, dims,
            window=window, ring=ring, position=position,
        )
        return h, (ns, nc, nk, nv, npos)

    new = None
    if mamba_super is not None:
        x, new = jax.lax.scan(
            super_body, x, (mamba_super, ssm_super, conv_super, attn_k, attn_v)
        )
    if mamba_tail is not None:
        x, ssm_tail, conv_tail = mamba_stack_decode(
            mamba_tail, x, ssm_tail, conv_tail, ssm_cfg, dims.d_model,
            dims.norm_eps,
        )
    if new is None:
        return x, None, None, None, None, ssm_tail, conv_tail, kv_positions
    ns, nc, nk, nv, npos = new
    return x, ns, nc, nk, nv, ssm_tail, conv_tail, npos[0]
