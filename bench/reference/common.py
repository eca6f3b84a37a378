"""Plain-reference pieces shared by every family: norms, the cut merge, the
loss, AdamW and the three-step training run the check follows.

Nothing here imports the program.  Weights use the program's tree layout
(``{"server": ..., "towers": [...]}``, layers stacked on a leading axis) so
the harness can hand the same trees to both sides.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def truncated_normal(key, shape, fan_in, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape)
            * (1.0 / fan_in ** 0.5)).astype(dtype)


def merge(cuts, strategy):
    """The cut merge over a list of K equally shaped cuts, every client live."""
    if strategy == "sum":
        return sum(cuts)
    if strategy == "avg":
        return sum(cuts) / len(cuts)
    if strategy == "max":
        out = cuts[0]
        for c in cuts[1:]:
            out = jnp.maximum(out, c)
        return out
    if strategy == "mul":
        out = cuts[0]
        for c in cuts[1:]:
            out = out * c
        return out
    if strategy == "concat":
        return jnp.concatenate(cuts, axis=-1)
    raise ValueError(f"unknown merge {strategy!r}")


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy, in f32."""
    logits = logits.astype(jnp.float32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1)) + top[..., 0]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def learning_rate(opt, count):
    """Linear warm-up to ``learning_rate`` over ``warmup`` updates, then a
    cosine decay to a tenth of it at ``schedule_steps``."""
    c = count.astype(jnp.float32)
    peak, warm, total = opt["learning_rate"], opt["warmup"], opt["schedule_steps"]
    progress = jnp.clip((c - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * progress))
    return peak * jnp.where(c < warm, c / max(warm, 1), cos)


def adamw(params, grads, state, opt):
    """One AdamW update after clipping ``grads`` to global norm
    ``grad_clip``; returns (params, state, the clipped grads)."""
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12))
    grads = jax.tree_util.tree_map(lambda g: (g * scale).astype(dtype), grads)
    count = state["count"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state["nu"], grads)
    # the step's scalars in f32, then the weights' dtype: in bfloat16
    # b2 ** 1 would round to 1 and the bias correction to 0
    c1 = (1 - b1 ** count.astype(jnp.float32)).astype(dtype)
    c2 = (1 - b2 ** count.astype(jnp.float32)).astype(dtype)
    lr = learning_rate(opt, count).astype(dtype)

    def update(p, m, v):
        step = m / c1 / (jnp.sqrt(v / c2) + opt["eps"])
        return p - lr * (step + opt["weight_decay"] * p)

    params = jax.tree_util.tree_map(update, params, mu, nu)
    return params, {"mu": mu, "nu": nu, "count": count}, grads


def adamw_init(params):
    zeros = lambda p: jnp.zeros_like(p)  # noqa: E731
    return {"mu": jax.tree_util.tree_map(zeros, params),
            "nu": jax.tree_util.tree_map(zeros, params),
            "count": jnp.zeros((), jnp.int32)}


def train_steps(family, arch, opt, weights, batches, *, summarize,
                half_batch=False, no_exchange=False):
    """Run ``len(batches)`` split-training steps of the plain reference.

    The server and each tower keep an optimizer of their own, each clipping
    its own gradients, as the split deployment does.  Returns the per-step
    losses, ``summarize`` of the first step's clipped gradients and the
    final weights.  ``weights`` is donated to the first step.

    ``half_batch`` and ``no_exchange`` plant two of the faults the limits
    are held against: the loss averaged over the first half of the rows
    only, and towers that never receive their cut jacobians.
    """
    loss_fn = family.loss_fn

    def objective(w, tokens, labels):
        if half_batch:
            h = tokens.shape[0] // 2
            tokens, labels = tokens[:h], labels[:h]
        return loss_fn(w, tokens, labels, arch)

    def step(w, states, tokens, labels):
        loss, g = jax.value_and_grad(objective)(w, tokens, labels)
        if no_exchange:
            g = {"server": g["server"],
                 "towers": jax.tree_util.tree_map(jnp.zeros_like, g["towers"])}
        server, s_state, s_grad = adamw(w["server"], g["server"], states[0],
                                        opt)
        towers, t_states, t_grads = [], [], []
        for tp, tg, ts in zip(w["towers"], g["towers"], states[1]):
            tp, ts, tg = adamw(tp, tg, ts, opt)
            towers.append(tp)
            t_states.append(ts)
            t_grads.append(tg)
        return ({"server": server, "towers": towers}, (s_state, t_states),
                loss, {"server": s_grad, "towers": t_grads})

    step = jax.jit(step, donate_argnums=(0, 1))
    states = (adamw_init(weights["server"]),
              [adamw_init(t) for t in weights["towers"]])
    losses, first_grads = [], None
    for tokens, labels in batches:
        weights, states, loss, grads = step(weights, states, tokens, labels)
        losses.append(float(loss))
        if first_grads is None:
            first_grads = summarize(grads)
        del grads
    return losses, first_grads, weights
