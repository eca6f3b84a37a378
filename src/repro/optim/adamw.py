"""AdamW implemented directly in JAX (no optax dependency).

Moments are stored in f32 regardless of param dtype; supports decoupled
weight decay, bias correction and a pluggable LR schedule.  Works on any
param pytree; with ZeRO-1 (repro.sharding.zero1) the moment pytree is
sharded over the data axis.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamW:
    learning_rate: float | Callable[[jnp.ndarray], jnp.ndarray] = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: Optional[float] = None

    def init(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {
            "mu": jax.tree_util.tree_map(zeros, params),
            "nu": jax.tree_util.tree_map(zeros, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def _lr(self, count):
        if callable(self.learning_rate):
            return self.learning_rate(count)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def update(self, params, grads, state):
        """One step: clipping, moments, bias correction and decoupled decay
        as one program, compiled once per parameter tree.  An eager caller
        (role 0's server update) then makes one fused pass over each leaf,
        where op-by-op dispatch would leave several full-size temporaries
        of each leaf allocated for as long as the device lags the host."""
        return self._compiled_update(params, grads, state)

    @functools.cached_property
    def _compiled_update(self):
        return jax.jit(self._update)

    def _update(self, params, grads, state):
        count = state["count"] + 1
        if self.grad_clip_norm is not None:
            from repro.optim.clipping import clip_by_global_norm

            grads, _ = clip_by_global_norm(grads, self.grad_clip_norm)

        b1, b2 = self.b1, self.b2

        def upd_mu(m, g):
            return b1 * m + (1 - b1) * g.astype(jnp.float32)

        def upd_nu(v, g):
            g32 = g.astype(jnp.float32)
            return b2 * v + (1 - b2) * g32 * g32

        mu = jax.tree_util.tree_map(upd_mu, state["mu"], grads)
        nu = jax.tree_util.tree_map(upd_nu, state["nu"], grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        lr = self._lr(count)

        def upd_param(p, m, v):
            step = m / c1 / (jnp.sqrt(v / c2) + self.eps)
            if self.weight_decay:
                step = step + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype)

        new_params = jax.tree_util.tree_map(upd_param, params, mu, nu)
        return new_params, {"mu": mu, "nu": nu, "count": count}
