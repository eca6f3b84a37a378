"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its limit (``bench/limits/<cell>.json``):

* ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the first three steps;
* ``grad_gap``: the first step's gradient as each optimizer got it (clipped),
  worked out from the program's AdamW first moment after one update, by the
  worst leaf;
* ``update_gap``: the parameters' change over the three updates, by the
  worst leaf.

By the worst leaf means the largest gap between the program's leaf norm and
the reference's, over the larger of the reference's norm of that leaf and
its median leaf's.  Layers stacked on a leading axis count one leaf each.
Leaves whose reference gradient is under a thousandth of the median leaf's
move by rounding alone under Adam and are left out of ``update_gap``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROUNDING_SHARE = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "update_gap")


def _names(tree) -> list[tuple[str, bool]]:
    """Leaf names of a ``{"server": ..., "towers": [...]}`` tree, and whether
    the leaf stacks layers: the server's ``server`` stack, a tower's
    ``blocks``."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        stacked = keys[:2] == ["server", "server"] or "blocks" in keys
        out.append(("/".join(keys), stacked))
    return out


@jax.jit
def _norms(tree):
    def norm(a, stacked):
        a = a.astype(jnp.float32)
        if stacked:
            return jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        return jnp.sqrt(jnp.sum(a * a))[None]

    leaves = jax.tree_util.tree_leaves(tree)
    flags = [s for _, s in _names(tree)]
    return [norm(a, s) for a, s in zip(leaves, flags)]


def leaf_norms(tree) -> dict[str, float]:
    """Norm of every leaf of ``tree``, one per layer for stacked layers."""
    out = {}
    for (name, stacked), v in zip(_names(tree), _norms(tree)):
        v = np.asarray(v, np.float64)
        if stacked:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v[0])
    return out


def change_norms(after, before) -> dict[str, float]:
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before))


def first_grads_from_moment(mu, b1: float):
    """The clipped gradient an AdamW optimizer got at its first update,
    from its first moment: mu_1 = (1 - b1) * g."""
    return jax.tree_util.tree_map(lambda m: m / (1.0 - b1), mu)


def _finite(x: float) -> float:
    """A gap that is not a number (a NaN on either side) is no match."""
    return float(x) if np.isfinite(x) else float("inf")


def worst_leaf(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    names = [n for n in ref if keep is None or n in keep]
    if set(prog) != set(ref):
        raise RuntimeError("program and reference leaves differ: "
                           f"{sorted(set(prog) ^ set(ref))[:8]}")
    median = float(np.median([ref[n] for n in ref]))
    worst, at = -1.0, ""
    for n in names:
        g = _finite(abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30))
        if g > worst:
            worst, at = g, n
    return worst, at


def numbers(prog: dict, ref: dict) -> dict[str, tuple[float, str]]:
    """The compared numbers, each with where it was worst.  ``prog`` and
    ``ref`` hold ``losses``, ``grad_norms`` and ``change_norms``."""
    if len(prog["losses"]) != len(ref["losses"]) or not ref["losses"]:
        raise RuntimeError(f"loss counts differ: {prog['losses']} vs "
                           f"{ref['losses']}")
    losses = [_finite(abs(p - r) / abs(r))
              for p, r in zip(prog["losses"], ref["losses"])]
    g = ref["grad_norms"]
    median = float(np.median(list(g.values())))
    moving = {n for n, v in g.items() if v >= ROUNDING_SHARE * median}
    return {
        "loss_gap": (max(losses), f"step {int(np.argmax(losses))}"),
        "grad_gap": worst_leaf(prog["grad_norms"], g),
        "update_gap": worst_leaf(prog["change_norms"], ref["change_norms"],
                                 keep=moving),
    }


def verdict(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """``correct`` and one plain line per number: its value, its limit."""
    ok, lines = True, []
    for name in NUMBERS:
        value, at = nums[name]
        limit = limits[name]
        ok = ok and bool(value <= limit)
        lines.append(f"{name} {value!r} limit {limit!r} (worst at {at})")
    return ok, lines
