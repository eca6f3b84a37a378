"""The comparison's arithmetic: worst leaf, median floor, NaN, rounding."""
import math
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import check  # noqa: E402


def test_leaf_norms_split_stacked_layers_only():
    tree = {"server": {"server": {"w": jnp.ones((3, 2, 2))},
                       "final_norm": {"scale": jnp.ones((4,))}},
            "towers": [{"blocks": {"w": jnp.full((2, 4), 2.0)},
                        "proj_in": jnp.ones((2, 2))}]}
    got = check.leaf_norms(tree)
    assert got == pytest.approx({
        "server/final_norm/scale": 2.0,
        "server/server/w[0]": 2.0, "server/server/w[1]": 2.0,
        "server/server/w[2]": 2.0,
        "towers/0/blocks/w[0]": 4.0, "towers/0/blocks/w[1]": 4.0,
        "towers/0/proj_in": 2.0})


def test_worst_leaf_is_relative_to_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-6}
    # c's gap 1e-6 is tiny against the median leaf (1.0); b's is 10%
    prog = {"a": 1.0, "b": 1.1, "c": 2e-6}
    gap, at = check.worst_leaf(prog, ref)
    assert at == "b" and gap == pytest.approx(0.1)


def test_nan_is_no_match():
    assert check.worst_leaf({"a": math.nan}, {"a": 1.0})[0] == math.inf
    nums = check.numbers(
        {"losses": [1.0, math.nan], "grad_norms": {"a": 1.0},
         "change_norms": {"a": 1.0}},
        {"losses": [1.0, 1.0], "grad_norms": {"a": 1.0},
         "change_norms": {"a": 1.0}})
    assert nums["loss_gap"][0] == math.inf
    ok, _ = check.verdict(nums, {"loss_gap": 1.0, "grad_gap": 1.0,
                                 "update_gap": 1.0})
    assert not ok


def test_leaves_that_move_by_rounding_are_left_out_of_the_update():
    ref = {"losses": [2.0], "grad_norms": {"a": 1.0, "b": 1.0, "bias": 1e-9},
           "change_norms": {"a": 1.0, "b": 1.0, "bias": 1e-3}}
    prog = {"losses": [2.0], "grad_norms": {"a": 1.0, "b": 1.0, "bias": 0.0},
            "change_norms": {"a": 1.0, "b": 1.0, "bias": 0.5}}
    nums = check.numbers(prog, ref)
    assert nums["update_gap"][0] == 0.0
    assert nums["grad_gap"][0] == pytest.approx(1e-9)
