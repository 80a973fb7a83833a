"""The comparison that decides ``correct``."""
import math

import numpy as np
import pytest

from bench import check


def test_leaf_gap_uses_the_larger_of_leaf_and_median_norm():
    ref = {"a": 1.0, "b": 1e-2, "c": 2.0}
    prog = {"a": 1.0, "b": 2e-2, "c": 2.0}
    # leaf b is off by 1e-2 against a median norm of 1.0
    assert check.leaf_gap(prog, ref, ref) == pytest.approx(1e-2)


def test_negligible_leaves_are_left_out_by_rule():
    grad = {"a": 1.0, "b": 1e-5, "c": 2.0}
    change = {"a": 0.5, "b": 0.5, "c": 0.5}
    prog = {"a": 0.5, "b": 5.0, "c": 0.5}
    assert check.leaf_gap(prog, change, grad) == 0.0


def test_a_state_left_unchanged_reads_one():
    ref = {"a": 0.3, "b": 0.4}
    assert check.leaf_gap({"a": 0.0, "b": 0.0}, ref, ref) == pytest.approx(1.0)


def test_non_finite_readings_fail():
    assert check.loss_gap([1.0, math.nan], [1.0, 1.0]) == math.inf
    assert check.leaf_gap({"a": math.nan}, {"a": 1.0}, {"a": 1.0}) == math.inf
    assert not check.verdict({"x": math.inf}, {"x": 1.0})


def test_leaf_norms_by_path():
    tree = {"layer0": {"w": np.ones((2, 2)), "b": np.zeros(3)}}
    norms = check.leaf_norms(tree)
    assert norms == {"['layer0']['b']": 0.0, "['layer0']['w']": 2.0}


def test_halo_gap_reads_the_program_buffers_at_the_reference_rows():
    ref = [np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])]
    # two partitions, three buffer rows each; entries at (recv, row)
    at = (np.array([0, 1, 1]), np.array([2, 0, 1]))
    buf = np.zeros((2, 3, 2))
    buf[at[0], at[1]] = ref[0]
    assert check.halo_gap([buf], ref, at) == 0.0
    swapped = buf[::-1].copy()                 # rows land in the wrong partition
    assert check.halo_gap([swapped], ref, at) > 0.5
    assert check.halo_gap([np.zeros_like(buf)], ref, at) == pytest.approx(1.0)


def test_grad_diff_sees_a_gradient_of_the_same_norm_pointing_elsewhere():
    ref = {"a": np.array([3.0, 4.0]), "b": np.array([1.0, 0.0])}
    turned = {"a": np.array([4.0, 3.0]), "b": np.array([1.0, 0.0])}
    norms = check.norms(ref)
    assert check.leaf_gap(check.norms(turned), norms, norms) == 0.0
    assert check.leaf_diff(turned, ref) == pytest.approx(np.sqrt(2) / 5)
    assert check.leaf_diff(ref, ref) == 0.0


def test_grad_diff_leaves_out_negligible_leaves_and_fails_non_finite():
    ref = {"a": np.ones(4), "b": np.full(4, 1e-6), "c": np.ones(4)}
    prog = {"a": np.ones(4), "b": np.ones(4), "c": np.ones(4)}
    assert check.leaf_diff(prog, ref) == 0.0
    assert check.leaf_diff({**prog, "a": np.full(4, np.nan)}, ref) == math.inf
    assert check.leaf_diff({"a": np.ones(4), "b": np.ones(4)}, ref) == math.inf
