"""The comparison's arithmetic: worst leaf, the round-off rule, limits."""
import numpy as np
import pytest

from bench import compare


def test_worst_leaf_gap_is_a_gap_of_norms_over_the_larger_scale():
    want = {"a": np.full(4, 1.0), "b": np.full(4, 0.001), "c": np.ones(1)}
    got = {"a": np.full(4, 1.1), "b": np.full(4, 0.002), "c": -np.ones(1)}
    # a: |2.2 - 2.0| / 2.0 = 0.1; b: |0.004 - 0.002| over the median leaf
    # norm (1.0) = 0.002; c: norms equal though the sign flipped = 0
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": np.ones(2)}, want)


def test_unchanged_state_reads_one_and_round_off_leaves_are_left_out():
    ref_grad = [np.ones(3), np.ones(3), np.full(3, 1e-9)]
    ref_update = [np.full(3, 0.01), np.full(3, 0.02), np.full(3, 0.03)]
    unchanged = [np.zeros(3)] * 3
    n = compare.training_numbers([1.0, 1.0], ref_grad, unchanged,
                                 [1.0, 2.0], ref_grad, ref_update)
    assert n["loss_gap.1"] == 0.0 and n["loss_gap.2"] == pytest.approx(0.5)
    assert n["grad_gap"] == 0.0
    assert n["update_gap"] == pytest.approx(1.0)
    n = compare.training_numbers(
        [1.0], ref_grad, [np.full(3, 0.01), np.full(3, 0.02), np.zeros(3)],
        [1.0], ref_grad, ref_update)
    assert n["update_gap"] == 0.0       # leaf 2 moves by round-off only
    assert compare.training_numbers(
        [float("nan")], ref_grad, ref_update, [1.0], ref_grad,
        ref_update)["loss_gap.1"] == float("inf")


def test_judge_fails_on_a_missing_number_or_limit():
    ok, table = compare.judge({"a": 1.0, "b": 2.0}, {"a": 1.0, "b": 3.0})
    assert ok and table["a"] == {"value": 1.0, "limit": 1.0}
    assert not compare.judge({"a": 1.5}, {"a": 1.0})[0]
    assert not compare.judge({"a": 1.0}, {"a": 1.0, "b": 1.0})[0]
    # a number the limits do not name is worked out but not compared
    ok, table = compare.judge({"a": 1.0, "b": 9.0}, {"a": 1.0})
    assert ok and list(table) == ["a"]
