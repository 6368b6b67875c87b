"""The numbers that decide ``correct``, on values made by hand."""

import math

import numpy as np

from chipbench import check

REF = {"a": np.float32(2.0), "layers/b": np.array([1.0, 4.0, 0.0])}


def test_worst_gap_is_relative_to_leaf_or_median():
    # leaves 2, 1, 4, 0: median 1.5.  b[0]: |1.3 - 1| / max(1, 1.5) = 0.2
    prog = {"a": np.float32(2.0), "layers/b": np.array([1.3, 4.0, 0.0])}
    assert math.isclose(check.worst_gap(prog, REF), 0.2)
    prog["layers/b"] = np.array([1.0, 4.0, 0.3])      # 0.3 / 1.5
    assert math.isclose(check.worst_gap(prog, REF), 0.2)


def test_unmoved_leaves_are_left_out_of_the_change():
    assert check.moved_leaves(REF) == {"a", "layers/b[0]", "layers/b[1]"}
    prog = {"a": np.float32(2.0), "layers/b": np.array([1.0, 4.0, 9.0])}
    assert check.worst_gap(prog, REF, check.moved_leaves(REF)) == 0.0


def test_nan_and_missing_leaves_fail():
    prog = {"a": np.float32(np.nan), "layers/b": REF["layers/b"]}
    assert not check.worst_gap(prog, REF) <= 1.0
    assert check.worst_gap({"a": np.float32(2.0)}, REF) == math.inf
    ref = {"losses": [1.0, 1.0], "grad_norms": REF, "change_norms": REF}
    prog = {"losses": [1.0, float("nan")], "grad_norms": REF,
            "change_norms": REF}
    values = check.numbers(prog, ref, [], [])
    assert values["loss_gap"] == math.inf
    ok, rows = check.judge(values, {"loss_gap": 1.0, "grad_gap": 1.0,
                                    "change_gap": 1.0, "feed_mismatch": 0})
    assert not ok and ["loss_gap", math.inf, 1.0] in rows


def test_feed_mismatch_counts_wrong_and_repeated_rows():
    rows = {"tokens": np.array([[1, 2], [3, 4]]),
            "targets": np.array([[2, 5], [4, 6]])}
    assert check.feed_mismatch([rows], [rows]) == 0
    bad = {"tokens": np.array([[1, 2], [1, 2]]),
           "targets": np.array([[2, 5], [4, 6]])}
    assert check.feed_mismatch([bad], [rows]) == 2     # differs, repeats
    assert check.feed_mismatch([], [rows]) == 1
