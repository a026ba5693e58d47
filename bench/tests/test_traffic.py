"""Rows and weights are functions of the seed alone."""
import json
import os

import numpy as np

from conftest import BENCH
from harness import feed
from reference import weights

with open(os.path.join(BENCH, "traffic", "tune_xla_s2048_b2.json")) as f:
    TUNE = json.load(f)
BIG = 2 ** 33 + 7


def test_rows_repeat_for_a_seed():
    a = feed.train_batches(BIG, 3, 2, 64, 500, TUNE)
    b = feed.train_batches(BIG, 3, 2, 64, 500, TUNE)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (3, 2, 65) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 500


def test_rows_differ_across_seeds_and_rows():
    a = feed.train_batches(1, 2, 2, 2048, 500, TUNE)
    b = feed.train_batches(2, 2, 2, 2048, 500, TUNE)
    assert not np.array_equal(a, b)
    flat = a.reshape(-1, 2049)
    assert len({r.tobytes() for r in flat}) == len(flat)
    assert (a == 0).any()          # documents end in token 0


def test_weights_repeat_for_a_seed():
    shapes = {"embed": ((8, 4), "bfloat16"), "x/scale": ((4,), "float32"),
              "w": ((3, 4, 5), "bfloat16")}
    a = weights.make(BIG, shapes)
    b = weights.make(BIG, shapes)
    c = weights.make(BIG + 1, shapes)
    for k in shapes:
        np.testing.assert_array_equal(np.asarray(a[k], np.float32),
                                      np.asarray(b[k], np.float32))
        assert str(a[k].dtype) == shapes[k][1]
    assert np.all(np.asarray(a["x/scale"]) == 1.0)
    assert not np.array_equal(np.asarray(a["w"], np.float32),
                              np.asarray(c["w"], np.float32))
