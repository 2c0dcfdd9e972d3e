import importlib.util
import os

import numpy as np

import flops
import peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _serve_open():
    spec = importlib.util.spec_from_file_location(
        "serve_open", os.path.join(os.path.dirname(HERE), "traffic",
                                   "serve_open.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fm_forward_hand_count():
    # 2 rows, 3 values, 2 factors
    f, b = flops.fm_forward(rows=2, nnz=3, dim=2)
    assert f == 4 * 3 * 2 + 2 * 3 + 3 * 2 * 2
    assert b == 3 * 3 * 4 + 3 * 2 * 4 + 2 * 4


def test_fm_train_counts_touched_rows_only():
    # doubling the table changes nothing: no term depends on its height
    f, b = flops.fm_train_step(rows=4096, nnz=155000, dim=32)
    assert b < 0.25e9 < 2 * (1 << 24) * 32 * 4      # far under one table pass
    f1, b1 = flops.fm_train_step(rows=1, nnz=1, dim=1)
    assert f1 == (4 + 2 + 3) + (4 + 2 + 8) + 12 * 2
    assert b1 == (2 * 4 + 2 * 4 + 4) + 2 * 2 * 4 + 7 * 2 * 4 + 2 * 4


def test_dcn_forward_hand_count():
    f, b = flops.dcn_forward(rows=2, nnz=3, dim=4, layers=2)
    assert f == 2 * 3 * 4 + 2 * 3 + 2 * (2 * 2 * 16 + 3 * 2 * 4) + 2 * 2 * 4
    assert b == 3 * 5 * 4 + 3 * 2 * 4 + 2 * (16 + 4) * 4 + 2 * 4


def test_least_seconds_names_the_bound():
    pk = peaks.device_peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, pk) == (1.0, "compute")
    t, bound = flops.least_seconds(1.0, 819e9, pk)
    assert bound == "bandwidth" and abs(t - 1.0) < 1e-12


def test_unknown_device_has_no_peaks():
    import pytest
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")


def test_schedule_same_work_for_every_seed():
    so = _serve_open()
    due_a, rows_a = so.schedule(250.0, 4.0, 1, 128, seed=1)
    due_b, rows_b = so.schedule(250.0, 4.0, 1, 128, seed=2 ** 31 + 5)
    assert len(due_a) == len(due_b) == 1000
    assert sorted(rows_a) == sorted(rows_b) and list(rows_a) != list(rows_b)
    gaps = lambda d: np.sort(np.diff(d))            # noqa: E731
    # same multiset of gaps up to the one each order drops (the first)
    assert abs(gaps(due_a).sum() - gaps(due_b).sum()) < 0.05
    assert rows_a.min() == 1 and rows_a.max() == 128
    # log-uniform: the median request is near sqrt(128), the mean near 26
    assert 9 <= np.median(rows_a) <= 13 and 22 <= rows_a.mean() <= 30


def test_schedule_due_times():
    so = _serve_open()
    due, _ = so.schedule(100.0, 10.0, 1, 128, seed=7)
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] < 10.0 and due[-1] > 9.0          # arrivals fill the window
    # exponential gaps: the coefficient of variation is near 1
    g = np.diff(due)
    assert 0.9 < g.std() / g.mean() < 1.1
