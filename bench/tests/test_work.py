"""``work.least_work`` against a count of the pairs one by one."""

import numpy as np
import pytest

import traffic
import work


def _brute(req):
    rows = {req.origin[0] + i for i in range(req.a.shape[0])}
    cols = {req.origin[1] + j for j in range(req.b.shape[0])}
    if req.same_table:
        pairs = {(min(i, j), max(i, j)) for i in rows for j in cols if i != j}
        touched = rows | cols
    else:
        pairs = {(i, j) for i in rows for j in cols}
        touched = [("x", i) for i in rows] + [("y", j) for j in cols]
    return len(pairs), len(touched)


@pytest.mark.parametrize("origin,n,same", [
    ((0, 0), (6, 6), True), ((3, 5), (6, 6), True), ((5, 3), (4, 7), True),
    ((0, 20), (6, 6), True), ((0, 0), (5, 7), False)])
def test_least_work(origin, n, same):
    a = np.zeros((n[0], 3), np.float32)
    b = np.zeros((n[1], 3), np.float32)
    req = traffic.Request(0, a, b, origin, same)
    got = work.least_work(req)
    pairs, rows = _brute(req)
    assert got["pairs"] == pairs
    assert got["flops"] == 2 * 3 * pairs
    assert got["bytes"] == rows * 3 * 4 + n[0] * n[1] * 4


def test_least_time_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.least_time(197e12, 0, peak, 1) == (1.0, "compute")
    assert work.least_time(0, 819e9 * 4, peak, 4) == (1.0, "memory")
    with pytest.raises(KeyError):
        work.peaks("cpu")
