"""The chip benchmark's work model: operations and bytes from shapes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import peaks, workmodel  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]


@pytest.mark.parametrize("i,j,d,flops,nbytes", [
    # covertype: 1024 x 1024 block, D = 54
    (1024, 1024, 54, 1024 * 1024 * 112, 4 * (2 * 1024 * 54 + 4 * 1024)),
    # D = 784, MNIST's width
    (1024, 1024, 784, 1024 * 1024 * 1572, 4 * (2 * 1024 * 784 + 4 * 1024)),
    # D = 28, HIGGS's width
    (1024, 1024, 28, 1024 * 1024 * 60, 4 * (2 * 1024 * 28 + 4 * 1024)),
])
def test_train_pass_work(i, j, d, flops, nbytes):
    w = workmodel.train_pass(i, j, d)
    assert w.flops == flops
    assert w.bytes == nbytes


def test_serve_work_counts_real_rows_only():
    w = workmodel.serve(16, 487_460, 54)
    assert w.flops == 16 * 487_460 * 110
    assert w.bytes == 4 * (487_460 * 54 + 487_460 + 16 * 54 + 16)
    assert workmodel.serve(0, 487_460, 54).flops == 0


def test_min_seconds_names_the_bound():
    t, bound = workmodel.min_seconds(workmodel.train_pass(1024, 1024, 784),
                                     V5E.flops_per_s, V5E.hbm_bytes_per_s)
    assert bound == "compute"
    assert t == pytest.approx(1024 * 1024 * 1572 / 197e12)
    t, bound = workmodel.min_seconds(workmodel.serve(1, 487_460, 54),
                                     V5E.flops_per_s, V5E.hbm_bytes_per_s)
    assert bound == "memory"
    assert t == pytest.approx(4 * (487_460 * 55 + 55) / 819e9)


def test_min_seconds_sum_adds_per_call_bounds():
    works = [workmodel.serve(q, 487_460, 54) for q in (1, 1024)]
    total, bound = workmodel.min_seconds_sum(works, V5E.flops_per_s,
                                             V5E.hbm_bytes_per_s)
    parts = [workmodel.min_seconds(w, V5E.flops_per_s,
                                   V5E.hbm_bytes_per_s)[0] for w in works]
    assert total == pytest.approx(sum(parts))
    assert bound == "compute"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("TPU v99")
    assert peaks.peak_for("TPU v5 lite").flops_per_s == 197e12
