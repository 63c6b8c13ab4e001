"""The table of peaks, and the operation and byte counts of the
rooflines (PERF.md, "Layers")."""
import pytest

from _paths import BENCH  # noqa: F401
import trace_reduce
from metrics import _work


def test_peaks_known_kind():
    p = trace_reduce.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_peaks_unknown_kind_raises():
    with pytest.raises(KeyError):
        trace_reduce.peaks("TPU v99")


@pytest.mark.parametrize("fn,args,ops,nbytes", [
    (_work.topk_mask, (7, 1024), 7 * 1024, 7 * 1024 * 5),
    (_work.tier_migrate, (18, 1024, 2, 64, 64), 18 * 1024 * 2,
     18 * (8 * 1024 + 5 * 128)),
])
def test_kernel_counts(fn, args, ops, nbytes):
    assert fn(*args) == (ops, nbytes)


def test_counts_scale_with_shapes():
    o1, b1 = _work.topk_mask(4, 512)
    o2, b2 = _work.topk_mask(8, 1024)
    assert (o2, b2) == (4 * o1, 4 * b1)
    a1 = _work.tier_migrate(2, 512, 3, 64, 64)
    a2 = _work.tier_migrate(2, 512, 3, 128, 128)
    assert a2[0] == a1[0] and a2[1] - a1[1] == 2 * 5 * 128
    l1 = _work.lane_interval("arms", 1024, 2)
    l2 = _work.lane_interval("arms", 2048, 2)
    assert l2 == (2 * l1[0], 2 * l1[1])
    assert l1[1] == 1024 * (17 + 2 * 25)


def test_roofline_takes_the_larger_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert _work.roofline_s(100, 10, peaks) == 1.0
    assert _work.roofline_s(1000, 10, peaks) == 10.0


def test_interval_mfu_is_over_device_time():
    """The whole interval's share divides by the sweep program's device
    time, so host time between sweeps does not lower it."""
    from cell import Cell
    from metrics import interval_mfu

    class Trace:
        def module_s(self, pred):
            return 2.0 if pred("jit__sim_synth_jit") else 99.0

    cell = Cell.load("paper.arms")
    peaks = trace_reduce.peaks("TPU v5 lite")
    ctx = dict(cell=cell, trace=Trace(), peaks=peaks, lane_intervals=500)
    per = _work.roofline_s(*_work.lane_interval("arms", cell.n, 2), peaks)
    assert interval_mfu.read(ctx) == pytest.approx(100 * per * 500 / 2.0)
