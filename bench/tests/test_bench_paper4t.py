"""``paper4t.arms`` at sizes a test run holds: the program against the
reference, the bfloat16 control, and a run whose rows take several page
blocks of the fused kernels (their block budget made small), on the
CPU."""
import json

import pytest

from _paths import BENCH  # noqa: F401
import check
import readings
import run
from cell import Cell

NAME = "paper4t.arms"


def small(n=1024, k=256, T=12):
    """Every lane of the cell at ``n`` pages, each one checked."""
    c = Cell.load(NAME).scaled(n, k, T)
    return c.scaled(n, k, T, lanes_per_policy=c.lanes)


def drive(cell, capsys, seed):
    import jax
    rc = run.main(["--workload", NAME, "--seed", str(seed),
                   "--seconds", "0.01", "--trace", "0"],
                  devices=jax.devices(), cell=cell)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_is_the_arms_route_at_4tib():
    cell = Cell.load(NAME)
    arms = Cell.load("paper.arms")
    assert (cell.n, cell.k, cell.T) == (2097152, 524288, 16)
    assert cell.n * cell.config["page_bytes"] == 4 << 40
    assert cell.policies == arms.policies and cell.lanes == 18
    assert cell.config["machines"] == arms.config["machines"]
    assert cell.config["workloads"] == arms.config["workloads"]


def test_program_agrees_with_reference(capsys):
    out = drive(small(), capsys, seed=2 ** 31 + 17)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 18


def test_control_is_not_correct():
    cell = small()
    gaps = readings.control_gaps(cell, 5)
    lim = check.limits(NAME)
    assert gaps["nonfinite_lanes"] > 0 or any(
        gaps[k] > lim[k] for k in lim), gaps


@pytest.fixture
def blocked(monkeypatch):
    """The interval ops routed through the interpret-mode kernels, whose
    rows walk blocks of 8 page rows (1024 pages)."""
    import jax
    from repro.kernels.interval_step import kernel
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    monkeypatch.setattr(kernel, "BLOCK_ROWS", 8)
    jax.clear_caches()
    yield kernel
    jax.clear_caches()


def test_blocked_rows_agree_with_reference(blocked, capsys):
    """3000 pages: three blocks a row, the last ending off the block."""
    cell = small(n=3000, k=750, T=6)
    with blocked.count_blocks() as blocks:
        out = drive(cell, capsys, seed=2 ** 31 + 29)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    assert {k: set(v) for k, v in blocks.items()} == {
        "topk_mask_kernel": {3}, "interval_account_kernel": {3},
        "ewma_update_kernel": {3}}


@pytest.mark.parametrize("metric,args,ops,nbytes", [
    ("account_blocked_roofline", (18, 1024, 2), 18 * 1024 * 4,
     18 * 1024 * 9),
    ("ewma_blocked_roofline", (18, 1024), 18 * 1024 * 9, 18 * 1024 * 24),
])
def test_blocked_kernel_counts(metric, args, ops, nbytes):
    import importlib
    mod = importlib.import_module(f"metrics.{metric}")
    assert mod.work(*args) == (ops, nbytes)


def test_blocked_rooflines_read_their_kernels():
    """Each share is calls x least time per call over the kernel's
    device time, and reads nothing where the kernel did not run."""
    import importlib
    import trace_reduce
    from metrics import _work

    class Trace:
        def __init__(self, names):
            self.names = names

        def count(self, pred):
            return sum(map(pred, self.names))

        def select_s(self, pred):
            return 0.25 * self.count(pred)

    cell = Cell.load(NAME)
    peaks = trace_reduce.peaks("TPU v5 lite")
    names = ["%topk_mask_kernel.3 = ", "%interval_account_kernel.1 = ",
             "%vmap_jit_ewma_update_kernel__.2 = "]
    want = {"topk_mask_blocked_roofline": _work.topk_mask(9, cell.n),
            "account_blocked_roofline": (0, 18 * cell.n * 9),
            "ewma_blocked_roofline": (0, 18 * cell.n * 24)}
    for metric, (ops, nbytes) in want.items():
        mod = importlib.import_module(f"metrics.{metric}")
        per = nbytes / peaks["hbm_bytes_per_s"]
        got = mod.read(dict(cell=cell, trace=Trace(names), peaks=peaks))
        assert got == pytest.approx(100 * per / 0.25), metric
        assert mod.read(dict(cell=cell, trace=Trace([]), peaks=peaks)) \
            is None
