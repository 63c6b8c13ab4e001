"""The comparison that decides ``correct``, at a size a test run holds:
the program against the reference, the control (the reference in
bfloat16 in the program's place) and the faults of the timed path."""
import json

import numpy as np
import pytest

from _paths import BENCH  # noqa: F401
import check
import readings
import run
from cell import Cell

#: small sizes of each cell: pages, fast pages, intervals; every lane is
#: checked so that a fault in any lane shows
SMALL = {"paper.arms": (1024, 256, 12), "paper.mixed8": (512, 128, 8),
         "paper.hemem-grid": (1024, 256, 8)}


def small(name):
    n, k, T = SMALL[name]
    c = Cell.load(name).scaled(n, k, T)
    W, M = len(c.workloads), len(c.machines)
    return c.scaled(n, k, T, lanes_per_policy=W * M)


def drive(cell, capsys, seed=11):
    """A whole run of ``bench/run.py`` on the CPU, without the look for a
    chip; -> its result line."""
    import jax
    rc = run.main(["--workload", cell.name, "--seed", str(seed),
                   "--seconds", "0.01", "--trace", "0"],
                  devices=jax.devices(), cell=cell)
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_agrees_with_reference(name, capsys):
    out = drive(small(name), capsys, seed=2 ** 31 + 7)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == small(name).lanes
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"lane_intervals_per_s", "peak_hbm_gb",
                                   "setup_s"}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_not_correct(name):
    cell = small(name)
    gaps = readings.control_gaps(cell, 5)
    lim = check.limits(name)
    assert gaps["nonfinite_lanes"] > 0 or any(
        gaps[k] > lim[k] for k in lim), gaps


def _alter_one_answer(monkeypatch):
    """Lane 0's execution time doubled where its result is made."""
    from repro.simulator import scan_engine
    orig = scan_engine._to_result

    def to_result(out, lane, name):
        r = orig(out, lane, name)
        if lane == 0:
            r.exec_time_s *= 2.0
        return r
    monkeypatch.setattr(scan_engine, "_to_result", to_result)


def _half_the_lanes(monkeypatch):
    from repro.simulator import scan_engine
    orig = scan_engine._to_result

    def to_result(out, lane, name):
        L = int(out["exec_time"].shape[0])
        return orig(out, lane % max(L // 2, 1), name)
    monkeypatch.setattr(scan_engine, "_to_result", to_result)


def _state_unchanged(monkeypatch):
    """Migrations counted but the placement left as it was."""
    import jax
    from repro.kernels.interval_step import ops
    from repro.simulator import simjax
    m_hop, m_tgt = ops.tier_migrate, simjax.apply_targeted_migrations

    def hop(tier, *a):
        return (tier,) + tuple(m_hop(tier, *a)[1:])

    def tgt(tier, *a):
        return (tier,) + tuple(m_tgt(tier, *a)[1:])
    monkeypatch.setattr(ops, "tier_migrate", hop)
    monkeypatch.setattr(simjax, "apply_targeted_migrations", tgt)
    jax.clear_caches()


FAULTS = {"answer": _alter_one_answer, "half": _half_the_lanes,
          "state": _state_unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_is_not_correct(name, fault, capsys, monkeypatch):
    import jax
    FAULTS[fault](monkeypatch)
    try:
        out = drive(small(name), capsys)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert out["correct"] is False, out["checks"]
    assert out["failed"] >= 1


def test_sample_is_drawn_from_the_seed():
    cell = Cell.load("paper.mixed8")
    a, b = check.sample_lanes(cell, 1), check.sample_lanes(cell, 1)
    assert a == b and a != check.sample_lanes(cell, 2)
    per = cell.traffic["check"]["lanes_per_policy"]
    assert len(a) == per * len(cell.policies)
    fams = [p for p, _, _ in a]
    assert all(fams.count(p) == per for p in range(len(cell.policies)))


def test_gaps_measure_the_widest_lane():
    base = dict(exec_time_s=2.0, promotions=10, demotions=10, wasteful=0,
                hot_recall=0.5, fast_hit_frac=0.5, mean_slow_bw=0.5)
    off = dict(base, exec_time_s=2.2, promotions=12)
    g = check.gaps([base, off], [base, base], 100)
    assert g["exec_time_gap"] == pytest.approx(0.1)
    assert g["migrations_gap"] == pytest.approx(0.02)
    assert g["fast_hit_gap"] == 0.0
    off = dict(base, hot_recall=0.47)
    assert check.gaps([base, off], [base, base], 100)["hot_recall_gap"] \
        == pytest.approx(0.03)
    assert np.isinf(check.gaps([dict(base, exec_time_s=np.nan)],
                               [base], 100)["exec_time_gap"])
