"""The comparison that decides ``correct``: a sample of the timed sweep's
lanes, drawn from the seed, replayed by the plain reference
(``reference/engine.py``) and compared statistic by statistic.

Each compared number is the widest gap over the sampled lanes, held to
the limit that ``checks/<cell>.json`` gives it.
"""
from __future__ import annotations

import json
import os

import numpy as np

from reference import engine as ref

BENCH = os.path.dirname(os.path.abspath(__file__))

#: compared numbers: name -> (statistic, kind); "rel" gaps are taken
#: against the reference's value, "cap" gaps in units of the fast tier's
#: capacity (pages migrated), "abs" gaps are differences of shares.
NUMBERS = {
    "exec_time_gap": ("exec_time_s", "rel"),
    "migrations_gap": ("migrations", "cap"),
    "fast_hit_gap": ("fast_hit_frac", "abs"),
    "slow_bw_gap": ("mean_slow_bw", "abs"),
    "hot_recall_gap": ("hot_recall", "abs"),
}
STATS = ("exec_time_s", "promotions", "demotions", "fast_hit_frac",
         "mean_slow_bw", "hot_recall")


def limits(cell_name: str) -> dict:
    with open(os.path.join(BENCH, "checks", cell_name + ".json")) as f:
        return json.load(f)["limits"]


def sample_lanes(cell, seed: int) -> list:
    """``lanes_per_policy`` (policy, workload, machine) lanes for each
    policy of the panel, drawn from the seed without replacement."""
    rng = np.random.default_rng(seed)
    W, M = len(cell.workloads), len(cell.machines)
    per = min(int(cell.traffic["check"]["lanes_per_policy"]), W * M)
    out = []
    for p in range(len(cell.policies)):
        for j in sorted(rng.choice(W * M, size=per, replace=False)):
            out.append((p, int(j) // M, int(j) % M))
    return out


def program_stats(result, cell, lanes) -> list:
    W, M = len(cell.workloads), len(cell.machines)
    out = []
    for p, w, m in lanes:
        r = result.grid[(p * W + w) * M + m]
        out.append({s: float(getattr(r, s)) for s in STATS})
    return out


def reference_stats(cell, lanes, seed: int, ft=np.float32) -> list:
    """Replay ``lanes`` with the reference in precision ``ft``."""
    n, k, T = cell.n, cell.k, cell.T
    cfg = cell.config
    u_rows = [ref.uniform_row(seed, t, n) for t in range(T)]
    rows = {}
    for w in sorted({w for _, w, _ in lanes}):
        wl = ref.Workload(cell.workloads[w]["components"], n, seed, ft)
        true = [wl.step(t) for t in range(T)]
        rows[w] = (true, [ref.top_k_mask(x, k) for x in true])
    out = []
    for p, w, m in lanes:
        pol = cell.policies[p]
        mach = ref.Machine(cfg["machines"][cell.machines[m]], n, k,
                           cfg["page_bytes"], cfg["cacheline_bytes"], ft)
        out.append(ref.run_lane(pol["family"], pol["knobs"], rows[w][0],
                                rows[w][1], u_rows, mach, n, k, T,
                                cfg["waste_window"], ft))
    return out


def _value(stats, name):
    if name == "migrations":
        return float(stats["promotions"] + stats["demotions"])
    return float(stats[name])


def gaps(got: list, want: list, fast_pages: int) -> dict:
    """Widest gap of each compared number over the lanes."""
    out = {}
    for num, (stat, kind) in NUMBERS.items():
        worst = 0.0
        for g, w in zip(got, want):
            a, b = _value(g, stat), _value(w, stat)
            if not (np.isfinite(a) and np.isfinite(b)):
                worst = float("inf")
                continue
            d = abs(a - b)
            if kind == "rel":
                d /= max(abs(b), 1e-30)
            elif kind == "cap":
                d /= fast_pages
            worst = max(worst, d)
        out[num] = worst
    return out


def lane_failures(got, want, lim, fast_pages: int) -> int:
    """Lanes on which some compared number is over its limit."""
    return sum(any(gaps([g], [w], fast_pages)[k] > lim[k] for k in lim)
               for g, w in zip(got, want))
