"""Operations and bytes from shapes, for the rooflines.

Counts are of the work the algorithm needs, whatever implements it:
each array it must read or write, once, and one operation per
arithmetic step of its formula.  ``B`` is the lanes a call covers,
``W`` the workloads, ``n`` the pages, ``R`` the tiers, ``P``/``D`` the
widths of the promotion and demotion plans (PERF.md, "Layers").
These are bounds only for operands that come from HBM: the accounting
and EWMA kernels read operands the compiler keeps in on-chip memory,
and ran faster than such a bound allows (PERF.md, Findings), so
they have no roofline metric.
"""
from __future__ import annotations

#: per-page policy state, bytes (the families' published state: scores,
#: counters, residency); read and written once per lane-interval
STATE_BYTES = {"oracle": 5, "arms": 25, "hemem": 9, "memtis": 5, "tpp": 9,
               "hybridtier": 8, "jenga": 20, "tierbpf": 12}
#: operations per page of one lane-interval: the 24-term Poisson inverse
#: CDF (3 each), the policy's EWMA (6) and the cost model (2 per tier)
SAMPLE_OPS, POLICY_OPS = 72, 6


def topk_mask(W, n):
    """Exact top-k mask of [W, n] f32 rows: read the row, write the mask,
    one comparison per element."""
    return W * n, W * n * (4 + 1)


def tier_migrate(B, n, R, P, D):
    """Hop-chain migrations: read and write the tier row (i32), read the
    plans (i32), write the executed masks; count each tier's occupancy."""
    return B * n * R, B * (8 * n + 5 * (P + D))


def lane_interval(family, n, R):
    """One lane-interval of the whole sweep: read the interval's true
    counts and uniform row (f32), read and write the tier index (i32) and
    the policy's per-page state, read the oracle mask."""
    ops = n * (SAMPLE_OPS + POLICY_OPS + 2 * R)
    return ops, n * (4 + 4 + 8 + 1 + 2 * STATE_BYTES[family])


def roofline_s(ops, nbytes, peaks):
    """Least time the chip could take for the work."""
    return max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
