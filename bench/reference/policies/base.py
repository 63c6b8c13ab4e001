"""Shared pieces of the reference policies."""
from __future__ import annotations

import numpy as np

from ..engine import ranked_take


class BasePolicy:
    tier_native = False
    wants_true = False
    slow_extra_ns = 0.0

    def __init__(self, knobs, n, k, mach, ft):
        self.kn, self.n, self.k, self.mach, self.ft = knobs, n, k, mach, ft
        self.t = 0

    def period(self):
        return self.ft(self.kn.get("sample_period", 10_000.0))

    def fires(self):
        return True

    def mode(self):
        return 0


def capacity_victims(in_fast, cold_key, cold_mask, n_want, k, pad_d,
                     extra_need=0):
    """Free slots first, then the coldest masked pages; -> (victims,
    how many promotions fit)."""
    free = k - int(in_fast.sum())
    need = max(max(n_want - free, extra_need), 0)
    victims = ranked_take(cold_key, cold_mask, pad_d, need)
    return victims, min(n_want, free + len(victims))


def binary_apply(in_fast, promote, victims):
    in_fast = in_fast.copy()
    in_fast[victims] = False
    in_fast[promote] = True
    return in_fast


def period_fires(t, period):
    return t % max(int(period), 1) == 0


__all__ = ["BasePolicy", "capacity_victims", "binary_apply", "period_fires",
           "np"]
