"""Jenga: responsive EWMA ranking, a move only after its target held for
``confirm`` passes, and a ``cooldown`` pin after every move."""
from ..engine import pair_budgets, rank_desc, rank_partition, tier_plan
from .base import BasePolicy, np, period_fires


class Policy(BasePolicy):
    tier_native = True

    def __init__(self, *a):
        super().__init__(*a)
        R = self.mach.R
        self.ewma = np.zeros(self.n, self.ft)
        self.tier = np.full(self.n, R - 1, np.int64)
        self.streak = np.zeros(self.n, np.int64)
        self.last_tgt = np.full(self.n, R - 1, np.int64)
        self.moved_at = np.full(self.n, -(10 ** 6), np.int64)
        self.passes = 0

    def observe(self, obs):
        ft = self.ft
        a = ft(min(max(ft(self.kn["alpha"]), ft(0.0)), ft(1.0)))
        self.ewma = ((ft(1) - a) * self.ewma + a * obs).astype(ft)
        self.t += 1

    def fires(self):
        return period_fires(self.t, self.kn["migration_period"])

    def tier_policy(self, util, slow_bw, app_bw, caps):
        ft = self.ft
        bs = int(self.kn["bs_max"])
        p = self.passes + 1
        raw = rank_partition(rank_desc(self.ewma), caps)
        self.streak = np.where(raw == self.last_tgt, self.streak + 1, 1)
        conf = max(int(self.kn["confirm"]), 1)
        cool = max(int(self.kn["cooldown"]), 0)
        eligible = (self.streak >= conf) & (p - self.moved_at > cool)
        tgt = np.where(eligible, raw, self.tier)
        pad = max(1, min(self.n, 2 * bs))
        pages, dst, tier = tier_plan(
            self.ewma, self.tier, tgt, caps, pair_budgets(util, bs, ft),
            pad, pad)
        self.moved_at = np.where(tier != self.tier, p, self.moved_at)
        self.tier, self.last_tgt, self.passes = tier, raw, p
        return pages, dst
