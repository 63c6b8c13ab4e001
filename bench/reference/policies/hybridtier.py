"""HybridTier: decayed frequency counters ranked against the capacity
ladder, a frequency gate into the fast tier, cold pages sunk to the
bottom, per-pair budgets from tier utilization."""
from ..engine import pair_budgets, rank_desc, rank_partition, tier_plan
from .base import BasePolicy, np, period_fires


class Policy(BasePolicy):
    tier_native = True

    def __init__(self, *a):
        super().__init__(*a)
        self.counts = np.zeros(self.n, self.ft)
        self.tier = np.full(self.n, self.mach.R - 1, np.int64)

    def observe(self, obs):
        ft = self.ft
        self.counts = (self.counts * ft(self.kn["decay"]) + obs).astype(ft)
        self.t += 1

    def fires(self):
        return period_fires(self.t, self.kn["migration_period"])

    def tier_policy(self, util, slow_bw, app_bw, caps):
        ft, R = self.ft, self.mach.R
        bs = int(self.kn["bs_max"])
        tgt = rank_partition(rank_desc(self.counts), caps)
        tgt = np.where((tgt == 0) & (self.tier > 0)
                       & (self.counts < ft(self.kn["hot_thresh"])),
                       self.tier, tgt)
        tgt = np.where(self.counts < ft(self.kn["warm_thresh"]), R - 1, tgt)
        pad = max(1, min(self.n, 2 * bs))
        pages, dst, self.tier = tier_plan(
            self.counts, self.tier, tgt, caps, pair_budgets(util, bs, ft),
            pad, pad)
        return pages, dst
