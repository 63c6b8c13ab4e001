"""TierBPF: an admission bar on promotions, and per-pair budgets scaled
down by the regret of recent promotions that the ranking sends back."""
from ..engine import pair_budgets, rank_desc, rank_partition, tier_plan
from .base import BasePolicy, np, period_fires


class Policy(BasePolicy):
    tier_native = True

    def __init__(self, *a):
        super().__init__(*a)
        self.ewma = np.zeros(self.n, self.ft)
        self.tier = np.full(self.n, self.mach.R - 1, np.int64)
        self.up_at = np.full(self.n, -(10 ** 6), np.int64)
        self.regret = self.ft(0.0)
        self.passes = 0

    def observe(self, obs):
        ft = self.ft
        a = ft(min(max(ft(self.kn["alpha"]), ft(0.0)), ft(1.0)))
        self.ewma = ((ft(1) - a) * self.ewma + a * obs).astype(ft)
        self.t += 1

    def fires(self):
        return period_fires(self.t, self.kn["migration_period"])

    def tier_policy(self, util, slow_bw, app_bw, caps):
        ft, kn = self.ft, self.kn
        bs = int(kn["bs_max"])
        p = self.passes + 1
        raw = rank_partition(rank_desc(self.ewma), caps)
        recent = self.up_at == p - 1
        flip = ft((recent & (raw > self.tier)).sum())
        now = ft(flip / max(ft(recent.sum()), ft(1.0)))
        ra = ft(min(max(ft(kn["regret_alpha"]), ft(0.0)), ft(1.0)))
        self.regret = ft(ft(ft(1) - ra) * self.regret + ra * now)
        scale = ft(min(max(ft(ft(1.0) - ft(kn["thrash_gain"]) * self.regret),
                           ft(0.0)), ft(1.0)))
        budgets = pair_budgets(util, bs, ft)
        budgets = np.maximum(np.floor(
            (budgets.astype(ft) * scale).astype(ft)).astype(np.int64), 1)
        tgt = np.where((raw < self.tier)
                       & (self.ewma < ft(kn["admit_thresh"])),
                       self.tier, raw)
        pad = max(1, min(self.n, 2 * bs))
        pages, dst, tier = tier_plan(self.ewma, self.tier, tgt, caps,
                                     budgets, pad, pad)
        self.up_at = np.where(tier < self.tier, p, self.up_at)
        self.tier, self.passes = tier, p
        return pages, dst
