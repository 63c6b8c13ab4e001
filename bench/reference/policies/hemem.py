"""HeMem: cooled sample counts against a static hot threshold; serial
FIFO promotion in hot-discovery order, demotions only to make room."""
from ..engine import ranked_take
from .base import BasePolicy, binary_apply, capacity_victims, np, \
    period_fires


class Policy(BasePolicy):
    def __init__(self, *a):
        super().__init__(*a)
        ft = self.ft
        self.counts = np.zeros(self.n, ft)
        self.in_fast = np.zeros(self.n, bool)
        self.first_hot = np.full(self.n, np.inf, np.float32)
        self.limit = int(self.kn["migration_limit"])

    def observe(self, obs):
        ft = self.ft
        self.t += 1
        counts = (self.counts + obs).astype(ft)
        if counts.max() >= ft(self.kn["cooling_threshold"]):
            counts = (counts * ft(0.5)).astype(ft)
        self.counts = counts
        hot = counts >= ft(self.kn["hot_threshold"])
        newly = hot & np.isinf(self.first_hot)
        self.first_hot = np.where(newly, np.float32(self.t), self.first_hot)
        self.first_hot = np.where(hot, self.first_hot, np.inf).astype(
            np.float32)

    def fires(self):
        return period_fires(self.t, self.kn["migration_period"])

    def policy(self, slow_bw, app_bw):
        n, k = self.n, self.k
        hot = self.counts >= self.ft(self.kn["hot_threshold"])
        pad = max(1, min(n, self.limit))
        want = ranked_take(self.first_hot, hot & ~self.in_fast, pad,
                           self.limit)
        victims, n_take = capacity_victims(
            self.in_fast, self.counts, self.in_fast & ~hot, len(want), k,
            pad)
        promote = want[:n_take]
        self.in_fast = binary_apply(self.in_fast, promote, victims)
        return promote, victims
