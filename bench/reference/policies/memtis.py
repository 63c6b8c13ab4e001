"""Memtis: the hot threshold is the k-th largest count, re-read every
adaptation period; counts halve after a fixed number of samples."""
from ..engine import ranked_take
from .base import BasePolicy, binary_apply, capacity_victims, np


class Policy(BasePolicy):
    def __init__(self, *a):
        super().__init__(*a)
        ft = self.ft
        self.counts = np.zeros(self.n, ft)
        self.in_fast = np.zeros(self.n, bool)
        self.samples = ft(0.0)
        self.thr = ft(1.0)
        self.limit = int(self.kn["migration_limit"])

    def observe(self, obs):
        ft = self.ft
        counts = (self.counts + obs).astype(ft)
        samples = ft(self.samples + obs.sum(dtype=ft))
        if samples >= ft(self.kn["cooling_period_samples"]):
            counts = (counts * ft(0.5)).astype(ft)
            samples = ft(0.0)
        self.counts, self.samples = counts, samples
        self.t += 1

    def policy(self, slow_bw, app_bw):
        n, k, ft = self.n, self.k, self.ft
        every = max(int(self.kn["adaptation_period"]), 1)
        kth = np.partition(self.counts, n - k)[n - k]
        if self.t % every == 0:
            self.thr = max(ft(kth), ft(1.0))
        hot = self.counts >= self.thr
        pad = max(1, min(n, self.limit))
        want = ranked_take(-self.counts, hot & ~self.in_fast, pad,
                           self.limit)
        victims, n_take = capacity_victims(
            self.in_fast, self.counts, self.in_fast & ~hot, len(want), k,
            pad)
        promote = want[:n_take]
        self.in_fast = binary_apply(self.in_fast, promote, victims)
        return promote, victims
