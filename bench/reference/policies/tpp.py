"""TPP: promotion after two NUMA hint faults on a slow page, in clock
order; demotion from the least recently sampled fast pages, keeping a
free-page watermark; hint faults cost slow accesses time."""
from ..engine import ranked_take
from .base import BasePolicy, binary_apply, capacity_victims, np


class Policy(BasePolicy):
    slow_extra_ns = 60.0

    def __init__(self, *a):
        super().__init__(*a)
        ft = self.ft
        self.in_fast = np.zeros(self.n, bool)
        self.faults = np.zeros(self.n, ft)
        self.last = np.zeros(self.n, np.int64)
        self.limit = int(self.kn["migration_limit"])

    def observe(self, obs):
        ft = self.ft
        self.t += 1
        add = np.where(self.in_fast, ft(0.0), np.minimum(obs, ft(4.0)))
        self.faults = (self.faults + add).astype(ft)
        self.last = np.where(obs > 0, self.t, self.last)

    def policy(self, slow_bw, app_bw):
        n, k, ft = self.n, self.k, self.ft
        eligible = (self.faults >= ft(self.kn["promote_hits"])) \
            & ~self.in_fast
        start = (self.t * 97) % n
        clock = (np.arange(n) - start) % n
        want = ranked_take(clock, eligible, max(1, min(n, self.limit)),
                           self.limit)
        free = k - int(self.in_fast.sum())
        target_free = int(np.floor(
            ft(ft(1.0) - ft(self.kn["watermark"])) * ft(k)))
        victims, n_take = capacity_victims(
            self.in_fast, self.last, self.in_fast, len(want), k,
            max(1, min(n, k)), extra_need=target_free - free)
        promote = want[:n_take]
        self.in_fast = binary_apply(self.in_fast, promote, victims)
        self.faults[promote] = 0.0
        self.faults[victims] = 0.0
        return promote, victims
