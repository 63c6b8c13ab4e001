"""Oracle: true counts, the top-k pages placed in the fast tier every
interval."""
from ..engine import ranked_take, top_k_mask
from .base import BasePolicy, np


class Policy(BasePolicy):
    wants_true = True

    def __init__(self, *a):
        super().__init__(*a)
        self.in_fast = np.zeros(self.n, bool)
        self.last = np.zeros(self.n, self.ft)

    def observe(self, obs):
        self.last = obs
        self.t += 1

    def policy(self, slow_bw, app_bw):
        n, k = self.n, self.k
        target = top_k_mask(self.last, k)
        idx = np.arange(n)
        pad = max(1, min(n, k))
        promote = ranked_take(idx, target & ~self.in_fast, pad)
        demote = ranked_take(idx, ~target & self.in_fast, pad, len(promote))
        self.in_fast = target
        return promote, demote
