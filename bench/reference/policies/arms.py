"""ARMS (arXiv 2508.04417 sections 4-5): dual-EWMA scores, top-k hot
set, multi-round filter and cost/benefit gate, bandwidth-aware batches,
Page-Hinkley change detection switching history and recency modes."""
from ..engine import NEG_BIG, top_k_idx, top_k_mask
from .base import BasePolicy, np

PERIOD = (10_000.0, 5_000.0)      # sampling period by mode
EVERY = (5, 1)                     # policy cadence by mode


class Policy(BasePolicy):
    def __init__(self, *a):
        super().__init__(*a)
        ft, n, c = self.ft, self.n, self.kn
        z = np.zeros(n, ft)
        self.ewma_s, self.ewma_l, self.score, self.prev = z, z, z, z
        self.age = np.zeros(n, np.int64)
        self.in_fast = np.zeros(n, bool)
        self.md, self.ttl = 0, 0
        self.sig_s = self.sig_l = ft(0.0)
        self.promo_cost = ft(c["init_promo_cost_us"])
        self.demo_cost = ft(c["init_demo_cost_us"])
        self.ph_n, self.ph_mean, self.ph_m, self.ph_min = \
            0, ft(0.0), ft(0.0), ft(0.0)
        self.buf = np.zeros(n, ft)
        self.promo_us = self.mach.promo_path_us
        self.demo_us = self.mach.demo_path_us

    def period(self):
        return self.ft(PERIOD[self.md])

    def mode(self):
        return self.md

    def observe(self, obs):
        self.buf = (self.buf + obs).astype(self.ft)
        self.t += 1

    def fires(self):
        return self.t % EVERY[self.md] == 0

    def _ew(self, a, x, prev):
        ft = self.ft
        return (ft(a) * x + ft(1 - a) * prev).astype(ft)

    def policy(self, slow_bw, app_bw):
        ft, c, n, k = self.ft, self.kn, self.n, self.k
        counts = (self.buf / ft(EVERY[self.md])).astype(ft)
        # Page-Hinkley on the slow-tier signal -> mode
        x = ft(slow_bw)
        self.sig_s = ft(self._ew(c["alpha_s"], x, self.sig_s))
        self.sig_l = ft(self._ew(c["alpha_l"], x, self.sig_l))
        stabilized = self.sig_s <= ft(self.sig_l + ft(c["stabilize_eps"]))
        pn = self.ph_n + 1
        mean = ft(self.ph_mean + ft(x - self.ph_mean) / ft(pn))
        m_t = ft(self.ph_m + ft(ft(x - mean) - ft(c["pht_delta"])))
        m_min = min(self.ph_min, m_t)
        alarm = ft(m_t - m_min) > ft(c["pht_lambda"])
        if alarm:
            self.ph_n, self.ph_mean, self.ph_m, self.ph_min = \
                0, ft(0.0), ft(0.0), ft(0.0)
        else:
            self.ph_n, self.ph_mean, self.ph_m, self.ph_min = \
                pn, mean, m_t, m_min
        if alarm:
            self.ttl = int(c["recency_ttl"])
        elif stabilized:
            self.ttl = max(self.ttl - 1, 0)
        self.md = 1 if self.ttl > 0 else 0
        # dual EWMA + score (Algorithm 1)
        rec = self.md == 1
        w_s = ft(c["w_s_recency"] if rec else c["w_s_history"])
        w_l = ft(c["w_l_recency"] if rec else c["w_l_history"])
        a_s, a_l = ft(c["alpha_s"]), ft(c["alpha_l"])
        self.ewma_s = (a_s * counts + (ft(1) - a_s) * self.ewma_s).astype(ft)
        self.ewma_l = (a_l * counts + (ft(1) - a_l) * self.ewma_l).astype(ft)
        self.prev = self.score
        self.score = (w_s * self.ewma_s + w_l * self.ewma_l).astype(ft)
        hot = top_k_mask(self.score, k)
        self.age = np.where(hot, self.age + 1, 0)
        # candidates, victims, cost/benefit gate (Algorithm 2)
        bs = min(int(c["bs_max"]), n)
        neg = ft(NEG_BIG)
        is_cand = hot & ~self.in_fast & (self.score >= self.prev) \
            & (self.age >= int(c["hot_age_min"]))
        keyed = np.where(is_cand, self.score, neg).astype(ft)
        cand = top_k_idx(keyed, bs)
        cand_ok = keyed[cand] > neg
        keyed = np.where(self.in_fast & ~hot, -self.score, neg).astype(ft)
        vict = top_k_idx(keyed, bs)
        vict_ok = keyed[vict] > neg
        free = k - int(self.in_fast.sum())
        j = np.arange(bs)
        uses_free = j < free
        vpos = np.clip(j - free, 0, bs - 1)
        victim, victim_ok = vict[vpos], vict_ok[vpos] & ~uses_free
        q = np.where(uses_free, ft(0.0), self.score[victim]).astype(ft)
        p = self.score[cand]
        age = self.age[cand].astype(ft)
        noise = (ft(c["noise_z"]) * np.sqrt(np.maximum(p + q, ft(0.0)))
                 ).astype(ft)
        gain = np.maximum(((p - q).astype(ft) - noise), ft(0.0)).astype(ft)
        dl = ft(c["latency_slow_us"] - c["latency_fast_us"])
        benefit = (((gain * age).astype(ft) * dl).astype(ft)
                   * ft(c["access_scale"])).astype(ft)
        cost = np.where(uses_free, self.promo_cost,
                        ft(self.promo_cost + self.demo_cost)).astype(ft)
        ok = cand_ok & (uses_free | victim_ok) & (benefit > cost)
        demote = np.where(uses_free, -1, victim)
        # bandwidth-aware batch, priority order (section 4.4)
        frac = min(max(ft(ft(1.0) - ft(app_bw)), ft(0.0)), ft(1.0))
        batch = min(max(int(np.floor(ft(frac * ft(bs)))), 1), bs)
        valid = ok & (np.cumsum(ok) - 1 < batch)
        promote = cand[valid]
        demote = demote[valid]
        demote = demote[demote >= 0]
        self.in_fast = self.in_fast.copy()
        self.in_fast[demote] = False
        self.in_fast[promote] = True
        if valid.any():                # self-calibrating migration costs
            a = c["migrate_cost_alpha"]
            self.promo_cost = self._ew(a, self.promo_us, self.promo_cost)
            self.demo_cost = self._ew(a, self.demo_us, self.demo_cost)
        self.buf = np.zeros(n, ft)
        return promote, demote
