"""Plain reference of the tiering simulation, one lane at a time.

A straightforward numpy implementation of what a sweep computes for one
(policy, workload, machine) lane: the workload's access distribution per
interval, PEBS-style sampled counts, the policy's decisions, the
migration executor, the interval cost model and the lane's statistics.
It imports nothing of the program under test and reads its inputs from
the benchmark's own configuration and traffic files.  ``jax.random``
supplies the counter-based random streams the sweep's semantics are
defined by (threefry keys, permutations and uniforms are exact integer
computations, the same on every backend).

Every float is computed in ``ft``: float32, the precision the
configurations state, or a lower one (bfloat16) for the control.
"""
from __future__ import annotations

import importlib

import jax
import numpy as np

NEVER = 1 << 30
POISSON_TERMS = 24
NORMAL_SWITCH = 12.0
NEG_BIG = -3.4e38


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _on_cpu(fn, *args):
    dev = _cpu()
    if dev is None:
        return np.asarray(fn(*args))
    with jax.default_device(dev):
        return np.asarray(fn(*args))


# ------------------------------------------------------------- orderings
def order_key(x) -> np.ndarray:
    """int64 key whose ascending order is the float total order
    (-0.0 below +0.0), for f32 or lower-precision floats."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.int64)
    return np.where(u & 0x80000000, 0xFFFFFFFF - u, u | 0x80000000)


def top_k_idx(x, k: int) -> np.ndarray:
    """Indices of the k largest entries, ties by ascending index."""
    x = np.asarray(x)
    key = np.asarray(x, np.int64) if np.issubdtype(x.dtype, np.integer) \
        else order_key(x)
    return np.argsort(-key, kind="stable")[:k]


def top_k_mask(x, k: int) -> np.ndarray:
    m = np.zeros(np.shape(x)[0], bool)
    m[top_k_idx(x, k)] = True
    return m


def ranked_take(key, mask, pad: int, limit=None) -> np.ndarray:
    """Masked indices in ascending ``key`` order (ties by index), at most
    ``pad`` of them and at most ``limit``."""
    n = mask.shape[0]
    pad = max(1, min(pad, n))
    idx = np.flatnonzero(mask)
    neg = -np.asarray(key, np.float32)[idx]
    order = idx[np.argsort(-order_key(neg), kind="stable")]
    count = len(idx)
    if limit is not None:
        count = min(count, int(limit))
    return order[:min(count, pad)]


def rank_desc(score) -> np.ndarray:
    n = score.shape[0]
    order = np.argsort(order_key(-np.asarray(score, np.float32)),
                       kind="stable")
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    return rank


def rank_partition(rank, caps) -> np.ndarray:
    cum = np.cumsum(caps)
    return (rank[:, None] >= cum[None, :-1]).sum(axis=1)


def pair_budgets(tier_util, bs_max: int, ft) -> np.ndarray:
    u = np.maximum(tier_util[:-1], tier_util[1:])
    frac = np.clip(ft(1.0) - u, 0.0, 1.0).astype(ft)
    return np.clip(np.floor(frac * ft(bs_max)).astype(np.int64), 1, bs_max)


def pair_limit(lo, hi, valid, budgets) -> np.ndarray:
    ok = valid.copy()
    for j in range(len(budgets)):
        crosses = valid & (lo <= j) & (j < hi)
        rank = np.cumsum(crosses) - 1
        ok &= ~crosses | (rank < budgets[j])
    return ok


def tier_plan(score, cur, target, caps, budgets, pad_down: int,
              pad_up: int):
    """Moves that take ``cur`` toward ``target`` within per-pair budgets
    and tier capacities: down-moves coldest first, then up-moves hottest
    first.  Returns (pages, dst, new_cur)."""
    R = len(caps)
    target = np.clip(target, 0, R - 1)
    occ = np.array([(cur == r).sum() for r in range(R)])

    d_pages = ranked_take(score, target > cur, pad_down)
    d_cur, d_tgt = cur[d_pages], target[d_pages]
    d_ok = pair_limit(d_cur, d_tgt, np.ones(len(d_pages), bool), budgets)
    adm_d = np.zeros(len(d_pages), bool)
    for r in range(R - 1, 0, -1):
        dep = (adm_d & (d_cur == r)).sum()
        room = caps[r] - occ[r] + dep
        cand = d_ok & (d_tgt == r) & ~adm_d
        adm_d |= cand & (np.cumsum(cand) - 1 < room)
    rem = np.maximum(np.array([
        budgets[j] - (adm_d & (d_cur <= j) & (j < d_tgt)).sum()
        for j in range(R - 1)]), 0)
    occ2 = occ + np.array([(adm_d & (d_tgt == r)).sum()
                           - (adm_d & (d_cur == r)).sum() for r in range(R)])

    u_pages = ranked_take(-np.asarray(score, np.float32), target < cur,
                          pad_up)
    u_cur, u_tgt = cur[u_pages], target[u_pages]
    u_ok = pair_limit(u_tgt, u_cur, np.ones(len(u_pages), bool), rem)
    adm_u = np.zeros(len(u_pages), bool)
    for r in range(R - 1):
        dep = (adm_u & (u_cur == r)).sum()
        room = caps[r] - occ2[r] + dep
        cand = u_ok & (u_tgt == r) & ~adm_u
        adm_u |= cand & (np.cumsum(cand) - 1 < room)

    new_cur = cur.copy()
    new_cur[d_pages[adm_d]] = d_tgt[adm_d]
    new_cur[u_pages[adm_u]] = u_tgt[adm_u]
    pages = np.concatenate([d_pages[adm_d], u_pages[adm_u]])
    dst = np.concatenate([d_tgt[adm_d], u_tgt[adm_u]])
    return pages, dst, new_cur


# ------------------------------------------------------------- executors
DST_BELOW = -2


def hop_migrate(tier, promote, demote, caps):
    """Demotions first, each cascading to the first tier below its source
    with room; then promotions to tier 0 while it has room."""
    R = len(caps)
    tier = tier.copy()
    src = tier[demote]
    dexec = src < R - 1
    dest = np.full(len(demote), R - 1)
    landed = np.zeros(len(demote), bool)
    for r in range(1, R - 1):
        occ_r = (tier == r).sum() - (dexec & (src == r)).sum()
        cand = dexec & ~landed & (src < r)
        land = cand & (np.cumsum(cand) - 1 < caps[r] - occ_r)
        dest[land] = r
        landed |= land
    tier[demote[dexec]] = dest[dexec]
    p_src = tier[promote]
    p_ok = p_src > 0
    room = caps[0] - (tier == 0).sum()
    pexec = p_ok & (np.cumsum(p_ok) - 1 < room)
    tier[promote[pexec]] = 0
    up = np.array([(pexec & (p_src > j)).sum() for j in range(R - 1)])
    down = np.array([(dexec & (src <= j) & (dest > j)).sum()
                     for j in range(R - 1)])
    return tier, promote[pexec], demote[dexec], up, down


def targeted_migrate(tier, pages, dst, caps):
    """Tier-targeted moves: down-moves first (cascading deeper when the
    target is full), then up-moves per destination tier, shallowest
    first, dropped when their exact destination is full."""
    R = len(caps)
    tier = tier.copy()
    src = tier[pages]
    dst = np.where(dst == DST_BELOW, src + 1, dst)
    dst = np.clip(dst, 0, R - 1)
    down = dst > src
    dest = np.full(len(pages), R - 1)
    landed = np.zeros(len(pages), bool)
    for r in range(1, R - 1):
        occ_r = (tier == r).sum() - (down & (src == r)).sum()
        cand = down & ~landed & (dst <= r)
        land = cand & (np.cumsum(cand) - 1 < caps[r] - occ_r)
        dest[land] = r
        landed |= land
    tier[pages[down]] = dest[down]
    mig_down = np.array([(down & (src <= j) & (dest > j)).sum()
                         for j in range(R - 1)])
    up_exec = np.zeros(len(pages), bool)
    up_from = np.zeros(len(pages), np.int64)
    for r in range(R - 1):
        u_src = tier[pages]
        cand = ~down & (dst == r) & (u_src > r)
        room = caps[r] - (tier == r).sum()
        take = cand & (np.cumsum(cand) - 1 < room)
        up_from = np.where(take, u_src, up_from)
        tier[pages[take]] = r
        up_exec |= take
    mig_up = np.array([(up_exec & (up_from > j) & (dst <= j)).sum()
                       for j in range(R - 1)])
    return tier, pages[up_exec], pages[down], mig_up, mig_down


# -------------------------------------------------------------- workload
class Workload:
    """A stack of access components, synthesized interval by interval."""

    def __init__(self, comps, n: int, wl_seed: int, ft):
        self.c = comps
        self.n, self.ft = n, ft
        key = jax.random.PRNGKey(wl_seed)
        self.bk = [jax.random.fold_in(key, c["seed"]) for c in comps]
        self.rank = [self._perm(b, 1, 0) for b in self.bk]
        self.rank2 = [self._perm(b, 2, 0) for b in self.bk]

    def _perm(self, bk, tag, epoch):
        return _on_cpu(lambda: jax.random.permutation(
            jax.random.fold_in(jax.random.fold_in(bk, tag), epoch),
            self.n)).astype(np.int64)

    def _comp_probs(self, i, t):
        c, ft, n = self.c[i], self.ft, self.n
        nf, tf = ft(n), ft(t)
        shift = int(np.floor(ft(c["drift_rate"]) * tf)) % n
        idx = (np.arange(n) - shift) % n
        r = self.rank[i][idx].astype(ft)
        kind = c["kind"]
        one = ft(1.0)
        if kind == 0:                                   # zipf
            p = (r + one) ** ft(-c["s"])
        elif kind in (1, 2):                            # hot set / xsbench
            kh = ft(np.clip(np.round(nf * ft(c["hot_frac"])), 1.0, nf))
            if kind == 1:
                hw = ft(c["hot_weight"])
                p = np.where(r < kh, hw / kh,
                             (one - hw) / max(nf - kh, one)).astype(ft)
            else:
                p = (ft(0.5) / nf + np.where(r < kh, ft(0.5) / kh,
                                             ft(0.0))).astype(ft)
        elif kind == 3:                                 # tpcc window
            w = ft(np.clip(np.round(nf * ft(c["window_frac"])), 1.0,
                           nf - one))
            span = max(nf - w, one)
            head = ft(np.mod(np.floor(ft(c["drift_pages"]) * tf), span))
            off = idx.astype(ft) - head
            inwin = (off >= 0) & (off < w)
            q = np.exp(ft(-2.0) / w)
            denom = (one - q ** w) / (one - q) if w > 1 else one
            dec = np.exp(-(w - one - off) / (w * ft(0.5)))
            p = (ft(0.05) / nf + np.where(inwin, ft(0.95) * dec / denom,
                                          ft(0.0))).astype(ft)
        else:                                           # zipf + boost
            m = (r + one) ** ft(-c["s"])
            base = m / max(m.sum(dtype=ft), ft(1e-30))
            nb = ft(np.clip(np.round(nf * ft(c["boost_frac"])), 1.0, nf))
            r2 = self.rank2[i][idx].astype(ft)
            p = (base + np.where(r2 < nb, ft(c["boost_gain"]) / nb,
                                 ft(0.0))).astype(ft)
        p = np.asarray(p, ft)
        return p / max(p.sum(dtype=ft), ft(1e-30))

    def _rates(self, t):
        ft = self.ft
        out = []
        for c in self.c:
            active = ft(c["t_start"] <= t < c["t_end"])
            per = max(c["period"], 1)
            busy = ft((t + c["phase_off"]) % per) < ft(c["duty"]) * ft(per)
            m = ft(1.0) if busy else ft(c["idle_scale"])
            out.append(ft(ft(ft(c["weight"]) * active) * ft(c["work"])) * m)
        return np.asarray(out, ft)

    def step(self, t: int):
        """Redraw due permutations, then -> f32 [n] true counts."""
        for i, c in enumerate(self.c):
            if not (c["t_start"] <= t < c["t_end"] and t > 0):
                continue
            se, be = max(c["shift_every"], 1), max(c["boost_every"], 1)
            if t % se == 0:
                self.rank[i] = self._perm(self.bk[i], 1, t // se)
            if be < NEVER and t % be == 0:
                self.rank2[i] = self._perm(self.bk[i], 2, t // be)
        ft = self.ft
        rate = self._rates(t)
        tot = rate.sum(dtype=ft)
        if tot > 0:
            mix = np.zeros(self.n, ft)
            for i in range(len(self.c)):
                if rate[i] != 0:
                    mix = (mix + rate[i] * self._comp_probs(i, t)).astype(ft)
            probs = (mix / max(tot, ft(1e-30))).astype(ft)
        else:
            probs = np.full(self.n, ft(1.0 / self.n), ft)
        return (tot * probs).astype(ft)


def uniform_row(sim_seed: int, t: int, n: int) -> np.ndarray:
    """The interval's shared uniform row (counter-based, keyed by t)."""
    key = jax.random.PRNGKey(sim_seed)
    return _on_cpu(lambda: jax.random.uniform(
        jax.random.fold_in(key, t), (n,), dtype=np.float32))


def pebs_sample(u, true, period, ft):
    """Poisson(true / period) by inverse CDF from the uniform ``u``, with
    the rounded normal approximation at rates of 12 and above."""
    from scipy.special import ndtri
    u = u.astype(ft)
    lam = (np.maximum(true, ft(0.0)) / ft(period)).astype(ft)
    pmf = np.exp(-lam).astype(ft)
    cdf = pmf
    out = (cdf < u).astype(ft)
    for j in range(1, POISSON_TERMS):
        pmf = (pmf * lam / ft(j)).astype(ft)
        cdf = (cdf + pmf).astype(ft)
        out = (out + (cdf < u)).astype(ft)
    z = ndtri(np.clip(u.astype(np.float64), 1e-7, 1.0 - 1e-7)).astype(ft)
    large = np.maximum(np.floor(lam + z * np.sqrt(lam) + ft(0.5)), ft(0.0))
    out = np.where(lam < ft(NORMAL_SWITCH), out, large)
    return np.where(lam <= 0, ft(0.0), out).astype(ft)


# --------------------------------------------------------------- machine
class Machine:
    def __init__(self, m: dict, n: int, k: int, page_bytes: int,
                 cacheline: int, ft):
        self.ft = ft
        self.lat = np.asarray(m["lat_ns"], np.float64).astype(ft)
        self.br = np.asarray(m["bw_read"], np.float64).astype(ft)
        self.bw = np.asarray(m["bw_write"], np.float64).astype(ft)
        self.mlp = ft(m["mlp"])
        self.R = len(m["lat_ns"])
        self.page = ft(page_bytes)
        self.cl = ft(cacheline)
        caps = np.asarray(m["capacity_pages"], np.float64)
        out = []
        for r, c in enumerate(caps):
            if r == 0:
                out.append(k)
            elif r == self.R - 1:
                out.append(n)
            elif c == 0:
                out.append(n)
            elif c < 0:
                out.append(int(round(-c * k)))
            else:
                out.append(int(round(c)))
        self.caps = np.clip(np.asarray(out), 0, n)
        br = np.asarray(m["bw_read"], np.float64)
        bw = np.asarray(m["bw_write"], np.float64)
        promo = (page_bytes / br[1:] + page_bytes / bw[:-1]) * 1e6
        demo = (page_bytes / br[:-1] + page_bytes / bw[1:]) * 1e6
        self.promo_path_us = promo.astype(ft).sum(dtype=ft)
        self.demo_path_us = demo.astype(ft).sum(dtype=ft)

    def times(self, acc, up, down):
        ft, R = self.ft, self.R
        up, down = up.astype(ft), down.astype(ft)
        t_lat = acc[0] * self.lat[0]
        for r in range(1, R):
            t_lat = ft(t_lat + acc[r] * self.lat[r])
        t_lat = ft(ft(t_lat * ft(1e-9)) / self.mlp)
        times = [ft((acc[0] * self.cl + (up[0] + down[0]) * self.page)
                    / self.br[0])]
        for r in range(1, R):
            rd = up[r - 1] + (down[r] if r < R - 1 else ft(0.0))
            wr = down[r - 1] + (up[r] if r < R - 1 else ft(0.0))
            times.append(ft((acc[r] * self.cl + rd * self.page) / self.br[r]
                            + wr * self.page / self.bw[r]))
        return t_lat, times

    def account(self, true, tier, up, down):
        """-> (acc per tier, wall, slow_share, app_raw, tier_util)."""
        ft, R = self.ft, self.R
        total = true.sum(dtype=ft)
        acc, rest = [], total
        for r in range(R - 1):
            a = true[tier == r].sum(dtype=ft)
            acc.append(a)
            rest = ft(rest - a)
        acc.append(rest)
        t_lat, times = self.times(acc, up, down)
        rest_max = max(max(times[1:]), ft(1e-12))
        wall = max(t_lat, times[0], rest_max)
        rest_acc = acc[1]
        for r in range(2, R):
            rest_acc = ft(rest_acc + acc[r])
        slow_share = ft(rest_acc / max(ft(acc[0] + rest_acc), ft(1e-9)))
        app_raw = ft(times[0] / max(t_lat, rest_max))
        util = np.asarray(times, ft) / max(max(t_lat, max(times)),
                                           ft(1e-12))
        return acc, ft(wall), slow_share, app_raw, util.astype(ft)


# ---------------------------------------------------------------- policy
def policy(family: str, knobs: dict, n: int, k: int, mach: Machine, ft):
    """The family's reference policy, found by name under policies/."""
    mod = importlib.import_module(
        f"{__package__}.policies.{family.replace('-', '_')}")
    return mod.Policy(knobs, n, k, mach, ft)


# ------------------------------------------------------------------ lane
def run_lane(family, knobs, workload_rows, oracle_rows, u_rows, mach,
             n: int, k: int, T: int, waste_window: int, ft):
    """Replay one lane over T intervals; -> dict of its statistics.

    ``workload_rows[t]`` is the interval's true counts, ``oracle_rows[t]``
    its top-k mask and ``u_rows[t]`` the shared uniform row.
    """
    pol = policy(family, knobs, n, k, mach, ft)
    R = mach.R
    tier = np.full(n, R - 1, np.int64)
    p_at = np.full(n, -(10 ** 9), np.int64)
    d_at = np.full(n, -(10 ** 9), np.int64)
    slow_bw, app_bw = ft(1.0), ft(0.0)
    util = np.zeros(R, ft)
    st = dict(exec_time=ft(0.0), promotions=0, demotions=0, wasteful=0,
              acc_fast=ft(0.0), acc_total=ft(0.0), recall=ft(0.0),
              slow=ft(0.0), hits=ft(0.0), mode=0, promos_max=0)
    for t0 in range(T):
        true = workload_rows[t0]
        if pol.wants_true:
            obs = true
        else:
            obs = pebs_sample(u_rows[t0], true, pol.period(), ft)
        pol.observe(obs)
        up_pages = down_pages = np.zeros(0, np.int64)
        mig_up = mig_down = np.zeros(R - 1, np.int64)
        if pol.fires():
            if pol.tier_native:
                pages, dst = pol.tier_policy(util, slow_bw, app_bw,
                                             mach.caps)
                tier, up_pages, down_pages, mig_up, mig_down = \
                    targeted_migrate(tier, pages, dst, mach.caps)
            else:
                promote, demote = pol.policy(slow_bw, app_bw)
                tier, up_pages, down_pages, mig_up, mig_down = hop_migrate(
                    tier, promote, demote, mach.caps)
        waste = int((t0 - d_at[up_pages] <= waste_window).sum()
                    + (t0 - p_at[down_pages] <= waste_window).sum())
        p_at[up_pages] = t0
        d_at[down_pages] = t0
        acc, wall, slow_share, app_raw, util = mach.account(
            true, tier, mig_up, mig_down)
        acc_slow = acc[1]
        for r in range(2, R):
            acc_slow = ft(acc_slow + acc[r])
        if pol.slow_extra_ns:
            wall = ft(wall + ft(ft(acc_slow * ft(pol.slow_extra_ns))
                                * ft(1e-9)) / mach.mlp)
        recall = ft(ft((tier[oracle_rows[t0]] == 0).sum()) / ft(k))
        hits = ft(acc[0] / max(ft(acc[0] + acc_slow), ft(1e-9)))
        slow_bw, app_bw = slow_share, min(ft(1.0), app_raw)
        st["exec_time"] = ft(st["exec_time"] + wall)
        st["promotions"] += len(up_pages)
        st["demotions"] += len(down_pages)
        st["wasteful"] += waste
        st["acc_fast"] = ft(st["acc_fast"] + acc[0])
        st["acc_total"] = ft(st["acc_total"] + ft(acc[0] + acc_slow))
        st["recall"] = ft(st["recall"] + recall)
        st["slow"] = ft(st["slow"] + slow_share)
        st["hits"] = ft(st["hits"] + hits)
        st["mode"] += pol.mode()
        st["promos_max"] = max(st["promos_max"], len(up_pages))
    return dict(
        exec_time_s=float(st["exec_time"]),
        promotions=st["promotions"], demotions=st["demotions"],
        wasteful=st["wasteful"],
        hot_recall=float(ft(st["recall"] / ft(T))),
        fast_hit_frac=float(ft(st["acc_fast"]
                               / max(st["acc_total"], ft(1e-9)))),
        mean_slow_bw=float(ft(st["slow"] / ft(T))),
        mean_fast_hits=float(ft(st["hits"] / ft(T))),
        mean_mode=float(ft(ft(st["mode"]) / ft(T))),
        max_promotions_interval=st["promos_max"])
