"""Compiled adaptive search engine: ASHA + cross-entropy tuning loops.

``tuning.tune`` historically scored one seeded random grid as a single
lane-batched sweep.  This module turns search itself into a compiled
engine: every *round* of an adaptive strategy — an elimination rung of
successive halving, a redraw generation of cross-entropy — is ONE
``experiment.sweep`` dispatch per policy family, with the round's config
population riding the policy axis and every lane sharing the CRN noise
field, so elimination decisions are paired comparisons (config
differences are never confounded with sampling noise).

Strategies (``run(family, strategy, ...)``):

  * ``"grid"`` — the historical exhaustive scoring of the sampled grid,
    one full-horizon dispatch; the compute reference the adaptive
    strategies are compared against.
  * ``"asha"`` — successive halving: round ``r`` of ``R`` simulates the
    surviving population at horizon ``T_r = T_full * eta**(r - R)``
    (clamped to ``t_min``), keeps the top ``1/eta`` under a stable
    exec-time ranking (a fully-tied rung eliminates nobody — zero
    information means an eta-cut would be draw-order luck), and the
    final round re-simulates survivors at the full horizon — total
    lane-intervals are a geometric fraction of the grid's
    ``budget * T_full`` whenever the rungs carry signal.
  * ``"ce"`` — cross-entropy: each round draws a population from a
    per-knob sampling distribution (categorical over the grid values;
    truncated normal for knobs named in ``CONTINUOUS_KNOBS`` — the ARMS
    alphas leave the grid entirely), scores it at full horizon, and
    refits the distribution from the elite set.  Deterministic under
    ``search_seed`` (one ``default_rng([search_seed, group])`` stream per
    group).

All strategies return a ``SearchResult`` carrying the per-round records
(population, survivors, dispatches, lane-intervals), so strategies are
comparable on *compute spent*, not just best-found:
``SearchResult.lane_intervals`` is the sum over rounds of
``dispatch lanes x horizon`` — the same unit for grid, ASHA and CE.

Lane modes: the search population can be scored per machine
(``machines=[...]``: per-machine elimination with the round dispatch
covering the union population x M machine lanes) or per workload
(``workloads=[...]``, ``T``/``n``) — both return ``{label: SearchResult}``
and both keep one dispatch per round.  ``transfer_matrix`` builds the
companion paper's headline robustness experiment on top of machine-lane
mode: tune per machine, then cross-evaluate every machine's tuned config
on every machine in ONE final sweep and report the A->B
slowdown-vs-native table.

``tuning.tune(strategy=...)`` / ``tune_hemem`` / ``tune_arms`` are thin
views over ``run`` keeping the historical ``(best_cfg, best_res, rows)``
return shape.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.baselines.arms_policy import ARMSSpec
from repro.simulator import experiment, scan_engine
from repro.simulator import machines as machines_mod
from repro.simulator.engine import SimResult

__all__ = [
    "CONTINUOUS_KNOBS", "RoundRecord", "SearchResult", "TransferMatrix",
    "rank_rows", "run", "transfer_matrix",
]

#: family -> knobs the cross-entropy strategy samples continuously (from a
#: truncated normal over the grid's [min, max] range) instead of from the
#: grid's categorical values.  The ARMS alphas are genuinely continuous
#: controller gains; every other family's knobs are integer-ish grid values.
CONTINUOUS_KNOBS = {
    "arms": frozenset({"alpha_s", "alpha_l"}),
    "hybridtier": frozenset({"decay"}),
    "jenga": frozenset({"alpha"}),
    "tierbpf": frozenset({"alpha", "admit_thresh", "thrash_gain",
                          "regret_alpha"}),
}

STRATEGIES = ("grid", "asha", "ce")


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted(cfg.items()))


def rank_rows(rows):
    """Stable exec-time ranking of ``(config, SimResult)`` rows.

    ``sorted`` is stable, so rows with bitwise-equal ``exec_time_s`` keep
    their draw order — rankings are deterministic even when CRN pairing
    makes duplicate configs score identically (asserted in
    tests/test_search.py).
    """
    return sorted(rows, key=lambda cr: cr[1].exec_time_s)


@dataclasses.dataclass
class RoundRecord:
    """One search round: ONE compiled dispatch per policy family."""

    index: int          #: 1-based round number
    horizon: int        #: intervals simulated this round (T_r)
    population: dict    #: group label -> configs entering the round
    survivors: dict     #: group label -> configs kept for the next round
    best_score: dict    #: group label -> best exec_time_s AT THIS HORIZON
    lanes: int          #: lanes of this round's dispatch
    dispatches: int     #: compiled dispatches this round (1 per family)
    lane_intervals: int  #: lanes * horizon — the round's compute spend


@dataclasses.dataclass
class SearchResult:
    """Outcome of one strategy run (per group in machine/workload modes).

    ``rows`` is the final FULL-horizon ranking (stable; see
    ``rank_rows``); ``rounds`` carries the shared per-round records —
    in machine/workload-lane modes every group's result holds the same
    round list, whose dispatch/lane-interval numbers cover the whole
    grouped search (the groups shared each round's dispatch).
    """

    family: str
    strategy: str
    best_config: dict
    best_result: SimResult
    rows: list
    rounds: list
    dispatches: int
    lane_intervals: int

    def curve(self):
        """[(cumulative lane-intervals, best exec_time_s at that round's
        horizon)] — the compute-vs-quality trajectory BENCH_search.json
        records.  Scores of non-final ASHA rounds are short-horizon."""
        pts, cum = [], 0
        for rec in self.rounds:
            cum += rec.lane_intervals
            best = min(rec.best_score.values())
            pts.append((cum, best))
        return pts


class _EvalCtx:
    """Shared evaluation state for one search.

    Resolves the trace / workload specs / machine lanes once, then scores
    a config population at any horizon as ONE compiled dispatch (per
    policy family): the population rides the policy axis of
    ``experiment.sweep``, machine or workload lanes ride their own axes,
    and all lanes share the CRN noise source seeded by ``sim_seed``.
    Short horizons slice the trace prefix (trace mode) or scan fewer
    synthesis intervals of the full-T-resolved workload specs (synth
    mode — the counter-based CRN rows make short runs exact prefixes of
    full runs).
    """

    def __init__(self, make, trace, machine, machines, workloads, k, T, n,
                 sim_seed, mesh=None):
        if machines is not None and workloads is not None:
            raise ValueError("machine-lane and workload-lane search modes "
                             "cannot be combined; pass one of them")
        self.make, self.k = make, k
        self.sim_seed = sim_seed
        self.mesh = mesh
        mach_in = list(machines) if machines is not None else [machine]
        self.machines = [machines_mod.get(m) for m in mach_in]
        self.wl_specs = None
        if workloads is not None:
            if trace is not None:
                raise ValueError("pass either trace or workloads, not both")
            if T is None or n is None:
                raise ValueError("workload-lane tuning needs T and n")
            self.trace = None
            self.wl_specs, names = experiment._resolve_workloads(
                list(workloads), T)
            self.T_full, self.n = int(T), int(n)
            self.group_axis = "workload"
            self.groups = experiment._dedup_labels(names)
        else:
            if trace is None:
                raise ValueError("need a trace or a workloads list")
            self.trace = np.asarray(trace)
            self.T_full, self.n = self.trace.shape
            if machines is not None:
                self.group_axis = "machine"
                self.groups = experiment._dedup_labels(
                    [m.name for m in self.machines])
            else:
                self.group_axis = None
                self.groups = [None]

    def eval(self, configs, horizon: int):
        """Score ``configs`` at ``horizon`` -> (per-group result lists,
        lanes, dispatches, lane-intervals).  One compiled dispatch per
        policy family (asserted by the CI search gate via the dispatch
        delta)."""
        horizon = int(horizon)
        specs = [self.make(**cfg) for cfg in configs]
        with scan_engine.count_dispatches() as ctr:
            if self.group_axis == "workload":
                res = experiment.sweep(
                    specs, workloads=self.wl_specs,
                    machines=[self.machines[0]], k=self.k, T=horizon,
                    n=self.n, sim_seed=self.sim_seed, mesh=self.mesh)
                per_group = [[res.at(policy=b, workload=g)
                              for b in range(len(configs))]
                             for g in range(len(self.groups))]
            else:
                res = experiment.sweep(
                    specs, trace=self.trace[:horizon],
                    machines=self.machines, k=self.k,
                    sim_seed=self.sim_seed, mesh=self.mesh)
                per_group = [[res.at(policy=b, machine=g)
                              for b in range(len(configs))]
                             for g in range(len(self.groups))]
        # ``lanes`` from the dispatch record is LOGICAL (pre-padding), so
        # lane_intervals — and every ASHA/CE compute curve built from it —
        # is identical at any mesh size.
        lanes = ctr.last.get("lanes", len(configs))
        return per_group, lanes, ctr.count, lanes * horizon


def _union(pops):
    """Ordered-dedup union of all groups' populations -> (configs, key->idx).

    Grouped searches evaluate each distinct config once per round even
    when several groups keep it alive; duplicate configs *within* a
    population (allowed, e.g. explicit ``configs`` lists) share a lane.
    """
    union, keyidx = [], {}
    for pop in pops.values():
        for cfg in pop:
            key = _cfg_key(cfg)
            if key not in keyidx:
                keyidx[key] = len(union)
                union.append(cfg)
    return union, keyidx


def _round_rows(pops, per_group, keyidx, groups):
    """Per-group ``(config, SimResult)`` rows in draw order."""
    return {g: [(cfg, per_group[gi][keyidx[_cfg_key(cfg)]])
                for cfg in pops[g]]
            for gi, g in enumerate(groups)}


def _grid(ctx, family, configs):
    """Exhaustive full-horizon scoring — the historical ``tuning.tune``."""
    pops = {g: list(configs) for g in ctx.groups}
    union, keyidx = _union(pops)
    per_group, lanes, disp, li = ctx.eval(union, ctx.T_full)
    rows_g = _round_rows(pops, per_group, keyidx, ctx.groups)
    ranked = {g: rank_rows(rows_g[g]) for g in ctx.groups}
    rec = RoundRecord(1, ctx.T_full, pops,
                      {g: [c for c, _ in ranked[g]] for g in ctx.groups},
                      {g: ranked[g][0][1].exec_time_s for g in ctx.groups},
                      lanes, disp, li)
    return {g: SearchResult(family, "grid", ranked[g][0][0],
                            ranked[g][0][1], ranked[g], [rec], disp, li)
            for g in ctx.groups}


def _n_rounds(n0: int, eta: int, T_full: int, t_min: int,
              rounds) -> int:
    if rounds is not None:
        return max(1, int(rounds))
    if eta <= 1 or n0 <= eta or t_min >= T_full:
        return 1
    return max(1, math.ceil(math.log(n0) / math.log(eta)))


def _asha(ctx, family, configs, eta: int, rounds, t_min: int):
    """Successive halving: geometric horizon ladder, stable elimination.

    Non-final rounds keep the top ``ceil(pop/eta)`` — unless the round's
    ranking is FULLY tied (zero information), in which case nobody is
    eliminated and the ladder continues with the whole population."""
    eta = max(1, int(eta))
    T_full = ctx.T_full
    R = _n_rounds(len(configs), eta, T_full, t_min, rounds)
    pops = {g: list(configs) for g in ctx.groups}
    recs, total_disp, total_li = [], 0, 0
    final_rows = {}
    for r in range(1, R + 1):
        if r == R:
            T_r = T_full
        else:
            T_r = min(T_full, max(int(t_min),
                                  math.ceil(T_full * eta ** (r - R))))
        union, keyidx = _union(pops)
        per_group, lanes, disp, li = ctx.eval(union, T_r)
        total_disp += disp
        total_li += li
        rows_g = _round_rows(pops, per_group, keyidx, ctx.groups)
        surv, best = {}, {}
        for g in ctx.groups:
            ranked = rank_rows(rows_g[g])
            best[g] = ranked[0][1].exec_time_s
            if r < R:
                if ranked[0][1].exec_time_s == ranked[-1][1].exec_time_s:
                    # Zero-information rung: every lane scored
                    # bitwise-identically under the shared CRN (the knobs
                    # are inert at this horizon — e.g. Memtis cooling
                    # periods that first fire late in the trace).  An
                    # eta-cut here would eliminate by draw order alone,
                    # so refuse and carry the whole population; the
                    # search degrades toward exhaustive scoring instead
                    # of returning a draw-lucky config.
                    surv[g] = list(pops[g])
                    continue
                keep = max(1, math.ceil(len(ranked) / eta))
                top = {_cfg_key(c) for c, _ in ranked[:keep]}
                # survivors keep DRAW order (not rank order) so later
                # rounds' tie-breaking stays anchored to the draw.
                surv[g] = [c for c in pops[g] if _cfg_key(c) in top]
            else:
                surv[g] = [c for c, _ in ranked]
                final_rows[g] = ranked
        recs.append(RoundRecord(r, T_r,
                                {g: list(pops[g]) for g in ctx.groups},
                                {g: list(surv[g]) for g in ctx.groups},
                                best, lanes, disp, li))
        pops = surv
    return {g: SearchResult(family, "asha", final_rows[g][0][0],
                            final_rows[g][0][1], final_rows[g], recs,
                            total_disp, total_li)
            for g in ctx.groups}


def _init_dists(space, cont, rng_unused=None):
    dists = {}
    for nm in sorted(space):
        vals = [float(v) for v in space[nm]]
        if nm in cont:
            lo, hi = min(vals), max(vals)
            dists[nm] = dict(kind="cont", lo=lo, hi=hi,
                             mu=float(np.mean(vals)),
                             sigma=max((hi - lo) / 2.0, 1e-6))
        else:
            dists[nm] = dict(kind="disc", vals=list(space[nm]),
                             p=np.full(len(vals), 1.0 / len(vals)))
    return dists


def _ce_draw(rng, dists, space):
    cfg = {}
    for nm in sorted(space):
        d = dists[nm]
        if d["kind"] == "disc":
            cfg[nm] = d["vals"][int(rng.choice(len(d["vals"]), p=d["p"]))]
        else:
            cfg[nm] = float(np.clip(rng.normal(d["mu"], d["sigma"]),
                                    d["lo"], d["hi"]))
    # present knobs in the space's declaration order, like _sample_grid
    return {nm: cfg[nm] for nm in space}


def _ce_refit(dists, elite, smoothing: float):
    for nm, d in dists.items():
        ev = [cfg[nm] for cfg, _ in elite]
        if d["kind"] == "disc":
            freq = np.array([float(sum(1 for v in ev if v == val))
                             for val in d["vals"]]) / len(ev)
            p = (1.0 - smoothing) * d["p"] + smoothing * freq
            d["p"] = p / p.sum()
        else:
            d["mu"] = (1.0 - smoothing) * d["mu"] \
                + smoothing * float(np.mean(ev))
            # sigma floor keeps a sliver of exploration alive so a
            # degenerate elite set cannot freeze the distribution.
            d["sigma"] = max((1.0 - smoothing) * d["sigma"]
                             + smoothing * float(np.std(ev)),
                             1e-3 * (d["hi"] - d["lo"]))


def _ce(ctx, family, space, defaults, budget: int, rounds: int,
        elite_frac: float, smoothing: float, search_seed: int):
    """Cross-entropy over the knob space: redraw from an elite-fit
    distribution each round, all rounds scored at the full horizon."""
    R = max(1, int(rounds))
    pop_n = max(2, math.ceil(budget / R))
    cont = CONTINUOUS_KNOBS.get(family, frozenset())
    dists = {g: _init_dists(space, cont) for g in ctx.groups}
    rngs = {g: np.random.default_rng([int(search_seed), gi])
            for gi, g in enumerate(ctx.groups)}
    seen = {g: {} for g in ctx.groups}   # cfg key -> (cfg, res), draw order
    recs, total_disp, total_li = [], 0, 0
    for r in range(1, R + 1):
        pops = {}
        for g in ctx.groups:
            draws = [dict(defaults)] if (r == 1 and defaults) else []
            while len(draws) < pop_n:
                draws.append(_ce_draw(rngs[g], dists[g], space))
            pops[g] = draws
        union, keyidx = _union(pops)
        per_group, lanes, disp, li = ctx.eval(union, ctx.T_full)
        total_disp += disp
        total_li += li
        rows_g = _round_rows(pops, per_group, keyidx, ctx.groups)
        surv, best = {}, {}
        for g in ctx.groups:
            ranked = rank_rows(rows_g[g])
            best[g] = ranked[0][1].exec_time_s
            elite = ranked[:max(1, int(len(ranked) * elite_frac))]
            surv[g] = [c for c, _ in elite]
            _ce_refit(dists[g], elite, smoothing)
            for cfg, res in rows_g[g]:
                seen[g].setdefault(_cfg_key(cfg), (cfg, res))
        recs.append(RoundRecord(r, ctx.T_full, pops, surv, best, lanes,
                                disp, li))
    out = {}
    for g in ctx.groups:
        # every round ran at the full horizon under the same CRN noise, so
        # rows from different rounds are directly comparable (and repeat
        # draws scored identically — first draw kept).
        rows = rank_rows(list(seen[g].values()))
        out[g] = SearchResult(family, "ce", rows[0][0], rows[0][1], rows,
                              recs, total_disp, total_li)
    return out


def run(family: str, strategy: str = "asha", *, trace=None,
        machine="pmem-large", machines=None, workloads=None, k: int,
        budget: int = 24, eta: int = 3, rounds=None, t_min: int = 16,
        ce_rounds: int = 4, elite_frac: float = 0.25,
        ce_smoothing: float = 0.7, search_seed: int = 0, sim_seed: int = 0,
        space: dict | None = None, defaults: dict | None = None,
        base_cfg=None, configs=None, T: int | None = None,
        n: int | None = None, mesh=None):
    """Run one search strategy for one policy family.

    Modes mirror ``tuning.tune``: trace + single ``machine`` returns ONE
    ``SearchResult``; ``machines=[...]`` (machine-lane mode) or
    ``workloads=[...]`` + ``T``/``n`` (workload-lane mode) return
    ``{label: SearchResult}`` with per-group searches sharing each
    round's single dispatch.  ``configs`` overrides the seeded grid draw
    (grid/asha initial population; CE always redraws from its fitted
    distribution, seeded by ``search_seed``).

    ``budget`` is the population size for grid/asha and the total draw
    count across CE rounds (``ce_rounds`` populations of
    ``ceil(budget / ce_rounds)``); ``eta``/``rounds``/``t_min`` shape the
    ASHA ladder (``eta=1`` collapses to one full-horizon round — exactly
    grid search, bitwise).

    ``mesh`` shards each round's lane batch over devices via the sweep
    fabric (experiment.sweep) — results, rankings and lane-interval
    compute curves are bitwise-identical at any mesh size.
    """
    from repro.simulator import tuning  # late import: tuning wraps run()
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}; "
                         f"known: {list(STRATEGIES)}")
    if family not in tuning.FAMILIES:
        raise ValueError(f"unknown family {family!r}; "
                         f"known: {sorted(tuning.FAMILIES)}")
    make, fam_space, fam_defaults = tuning.FAMILIES[family]
    space = dict(space if space is not None else fam_space)
    defaults = dict(defaults if defaults is not None else fam_defaults)
    if base_cfg is not None:
        if family != "arms":
            raise ValueError("base_cfg is an ARMS-only knob")
        make = lambda **cfg: ARMSSpec.make(cfg, base_cfg=base_cfg)  # noqa: E731
    if configs is None:
        configs = tuning._sample_grid(space, defaults, budget, search_seed)
    else:
        configs = [dict(c) for c in configs]
    ctx = _EvalCtx(make, trace, machine, machines, workloads, k, T, n,
                   sim_seed, mesh=mesh)
    if strategy == "grid":
        out = _grid(ctx, family, configs)
    elif strategy == "asha":
        out = _asha(ctx, family, configs, eta, rounds, t_min)
    else:
        out = _ce(ctx, family, space, defaults, budget,
                  ce_rounds if rounds is None else rounds, elite_frac,
                  ce_smoothing, search_seed)
    if ctx.group_axis is None:
        return out[None]
    return out


# ------------------------------------------------- machine-transfer matrix
@dataclasses.dataclass
class TransferMatrix:
    """"Tuned on machine A, deployed on machine B" robustness table.

    ``exec_time[a, b]`` is the exec time of the config tuned natively on
    machine ``a`` when deployed on machine ``b``;
    ``slowdown[a, b] = exec_time[a, b] / exec_time[b, b]`` (1.0 on the
    diagonal; > 1 measures what deploying a foreign tuning costs vs
    re-tuning natively — the companion tuning paper's headline).
    """

    family: str
    machines: list
    tuned: dict                 #: machine label -> natively tuned config
    exec_time: np.ndarray       #: [A, B] deployed exec times (seconds)
    slowdown: np.ndarray        #: [A, B] vs the native-tuned diagonal
    search: dict                #: machine label -> SearchResult

    def rows(self):
        """JSON-friendly per-source rows for benches/tables."""
        out = []
        for a, src in enumerate(self.machines):
            out.append(dict(
                tuned_on=src, config=self.tuned[src],
                exec_time_s={b: round(float(self.exec_time[a, bi]), 6)
                             for bi, b in enumerate(self.machines)},
                slowdown={b: round(float(self.slowdown[a, bi]), 4)
                          for bi, b in enumerate(self.machines)}))
        return out


def transfer_matrix(family: str, trace, machines, k: int,
                    budget: int = 24, strategy: str = "asha",
                    search_seed: int = 0, sim_seed: int = 0,
                    **search_kw) -> TransferMatrix:
    """Tune per machine, then cross-evaluate tuned configs everywhere.

    Phase 1 is ONE machine-lane search (per-machine elimination, each
    round a single union-population x M-machine dispatch); phase 2
    re-scores the M tuned configs on all M machines in ONE final
    ``experiment.sweep`` dispatch (config axis x machine axis, shared
    CRN), so ``exec_time[b, b]`` reproduces the native search score
    bitwise and off-diagonal cells are paired with it.
    """
    machines = list(machines)
    if len(machines) < 2:
        raise ValueError("a transfer matrix needs >= 2 machines")
    per = run(family, strategy, trace=trace, machines=machines, k=k,
              budget=budget, search_seed=search_seed, sim_seed=sim_seed,
              **search_kw)
    labels = list(per)
    from repro.simulator import tuning  # late import: tuning wraps run()
    make = tuning.FAMILIES[family][0]
    specs = [make(**per[g].best_config) for g in labels]
    res = experiment.sweep(specs, trace=np.asarray(trace),
                           machines=machines, k=k, sim_seed=sim_seed)
    M = len(labels)
    exec_time = np.array([[res.at(policy=a, machine=b).exec_time_s
                           for b in range(M)] for a in range(M)])
    slowdown = exec_time / np.diag(exec_time)[None, :]
    return TransferMatrix(family, labels,
                          {g: per[g].best_config for g in labels},
                          exec_time, slowdown, per)
