"""Search-engine invariants: ASHA/CE round structure, CRN-paired
elimination, deterministic ranking, and the machine-transfer matrix.

The load-bearing properties: (1) every search round is ONE compiled
dispatch per policy family (asserted via ``scan_engine.count_dispatches``);
(2) ASHA with ``eta=1`` degenerates to exhaustive grid search
BITWISE — same configs, same scores, same ranking — because both paths
evaluate the same population in the same lanes under the same CRN field;
(3) survivors are always drawn from the previous round's population;
(4) rankings are stable (equal ``exec_time_s`` keeps draw order) and CE's
redraw stream is a pure function of ``search_seed``.
"""
import numpy as np
import pytest

from repro.simulator import scan_engine, search, tuning, workloads
from repro.simulator.engine import SimResult
from repro.simulator.machine import PMEM_LARGE

T, N, K = 80, 256, 32


def _trace(wl="gups"):
    return workloads.make(wl, T=T, n=N)


def _res(t):
    return SimResult(name="x", exec_time_s=t, promotions=0, demotions=0,
                     wasteful=0, hot_recall=0.0, fast_hit_frac=0.0)


# ------------------------------------------------------------ _sample_grid
class TestSampleGrid:
    def test_budget_respected_with_default_inserted(self):
        """The draw returns AT MOST ``budget`` configs even when the
        default config wasn't among the sampled indices (earlier
        revisions returned budget + 1)."""
        for budget in (1, 3, 6, 24):
            cfgs = tuning.sample_configs(budget)
            assert len(cfgs) <= budget
            assert dict(tuning.HEMEM_DEFAULTS) in cfgs

    def test_huge_space_not_materialized(self):
        """A grid far too large to materialize samples in O(budget)."""
        space = {f"k{i}": list(range(32)) for i in range(8)}  # 32**8 ~ 1e12
        defaults = {f"k{i}": 0 for i in range(8)}
        cfgs = tuning._sample_grid(space, defaults, 8, seed=1)
        assert len(cfgs) == 8
        keys = [tuple(sorted(c.items())) for c in cfgs]
        assert len(set(keys)) == len(keys)  # draws are unique
        for c in cfgs:
            assert list(c) == list(space)   # knob order preserved
            assert all(c[nm] in space[nm] for nm in space)

    def test_seeded_draw_deterministic(self):
        assert tuning.sample_configs(8, seed=5) == \
            tuning.sample_configs(8, seed=5)
        a = tuning.sample_configs(12, seed=0)
        b = tuning.sample_configs(12, seed=1)
        assert a != b

    def test_decode_matches_product_order(self):
        """Mixed-radix decode agrees with the itertools.product C order
        the materializing implementation indexed into."""
        import itertools
        space = dict(a=[1, 2, 3], b=[10, 20], c=[0.5, 0.7])
        grid = list(itertools.product(*space.values()))
        keys, sizes = list(space), [len(v) for v in space.values()]
        for i in range(len(grid)):
            assert tuning._decode_grid_index(space, keys, sizes, i) == \
                dict(zip(keys, grid[i]))


# ------------------------------------------------------------------- ASHA
class TestASHA:
    def test_eta1_reproduces_grid_bitwise(self):
        """budget >= population and eta=1 collapse ASHA to ONE full-horizon
        round — exactly grid search, bitwise, under the shared CRN."""
        trace = _trace()
        kw = dict(trace=trace, k=K, budget=6, search_seed=2, sim_seed=9)
        a = search.run("hemem", "asha", eta=1, **kw)
        g = search.run("hemem", "grid", **kw)
        assert [c for c, _ in a.rows] == [c for c, _ in g.rows]
        for (_, ra), (_, rg) in zip(a.rows, g.rows):
            assert ra.exec_time_s == rg.exec_time_s  # bitwise
        assert a.best_config == g.best_config
        assert len(a.rounds) == 1
        assert a.lane_intervals == g.lane_intervals

    def test_survivors_subset_of_population(self):
        trace = _trace("silo-tpcc")
        sr = search.run("hemem", "asha", trace=trace, k=K, budget=9, eta=3,
                        search_seed=1, sim_seed=0)
        assert len(sr.rounds) >= 2
        for rec in sr.rounds:
            pop = {search._cfg_key(c) for c in rec.population[None]}
            surv = [search._cfg_key(c) for c in rec.survivors[None]]
            assert set(surv) <= pop
        for prev, nxt in zip(sr.rounds, sr.rounds[1:]):
            assert nxt.population[None] == prev.survivors[None]
            assert len(nxt.population[None]) < len(prev.population[None])
        # final round runs at the full horizon; earlier rounds are shorter
        assert sr.rounds[-1].horizon == trace.shape[0]
        assert all(r.horizon < trace.shape[0] for r in sr.rounds[:-1])

    def test_zero_information_rung_eliminates_nobody(self):
        """When every lane of a rung scores bitwise-identically (knobs
        inert at that horizon — Memtis cooling periods never fire in a
        short low-sample-rate trace), an eta-cut would eliminate by draw
        order alone; ASHA must refuse and carry the whole population to
        the next rung."""
        trace = _trace()  # gups, T=80, n=256: no memtis cooling fires
        sr = search.run("memtis", "asha", trace=trace, k=K, budget=9,
                        eta=3, search_seed=1, sim_seed=0)
        assert len(sr.rounds) >= 2
        for rec in sr.rounds[:-1]:
            assert rec.survivors[None] == rec.population[None]
        # the full population reached the full-horizon round, so the
        # result ranks every config — exactly the exhaustive grid's rows.
        g = search.run("memtis", "grid", trace=trace, k=K, budget=9,
                       search_seed=1, sim_seed=0)
        assert [c for c, _ in sr.rows] == [c for c, _ in g.rows]
        assert sr.lane_intervals > g.lane_intervals  # paid for the rungs

    def test_one_dispatch_per_round(self):
        sr = search.run("hemem", "asha", trace=_trace(), k=K, budget=9,
                        eta=3, search_seed=0, sim_seed=0)
        assert all(rec.dispatches == 1 for rec in sr.rounds)
        assert sr.dispatches == len(sr.rounds)
        assert sr.lane_intervals == sum(r.lane_intervals for r in sr.rounds)
        assert sr.lane_intervals == sum(r.lanes * r.horizon
                                        for r in sr.rounds)

    def test_arms_rows_match_recorded(self):
        """ARMS scored through the sweep's CRN field: the rows are those
        ARMS's former precomputed-observation route gave, bit for bit
        (recorded from it; T=80, n=256, k=32, btree, which switches
        between ARMS's modes and so between its sampling periods)."""
        sr = search.run("arms", trace=_trace("btree"), k=K, budget=6,
                        search_seed=0, sim_seed=0)
        assert [(r.horizon, len(r.population[None])) for r in sr.rounds] \
            == [(27, 6), (80, 2)]
        got = [(c, (r.exec_time_s.hex(), r.promotions, r.demotions,
                    r.wasteful, r.hot_recall.hex(), r.fast_hit_frac.hex(),
                    r.mean_slow_bw.hex(), r.mean_fast_hits.hex(),
                    r.mean_mode.hex(), r.max_promotions_interval))
               for c, r in sr.rows]
        assert got == [
            (dict(alpha_s=0.7, alpha_l=0.1, noise_z=0.25, pht_lambda=0.1),
             ("0x1.c21da20000000p+2", 65, 33, 4, "0x1.90ccce0000000p-1",
              "0x1.f7b11c0000000p-2", "0x1.0427740000000p-1",
              "0x1.f7b10e0000000p-2", "0x1.2666660000000p-2", 30)),
            (dict(alpha_s=0.6, alpha_l=0.05, noise_z=0.5, pht_lambda=0.2),
             ("0x1.c2638a0000000p+2", 67, 35, 8, "0x1.93ccce0000000p-1",
              "0x1.f78df00000000p-2", "0x1.04390c0000000p-1",
              "0x1.f78de00000000p-2", "0x1.ccccce0000000p-2", 30)),
        ]

    def test_machine_lane_mode(self):
        """machines=[...]: per-machine elimination, each round one
        union-population x M dispatch; every machine gets its own result."""
        machines = ["pmem-large", "numa"]
        out = search.run("hemem", "asha", trace=_trace(), machines=machines,
                         k=K, budget=6, eta=3, search_seed=0, sim_seed=0)
        # group labels are the RESOLVED spec names (same scheme as
        # experiment.sweep's machine axis: "numa" -> spec named "NUMA")
        assert sorted(nm.lower() for nm in out) == sorted(machines)
        a, b = out["pmem-large"], out["NUMA"]
        assert a.rounds is b.rounds          # shared round records
        rec = a.rounds[0]
        union = {search._cfg_key(c)
                 for g in rec.population for c in rec.population[g]}
        assert rec.lanes == len(union) * len(machines)
        assert rec.dispatches == 1


# ---------------------------------------------------------- cross-entropy
class TestCE:
    def test_deterministic_under_search_seed(self):
        trace = _trace()
        kw = dict(trace=trace, k=K, budget=8, ce_rounds=2, sim_seed=3)
        a = search.run("hemem", "ce", search_seed=7, **kw)
        b = search.run("hemem", "ce", search_seed=7, **kw)
        assert [c for c, _ in a.rows] == [c for c, _ in b.rows]
        for (_, ra), (_, rb) in zip(a.rows, b.rows):
            assert ra.exec_time_s == rb.exec_time_s
        for ra, rb in zip(a.rounds, b.rounds):
            assert ra.population == rb.population
            assert ra.survivors == rb.survivors
        c = search.run("hemem", "ce", search_seed=8, **kw)
        assert [cf for cf, _ in a.rows] != [cf for cf, _ in c.rows]

    def test_one_dispatch_per_round_and_elite_shrinks(self):
        sr = search.run("hemem", "ce", trace=_trace(), k=K, budget=12,
                        ce_rounds=3, elite_frac=0.25, search_seed=0,
                        sim_seed=0)
        assert len(sr.rounds) == 3
        assert all(rec.dispatches == 1 for rec in sr.rounds)
        for rec in sr.rounds:
            assert len(rec.survivors[None]) <= len(rec.population[None])
            # CE scores every round at the full horizon
            assert rec.horizon == T
        # round 1 tries the published defaults first
        assert sr.rounds[0].population[None][0] == tuning.HEMEM_DEFAULTS

    def test_continuous_arms_alphas_leave_the_grid(self):
        """The CE continuous path samples ARMS alphas from a truncated
        normal — off-grid values — each round one CRN-paired sweep
        dispatch (alphas are batched spec leaves)."""
        sr = search.run("arms", "ce", trace=_trace(), k=K, budget=10,
                        ce_rounds=2, search_seed=0, sim_seed=0)
        assert all(rec.dispatches == 1 for rec in sr.rounds)
        assert scan_engine.last_dispatch["sampling"] == "crn"
        drawn = [c for c, _ in sr.rows if c != tuning.ARMS_DEFAULTS]
        assert any(c["alpha_s"] not in tuning.ARMS_SPACE["alpha_s"]
                   for c in drawn)
        lo, hi = min(tuning.ARMS_SPACE["alpha_s"]), \
            max(tuning.ARMS_SPACE["alpha_s"])
        assert all(lo <= c["alpha_s"] <= hi for c in drawn)
        # discrete knobs stay on the grid
        assert all(c["noise_z"] in tuning.ARMS_SPACE["noise_z"]
                   for c in drawn)


# ------------------------------------------------------- ranking stability
class TestRanking:
    def test_equal_scores_keep_draw_order(self):
        rows = [({"a": 1}, _res(2.0)), ({"a": 2}, _res(1.0)),
                ({"a": 3}, _res(1.0)), ({"a": 4}, _res(1.0))]
        ranked = search.rank_rows(rows)
        assert [c["a"] for c, _ in ranked] == [2, 3, 4, 1]

    def test_duplicate_configs_share_a_lane_and_stay_adjacent(self):
        """Explicit duplicate configs are evaluated once (one lane) and —
        scoring identically under CRN — keep draw order in the ranking."""
        cfg_a = dict(tuning.HEMEM_DEFAULTS)
        cfg_b = dict(cfg_a, hot_threshold=1)
        with scan_engine.count_dispatches() as ctr:
            sr = search.run("hemem", "grid", trace=_trace(), k=K,
                            configs=[cfg_a, cfg_b, cfg_a], sim_seed=0)
        assert ctr.count == 1
        assert ctr.last["lanes"] == 2  # deduped population, not 3
        assert len(sr.rows) == 3
        dup = [i for i, (c, _) in enumerate(sr.rows) if c == cfg_a]
        assert dup == [dup[0], dup[0] + 1]  # adjacent, draw order
        r0, r1 = sr.rows[dup[0]][1], sr.rows[dup[1]][1]
        assert r0.exec_time_s == r1.exec_time_s


# ------------------------------------------------------- tuning thin views
class TestTuneViews:
    def test_strategy_views_keep_legacy_shape(self):
        trace = _trace()
        for strategy in ("grid", "asha", "ce"):
            best_cfg, best_res, rows = tuning.tune_hemem(
                trace, PMEM_LARGE, K, budget=6, strategy=strategy)
            assert set(best_cfg) == set(tuning.SPACE)
            assert best_res.exec_time_s == min(r.exec_time_s
                                               for _, r in rows)

    def test_unknown_strategy_rejected(self):
        with pytest.raises(ValueError):
            tuning.tune("hemem", _trace(), PMEM_LARGE, K, budget=2,
                        strategy="bayes")

    def test_machines_mode_returns_per_machine_tuples(self):
        out = tuning.tune("hemem", _trace(), None, K, budget=4,
                          machines=["pmem-large", "numa"])
        assert sorted(out) == ["NUMA", "pmem-large"]
        for best_cfg, best_res, rows in out.values():
            assert set(best_cfg) == set(tuning.SPACE)
            assert len(rows) <= 4

    def test_tune_arms_asha_keeps_pre_path(self):
        """ARMS tunes through the sweep like every family: one CRN-paired
        dispatch per round (it once had a sampling path of its own)."""
        with scan_engine.count_dispatches() as ctr:
            best_cfg, best_res, rows = tuning.tune_arms(
                _trace(), PMEM_LARGE, K, budget=6, strategy="asha")
        assert ctr.count >= 2
        assert all(r["sampling"] == "crn" for r in ctr.records)
        assert set(best_cfg) == set(tuning.ARMS_SPACE)
        assert best_res.exec_time_s == min(r.exec_time_s for _, r in rows)

    def test_workload_lane_asha(self):
        out = tuning.tune("hemem", None, PMEM_LARGE, K, budget=6,
                          workloads=["gups", "silo-tpcc"], T=T, n=N,
                          strategy="asha")
        assert sorted(out) == ["gups", "silo-tpcc"]
        # the final round's dispatch covers W x survivors lanes
        d = scan_engine.last_dispatch
        assert d["synth"] is True and d["workloads"] == 2


# -------------------------------------------------------- transfer matrix
class TestTransferMatrix:
    def test_native_tuning_is_optimal_under_shared_crn(self):
        """With grid strategy the matrix is exact: phase 2 re-scores every
        tuned config under the SAME CRN field phase 1 ranked them with, so
        the native config is optimal among the tuned set — diagonal 1.0,
        off-diagonal slowdown >= 1.0."""
        tm = search.transfer_matrix(
            "hemem", _trace(), ["pmem-large", "numa", "cxl-1hop"], K,
            budget=5, strategy="grid")
        assert tm.slowdown.shape == (3, 3)
        assert np.allclose(np.diag(tm.slowdown), 1.0)
        assert (tm.slowdown >= 1.0 - 1e-12).all()
        rows = tm.rows()
        assert [r["tuned_on"] for r in rows] == tm.machines
        assert all(r["slowdown"][r["tuned_on"]] == 1.0 for r in rows)

    def test_needs_two_machines(self):
        with pytest.raises(ValueError):
            search.transfer_matrix("hemem", _trace(), ["numa"], K)
