"""Tier-native protocol: shim equivalence, targeted executor, new families.

The PR-8 contract extends the binary promote/demote protocol to
tier-targeted migrations.  Three guarantees anchor it:

  * SHIM EQUIVALENCE — every binary policy routed through the protocol's
    ``tier_policy`` shim and the tier-targeted executor (a one-family
    ``dispatch="union"`` sweep) is BITWISE equal (counts, exec time,
    timelines) to its own hop-chain path (``dispatch="grouped"``) under
    CRN, on 2- and 3-tier machines alike;
  * EXECUTOR EQUIVALENCE — the compiled targeted executor
    (``simjax.apply_targeted_migrations``) matches the sequential numpy
    reference (``engine.apply_targeted_migrations_np``) on random plans;
  * FAMILY EQUIVALENCE — the tier-native families (HybridTier / Jenga /
    TierBPF) produce exactly the same migration counts under both engines
    with shared CRN noise.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.arms_policy import ARMSSpec
from repro.baselines.hemem import HeMemSpec
from repro.baselines.hybridtier import HybridTierPolicy, HybridTierSpec
from repro.baselines.jenga import JengaPolicy, JengaSpec
from repro.baselines.memtis import MemtisSpec
from repro.baselines.protocol import (pair_limit, rank_desc, rank_partition,
                                      tier_plan)
from repro.baselines.static import AllSlowSpec, OracleSpec
from repro.baselines.tierbpf import TierBPFPolicy, TierBPFSpec
from repro.baselines.tpp import TPPSpec
from repro.core import scheduler
from repro.simulator import (experiment, machine_spec, machines, scan_engine,
                             simjax, workloads)
from repro.simulator.engine import apply_targeted_migrations_np, run
from repro.simulator.sampling import uniform_field

T, N, K = 96, 256, 32

BINARY_FAMILIES = [
    ("arms", lambda: ARMSSpec.make()),
    ("hemem", lambda: HeMemSpec.make()),
    ("memtis", lambda: MemtisSpec.make()),
    ("tpp", lambda: TPPSpec.make()),
    ("all-slow", AllSlowSpec),
    ("oracle", OracleSpec),
]
TIER_FAMILIES = [
    (HybridTierPolicy, lambda: HybridTierSpec.make()),
    (JengaPolicy, lambda: JengaSpec.make()),
    (TierBPFPolicy, lambda: TierBPFSpec.make()),
]
MACHS = ["pmem-large", "dram-cxl-pmem"]


def _same_counts(a, b):
    assert a.promotions == b.promotions
    assert a.demotions == b.demotions
    assert a.wasteful == b.wasteful


class TestShimEquivalence:
    """Binary specs through the tier-targeted executor == hop-chain path:
    a one-family union dispatch runs the family through the base shim,
    the grouped dispatch through its own route."""

    @pytest.mark.parametrize("mach", MACHS)
    @pytest.mark.parametrize("fam,mk", BINARY_FAMILIES)
    def test_bitwise_equal_under_crn(self, fam, mk, mach):
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=123)
        kw = dict(trace=trace, machines=mach, k=K, sample_u=u,
                  timelines=True)
        base = experiment.sweep([mk()], dispatch="grouped", **kw).grid[0]
        shim = experiment.sweep([mk()], dispatch="union", **kw).grid[0]
        _same_counts(base, shim)
        assert base.exec_time_s == shim.exec_time_s          # bitwise
        assert base.hot_recall == shim.hot_recall
        np.testing.assert_array_equal(base.timeline_promotions,
                                      shim.timeline_promotions)
        np.testing.assert_array_equal(base.timeline_slow_bw,
                                      shim.timeline_slow_bw)

    def test_bitwise_equal_on_unfused_path(self):
        # the shim routes around the fused interval kernel as well.
        trace = workloads.make("silo-tpcc", T=T, n=N)
        u = uniform_field(T, N, seed=7)
        kw = dict(trace=trace, machines="dram-cxl-pmem", k=K, sample_u=u,
                  use_interval_kernel=False)
        base = experiment.sweep([HeMemSpec.make()], dispatch="grouped",
                                **kw).grid[0]
        shim = experiment.sweep([HeMemSpec.make()], dispatch="union",
                                **kw).grid[0]
        _same_counts(base, shim)
        assert base.exec_time_s == shim.exec_time_s


class TestTargetedExecutor:
    """Compiled targeted executor vs the sequential numpy reference."""

    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("R", [2, 3, 4])
    def test_matches_numpy_on_random_plans(self, seed, R):
        rng = np.random.default_rng(seed)
        n = 64
        caps_l = [8] + [int(rng.integers(4, 16)) for _ in range(R - 2)] + [n]
        caps = jnp.asarray(caps_l, jnp.int32)
        tier = rng.integers(0, R, size=n).astype(np.int64)
        # keep starting occupancy feasible for the non-bottom tiers
        for r in range(R - 1):
            over = np.flatnonzero(tier == r)[caps_l[r]:]
            tier[over] = R - 1
        m = 24
        pages = rng.choice(n, size=m, replace=False).astype(np.int64)
        dst = rng.integers(-2, R, size=m).astype(np.int64)

        tier_np = tier.copy()
        up_np, down_np, mu_np, md_np = apply_targeted_migrations_np(
            tier_np, pages, dst, caps_l)

        pad = np.concatenate([pages, -np.ones(5, np.int64)])
        dpad = np.concatenate([dst, np.zeros(5, np.int64)])
        tier_j, up_exec, down_exec, mu_j, md_j = (
            simjax.apply_targeted_migrations(
                jnp.asarray(tier, jnp.int32), jnp.asarray(pad, jnp.int32),
                jnp.asarray(dpad, jnp.int32), caps))
        np.testing.assert_array_equal(np.asarray(tier_j), tier_np)
        np.testing.assert_array_equal(np.asarray(mu_j), mu_np)
        np.testing.assert_array_equal(np.asarray(md_j), md_np)
        assert int(up_exec.sum()) == len(up_np)
        assert int(down_exec.sum()) == len(down_np)

    def test_sentinel_entries_are_inert(self):
        caps = jnp.asarray([2, 8], jnp.int32)
        tier = jnp.asarray([1, 1, 1, 0, 0, 1, 1, 1], jnp.int32)
        pages = jnp.asarray([-1, -1, -1], jnp.int32)
        dst = jnp.zeros(3, jnp.int32)
        t2, up, down, mu, md = simjax.apply_targeted_migrations(
            tier, pages, dst, caps)
        np.testing.assert_array_equal(np.asarray(t2), np.asarray(tier))
        assert int(up.sum()) == int(down.sum()) == 0
        assert int(mu.sum()) == int(md.sum()) == 0


class TestTierPlan:
    """Feasibility of the shared planner every tier-native family uses."""

    @pytest.mark.parametrize("seed", range(3))
    def test_plan_respects_caps_and_budgets(self, seed):
        rng = np.random.default_rng(seed)
        n, R = 96, 3
        caps = jnp.asarray([12, 20, n], jnp.int32)
        cur = np.full(n, R - 1, np.int64)
        cur[rng.choice(n, 10, replace=False)] = 0
        cur[rng.choice(np.flatnonzero(cur == 2), 15, replace=False)] = 1
        score = jnp.asarray(rng.random(n), jnp.float32)
        target = rank_partition(rank_desc(score), caps)
        budgets = jnp.asarray([rng.integers(1, 6) for _ in range(R - 1)],
                              jnp.int32)
        pages, dst, new_cur = tier_plan(
            score, jnp.asarray(cur, jnp.int32), target, caps, budgets,
            32, 32)
        new_cur = np.asarray(new_cur)
        occ = np.bincount(new_cur, minlength=R)
        assert (occ[:-1] <= np.asarray(caps)[:-1]).all()
        moved = np.flatnonzero(new_cur != cur)
        # up-moves spend the budget the down-moves left over, so TOTAL
        # crossings per pair stay within the pair's budget.
        for j in range(R - 1):
            crossing = sum(1 for p in moved
                           if min(cur[p], new_cur[p]) <= j
                           < max(cur[p], new_cur[p]))
            assert crossing <= int(budgets[j])

    def test_pair_limit_counts_crossings(self):
        lo = jnp.asarray([0, 0, 1, 0], jnp.int32)
        hi = jnp.asarray([2, 1, 2, 2], jnp.int32)
        valid = jnp.asarray([True, True, True, True])
        ok = pair_limit(lo, hi, valid, jnp.asarray([2, 1], jnp.int32))
        # pair 1 (tier1<->tier2) is crossed by entries 0, 2, 3 in order;
        # budget 1 keeps only entry 0.
        np.testing.assert_array_equal(np.asarray(ok),
                                      [True, True, False, False])


class TestTierNativeFamilies:
    """HybridTier / Jenga / TierBPF: scan engine == numpy engine (CRN)."""

    @pytest.mark.parametrize("mach", MACHS)
    @pytest.mark.parametrize("pol,mk", TIER_FAMILIES)
    def test_cross_engine_equivalence(self, pol, mk, mach):
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=123)
        m = machines.get(mach)
        ref = run(pol(), trace, m, K, sample_u=u)
        out = experiment.sweep([mk()], trace=trace, machines=mach, k=K,
                               sample_u=u, timelines=True).grid[0]
        _same_counts(ref, out)
        # exec time and recall accumulate in f32 on device, f64 on host.
        np.testing.assert_allclose(out.exec_time_s, ref.exec_time_s,
                                   rtol=1e-5)
        np.testing.assert_allclose(out.hot_recall, ref.hot_recall,
                                   rtol=1e-5)
        np.testing.assert_array_equal(out.timeline_promotions,
                                      ref.timeline_promotions)

    def test_families_migrate_on_hot_workloads(self):
        # regression: the defaults must actually fire at the named
        # workloads' observed-count magnitudes (~30-60 samples/interval
        # for hot pages), not sit inert below their thresholds.
        trace = workloads.make("gups", T=T, n=N)
        for _, mk in TIER_FAMILIES:
            out = experiment.sweep([mk()], trace=trace, machines="pmem-large",
                                   k=K, seeds=[0, 1]).at(seed=0)
            assert out.promotions > 0


class TestPairBudgetsEdges:
    """scheduler.pair_budgets at the contract's edges (satellite c)."""

    def test_saturated_util_keeps_floor(self):
        # raw utilization can exceed 1 (overcommitted interval); the
        # budget must clamp to the floor of 1, never 0 or negative.
        u = jnp.asarray([3.2, 1.0, 0.5], jnp.float32)
        b = scheduler.pair_budgets(u, 64)
        np.testing.assert_array_equal(np.asarray(b), [1, 1])

    def test_bs_max_one(self):
        u = jnp.asarray([0.0, 0.0], jnp.float32)
        b = scheduler.pair_budgets(u, 1)
        np.testing.assert_array_equal(np.asarray(b), [1])

    def test_zero_bandwidth_padded_tier_gets_full_budget(self):
        # a 2-tier preset padded to 3 tiers for a mixed-depth sweep: the
        # neutral pad tier carries no traffic, so its utilization is 0
        # and the adjacent pair budget stays wide open (bounded by the
        # busier side of each pair).
        base = machines.get("pmem-large")
        spec, _caps = machine_spec.pad_tiers(
            base, machine_spec.resolved_caps(base, N, K), 3)
        util = machine_spec.tier_utilization_host(
            spec, np.array([5e6, 0.0, 3e7]),
            np.array([10.0, 0.0]), np.array([8.0, 0.0]))
        assert util[1] == 0.0
        b = np.asarray(scheduler.pair_budgets(
            jnp.asarray(util, jnp.float32), 32))
        assert b.shape == (2,)
        assert (1 <= b).all() and (b <= 32).all()


class TestSweepIntegration:
    """Tier-native and binary families mix in one sweep: ONE union
    dispatch by default (one per family on the forced grouped path),
    machine labels carried through for spec objects."""

    def test_mixed_family_dispatch_counts(self):
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=123)
        with scan_engine.count_dispatches() as ctr:
            res = experiment.sweep(["hemem", "jenga"], trace=trace,
                                   machines=["pmem-large", "dram-cxl-pmem"],
                                   k=K, sample_u=u)
        # default dispatch="auto": the union fabric fuses both families.
        assert ctr.count == 1
        assert ctr.last["dispatch"] == "union"
        assert ctr.last["families"] == 2
        with scan_engine.count_dispatches() as ctr:
            grp = experiment.sweep(["hemem", "jenga"], trace=trace,
                                   machines=["pmem-large", "dram-cxl-pmem"],
                                   k=K, sample_u=u, dispatch="grouped")
        assert ctr.count == 2
        assert res.axes["policy"] == ["hemem", "jenga"]
        solo = experiment.sweep([JengaSpec.make()], trace=trace,
                                machines="dram-cxl-pmem", k=K,
                                sample_u=u).grid[0]
        cell = res.at(policy="jenga", machine="dram-cxl-pmem")
        assert cell.promotions == solo.promotions
        assert cell.exec_time_s == solo.exec_time_s
        gcell = grp.at(policy="jenga", machine="dram-cxl-pmem")
        assert gcell.exec_time_s == cell.exec_time_s

    def test_machine_spec_labels_not_anonymous(self):
        specs = [machines.get("pmem-large"), machines.get("cxl-1hop")]
        trace = workloads.make("gups", T=T, n=N)
        res = experiment.sweep(["oracle"], trace=trace, machines=specs,
                               k=K)
        assert res.axes["machine"] == ["pmem-large", "cxl-1hop"]

    def test_dedup_labels_suffixes_duplicates_only(self):
        out = experiment._dedup_labels(["a", "b", "a", "c"])
        assert out == ["a#0", "b", "a#2", "c"]

    def test_anonymous_machine_specs_get_positional_labels(self):
        import dataclasses

        sp = machines.get("pmem-large")
        anon = dataclasses.replace(sp, name="")
        labels = experiment._machine_labels([anon, "numa"], [anon, sp])
        assert labels == ["m0", "numa"]


class TestSearchRouting:
    """tuning/search route the tier-native families (satellite f)."""

    def test_asha_smoke_on_jenga(self):
        from repro.simulator import search

        trace = workloads.make("gups", T=T, n=N)
        sr = search.run("jenga", "asha", trace=trace,
                        machine="pmem-large", k=K, budget=4, t_min=24)
        assert set(sr.best_config) == {"alpha", "confirm", "cooldown",
                                       "migration_period"}
        assert all(r.dispatches == 1 for r in sr.rounds)
        assert sr.best_result.exec_time_s > 0

    def test_families_registry_routes_new_specs(self):
        from repro.simulator import tuning

        for fam, cls in (("hybridtier", HybridTierSpec),
                         ("jenga", JengaSpec),
                         ("tierbpf", TierBPFSpec)):
            make, space, defaults = tuning.FAMILIES[fam]
            assert isinstance(make(**defaults), cls)
            assert set(defaults) <= set(space)
