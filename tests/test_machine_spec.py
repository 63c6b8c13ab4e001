"""N-tier machine protocol: legacy-bitwise safety net + tier invariants.

The refactor's contract (ISSUE 5): an N=2 ``TieredMachineSpec`` run must
be BITWISE equivalent to the pre-refactor boolean two-tier path in both
engines.  ``_legacy_crn_run`` below is a frozen, self-contained copy of
that pre-refactor reference engine (CRN mode): same boolean ``in_fast``
placement, same f32 interval arithmetic, same at-source clamping — the
new engines must reproduce its migration counts and exec time exactly.

Plus: adjacent-pair hop-chain migration properties (conservation, caps),
three-tier cross-engine equivalence, neutral-padding bitwise neutrality,
the raw-ratio clamping regression, the machine registry, and the
axis-product experiment API.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.arms_policy import ARMSPolicy, ARMSSpec
from repro.baselines.hemem import HeMemPolicy, HeMemSpec
from repro.baselines.memtis import MemtisPolicy, MemtisSpec
from repro.baselines.static import AllSlowPolicy, AllSlowSpec
from repro.baselines.tpp import TPPPolicy, TPPSpec
from repro.core import scheduler
from repro.simulator import (engine, experiment, machine_spec, machines,
                             scan_engine, tuning, workload_spec, workloads)
from repro.simulator.engine import run
from repro.simulator.machine import CACHELINE, PAGE_BYTES, PMEM_LARGE
from repro.simulator.machine import NUMA, MachineSpec, interval_time
from repro.simulator.sampling import uniform_field

T, N, K = 120, 256, 32


# --------------------------------------------------------------------------
# frozen pre-refactor two-tier reference engine (CRN mode only)
# --------------------------------------------------------------------------
def _legacy_accounting(m: MachineSpec):
    f32 = jnp.float32
    lat_f, lat_s = f32(m.lat_fast_ns), f32(m.lat_slow_ns)
    bw_f, bw_sr, bw_sw = f32(m.bw_fast), f32(m.bw_slow_read), \
        f32(m.bw_slow_write)
    mlp = f32(m.mlp)

    @jax.jit
    def acct(true_counts, in_fast, promo_pages, demo_pages):
        true = jnp.asarray(true_counts, f32)
        acc_fast = jnp.sum(true * in_fast)
        acc_slow = jnp.sum(true) - acc_fast
        promo = jnp.asarray(promo_pages, f32)
        demo = jnp.asarray(demo_pages, f32)
        app_fast_bytes = acc_fast * CACHELINE
        app_slow_bytes = acc_slow * CACHELINE
        mig_fast_bytes = (promo + demo) * PAGE_BYTES
        mig_slow_read = promo * PAGE_BYTES
        mig_slow_write = demo * PAGE_BYTES
        t_lat = (acc_fast * lat_f + acc_slow * lat_s) * 1e-9 / mlp
        t_bw_fast = (app_fast_bytes + mig_fast_bytes) / bw_f
        t_bw_slow = ((app_slow_bytes + mig_slow_read) / bw_sr
                     + mig_slow_write / bw_sw)
        wall = jnp.maximum(jnp.maximum(t_lat, t_bw_fast),
                           jnp.maximum(t_bw_slow, 1e-12))
        slow_share = acc_slow / jnp.maximum(acc_fast + acc_slow, 1e-9)
        app_frac = jnp.minimum(1.0, t_bw_fast / wall)   # at-source clamp
        return acc_fast, acc_slow, wall, slow_share, app_frac

    return acct


def _legacy_crn_run(policy, trace, m: MachineSpec, k, sample_u):
    """Pre-refactor numpy reference engine, boolean in_fast placement."""
    from repro.simulator.engine import WASTE_WINDOW, _crn_sampler

    T_, n = trace.shape
    policy.reset(n, k, machines.get(m))
    acct = _legacy_accounting(m)
    crn_sample = _crn_sampler()
    in_fast = np.zeros(n, bool)
    promoted_at = np.full(n, -(10 ** 9))
    demoted_at = np.full(n, -(10 ** 9))
    slow_bw_frac, app_bw_frac = 1.0, 0.0
    exec_time = 0.0
    promotions = demotions = wasteful = 0
    for t in range(T_):
        true = trace[t]
        if policy.wants_true_counts():
            observed = true
        else:
            observed = np.asarray(crn_sample(
                sample_u[t], true.astype(np.float32),
                np.float32(policy.sampling_period())), np.float64)
        promote, demote = policy.step(observed, slow_bw_frac, app_bw_frac)
        demote = np.asarray(demote, np.int64)
        promote = np.asarray(promote, np.int64)
        demote = demote[in_fast[demote]]
        in_fast[demote] = False
        promote = promote[~in_fast[promote]]
        room = k - int(in_fast.sum())
        promote = promote[:room]
        in_fast[promote] = True
        wasteful += int((t - demoted_at[promote] <= WASTE_WINDOW).sum())
        wasteful += int((t - promoted_at[demote] <= WASTE_WINDOW).sum())
        promoted_at[promote] = t
        demoted_at[demote] = t
        promotions += len(promote)
        demotions += len(demote)
        _, acc_slow, wall, slow_share, app_frac = (
            float(v) for v in acct(true.astype(np.float32), in_fast,
                                   float(len(promote)), float(len(demote))))
        extra_ns = getattr(policy, "slow_access_extra_ns", 0.0)
        if extra_ns:
            wall += acc_slow * extra_ns * 1e-9 / m.mlp
        exec_time += wall
        slow_bw_frac, app_bw_frac = slow_share, app_frac
    return dict(promotions=promotions, demotions=demotions,
                wasteful=wasteful, exec_time=exec_time)


class TestLegacyBitwiseEquivalence:
    """N=2 tier-index runs == the frozen pre-refactor two-tier engine."""

    @pytest.mark.parametrize("policy_cls", [HeMemPolicy, ARMSPolicy,
                                            TPPPolicy])
    def test_numpy_engine_matches_frozen_legacy(self, policy_cls):
        trace = workloads.make("silo-tpcc", T=T, n=N)
        u = uniform_field(T, N, seed=11)
        ref = _legacy_crn_run(policy_cls(), trace, PMEM_LARGE, K, u)
        out = run(policy_cls(), trace, PMEM_LARGE, K, sample_u=u)
        # the migration decisions are BITWISE those of the frozen legacy
        # engine; exec time is float-tolerant only because the two jitted
        # cost programs may fuse (FMA) differently.
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref["promotions"], ref["demotions"], ref["wasteful"])
        np.testing.assert_allclose(out.exec_time_s, ref["exec_time"],
                                   rtol=1e-5)

    def test_scan_engine_matches_frozen_legacy(self):
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=7)
        ref = _legacy_crn_run(HeMemPolicy(), trace, PMEM_LARGE, K, u)
        out = experiment.sweep([HeMemSpec.make()], trace=trace,
                               machines=PMEM_LARGE, k=K, sample_u=u).grid[0]
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref["promotions"], ref["demotions"], ref["wasteful"])
        np.testing.assert_allclose(out.exec_time_s, ref["exec_time"],
                                   rtol=1e-5)

    def test_numa_machine_matches_frozen_legacy(self):
        trace = workloads.make("btree", T=T, n=N)
        u = uniform_field(T, N, seed=3)
        ref = _legacy_crn_run(ARMSPolicy(), trace, NUMA, K, u)
        out = run(ARMSPolicy(), trace, NUMA, K, sample_u=u)
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref["promotions"], ref["demotions"], ref["wasteful"])
        np.testing.assert_allclose(out.exec_time_s, ref["exec_time"],
                                   rtol=1e-5)


# --------------------------------------------------------------------------
# hop-chain migration invariants
# --------------------------------------------------------------------------
def _random_case(rng, R):
    n = int(rng.integers(16, 64))
    tier = rng.integers(0, R, n).astype(np.int32)
    caps = np.full(R, n, np.int64)
    caps[0] = int(rng.integers(max(1, (tier == 0).sum()), n + 1))
    for r in range(1, R - 1):
        caps[r] = int(rng.integers((tier == r).sum(), n + 1))
    pad_p, pad_d = int(rng.integers(1, 12)), int(rng.integers(1, 12))
    pick = lambda w: np.where(rng.random(w) < 0.75,
                              rng.choice(n, w, replace=False), -1)
    return tier, pick(min(pad_p, n)).astype(np.int32), \
        pick(min(pad_d, n)).astype(np.int32), caps.astype(np.int32)


class TestTierMigrations:
    def test_n2_matches_boolean_form(self):
        rng = np.random.default_rng(0)
        for _ in range(40):
            tier, promote, demote, caps = _random_case(rng, 2)
            in_fast = tier == 0
            t2, pexec, dexec, up, down = simjax_apply(tier, promote, demote,
                                                      caps)
            f2, pexec_b, dexec_b = __import__(
                "repro.simulator.simjax", fromlist=["x"]
            ).apply_padded_migrations(jnp.asarray(in_fast),
                                      jnp.asarray(promote),
                                      jnp.asarray(demote), int(caps[0]))
            np.testing.assert_array_equal(np.asarray(t2) == 0,
                                          np.asarray(f2))
            np.testing.assert_array_equal(np.asarray(pexec),
                                          np.asarray(pexec_b))
            np.testing.assert_array_equal(np.asarray(dexec),
                                          np.asarray(dexec_b))
            assert int(up[0]) == int(np.asarray(pexec).sum())
            assert int(down[0]) == int(np.asarray(dexec).sum())

    @pytest.mark.parametrize("R", [2, 3, 4])
    def test_conservation_and_caps(self, R):
        rng = np.random.default_rng(R)
        for _ in range(40):
            tier, promote, demote, caps = _random_case(rng, R)
            t2, pexec, dexec, up, down = simjax_apply(tier, promote, demote,
                                                      caps)
            t2 = np.asarray(t2)
            # populations sum to n; every tier but the bottom within caps
            counts = np.bincount(t2, minlength=R)
            assert counts.sum() == len(t2)
            assert (t2 >= 0).all() and (t2 <= R - 1).all()
            for r in range(R - 1):
                assert counts[r] <= caps[r]

    @pytest.mark.parametrize("R", [2, 3, 4])
    def test_numpy_mirror_matches_jnp(self, R):
        rng = np.random.default_rng(100 + R)
        for _ in range(40):
            tier, promote, demote, caps = _random_case(rng, R)
            t_jnp, pexec, dexec, up, down = simjax_apply(
                tier, promote, demote, caps)
            t_np = tier.copy()
            pr, de, up_np, down_np = engine.apply_tier_migrations_np(
                t_np, promote[promote >= 0], demote[demote >= 0], caps)
            np.testing.assert_array_equal(np.asarray(t_jnp), t_np)
            assert int(np.asarray(pexec).sum()) == len(pr)
            assert int(np.asarray(dexec).sum()) == len(de)
            np.testing.assert_array_equal(np.asarray(up), up_np)
            np.testing.assert_array_equal(np.asarray(down), down_np)

    def test_demotion_cascades_past_full_tier(self):
        # tier 1 full -> a page demoted from tier 0 lands in tier 2,
        # crossing both pairs.
        tier = np.array([0, 1, 2, 2], np.int32)
        caps = np.array([1, 1, 4], np.int32)
        t2, pexec, dexec, up, down = simjax_apply(
            tier, np.array([-1], np.int32), np.array([0], np.int32), caps)
        assert int(np.asarray(t2)[0]) == 2
        np.testing.assert_array_equal(np.asarray(down), [1, 1])

    def test_promotion_charges_every_pair_crossed(self):
        tier = np.array([2, 2, 1, 0], np.int32)
        caps = np.array([3, 2, 4], np.int32)
        t2, pexec, dexec, up, down = simjax_apply(
            tier, np.array([0, 2], np.int32), np.array([-1], np.int32),
            caps)
        assert int(np.asarray(t2)[0]) == 0 and int(np.asarray(t2)[2]) == 0
        # page 0 came from tier 2 (both pairs), page 2 from tier 1 (pair 0)
        np.testing.assert_array_equal(np.asarray(up), [2, 1])


def simjax_apply(tier, promote, demote, caps):
    from repro.simulator import simjax
    return simjax.apply_tier_migrations(
        jnp.asarray(tier), jnp.asarray(promote), jnp.asarray(demote),
        jnp.asarray(caps))


# --------------------------------------------------------------------------
# three-tier cross-engine equivalence + conservation in a full run
# --------------------------------------------------------------------------
class TestThreeTier:
    @pytest.mark.parametrize("policy_cls,make_spec", [
        (HeMemPolicy, lambda: HeMemSpec.make()),
        (MemtisPolicy, lambda: MemtisSpec.make()),
        (TPPPolicy, lambda: TPPSpec.make()),
        (ARMSPolicy, None),
        (AllSlowPolicy, AllSlowSpec),
    ])
    def test_cross_engine_exact(self, policy_cls, make_spec):
        """Scan and numpy engines agree EXACTLY on a 3-tier chain under a
        shared CRN field — two independent implementations of the hop-chain
        semantics."""
        trace = workloads.make("silo-tpcc", T=80, n=N)
        u = uniform_field(80, N, seed=5)
        ref = run(policy_cls(), trace, "dram-cxl-pmem", K, sample_u=u)
        spec = ARMSSpec.make() if make_spec is None else make_spec()
        out = experiment.sweep([spec], trace=trace, machines="dram-cxl-pmem",
                               k=K, sample_u=u).grid[0]
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref.promotions, ref.demotions, ref.wasteful)
        np.testing.assert_allclose(out.exec_time_s, ref.exec_time_s,
                                   rtol=1e-4)

    def test_three_tier_differs_from_two_tier(self):
        trace = workloads.make("gups", T=80, n=N)
        u = uniform_field(80, N, seed=5)
        three = run(HeMemPolicy(), trace, "dram-cxl-pmem", K, sample_u=u)
        two = run(HeMemPolicy(), trace, "pmem-large", K, sample_u=u)
        assert three.exec_time_s != two.exec_time_s

    def test_padding_is_bitwise_neutral(self):
        """A 2-tier machine padded to 3 tiers replays bitwise unchanged."""
        trace = workloads.make("gups", T=80, n=N)
        u = uniform_field(80, N, seed=9)
        m = machines.get("pmem-large")
        caps = machine_spec.resolved_caps(m, N, K)
        padded, _ = machine_spec.pad_tiers(m, caps, 3)
        a = run(HeMemPolicy(), trace, m, K, sample_u=u)
        b = run(HeMemPolicy(), trace, padded, K, sample_u=u)
        assert (a.promotions, a.demotions, a.wasteful, a.exec_time_s) == \
            (b.promotions, b.demotions, b.wasteful, b.exec_time_s)


# --------------------------------------------------------------------------
# raw-ratio clamping (satellite): oversaturation visible, consumers clamp
# --------------------------------------------------------------------------
class TestRawUtilization:
    def test_interval_time_reports_oversaturation(self):
        # slow-tier-bound interval on pmem-large: slow bandwidth time far
        # exceeds the latency-bound time -> the raw ratio exceeds 1
        # instead of being pegged at 1 by the old min(1, t/wall) clamp.
        out = interval_time(PMEM_LARGE, 0.0, 1e9, 0, 0)
        assert out.slow_bw_frac > 1.0
        # unsaturated direction still reports <= 1
        assert out.app_bw_frac <= 1.0

    def test_simjax_raw_matches_host(self):
        # a machine whose fast tier is bandwidth-starved: tier-0 raw
        # utilization exceeds 1 and is visible to accounting consumers.
        m = machine_spec.make("starved", [80.0, 200.0], [1e9, 7.45e9],
                              [1e9, 2.25e9])
        from repro.simulator import simjax
        tier = jnp.zeros(8, jnp.int32)
        true = jnp.full((8,), 2e8, jnp.float32)
        _, _, wall, _, app_raw = (
            float(v) for v in simjax.interval_accounting(
                m, true, tier, jnp.asarray([400.0], jnp.float32),
                jnp.asarray([0.0], jnp.float32)))
        assert app_raw > 1.0
        host = machine_spec.interval_outcome_host(
            m, [8 * 2e8, 0.0], [400.0], [0.0])
        assert host[2] > 1.0                       # same story on host
        np.testing.assert_allclose(app_raw, host[2], rtol=1e-5)

    def test_consumer_clamp_preserves_signal(self):
        # scheduler.batch_size is the consumer: raw > 1 behaves as 1.
        for raw in (1.0, 1.7, 9.0):
            assert int(scheduler.batch_size(raw, 1.0, 64)) == \
                int(scheduler.batch_size(1.0, 1.0, 64))
        # and unsaturated signals pass through unchanged
        assert int(scheduler.batch_size(0.5, 1.0, 64)) == 32

    def test_pair_budgets_clip_and_bound(self):
        u = jnp.asarray([0.0, 2.5, 0.4], jnp.float32)   # oversaturated mid
        b = scheduler.pair_budgets(u, 64)
        assert b.shape == (2,)
        assert int(b[0]) == 1 and int(b[1]) == 1   # saturated endpoint
        b2 = scheduler.pair_budgets(jnp.asarray([0.0, 0.0], jnp.float32), 64)
        assert int(b2[0]) == 64


# --------------------------------------------------------------------------
# registry + experiment axis product
# --------------------------------------------------------------------------
class TestRegistryAndExperiment:
    def test_get_accepts_all_forms(self):
        a = machines.get("pmem-large")
        b = machines.get(PMEM_LARGE)
        c = machines.get(a)
        np.testing.assert_array_equal(np.asarray(a.lat_ns),
                                      np.asarray(b.lat_ns))
        assert c is a
        with pytest.raises(ValueError):
            machines.get("optane-9000")
        with pytest.raises(TypeError):
            machines.get(42)

    def test_names_anywhere(self):
        trace = workloads.make("gups", T=40, n=64)
        r1 = run(HeMemPolicy(), trace, "numa", 8)
        r2 = run(HeMemPolicy(), trace, NUMA, 8)
        assert r1.exec_time_s == r2.exec_time_s
        s1 = experiment.sweep([HeMemSpec.make()], trace=trace,
                              machines="pmem-large", k=8,
                              seeds=[0, 1]).at(seed=0)
        assert s1.promotions >= 0
        best_cfg, _, _ = tuning.tune("hemem", trace, "pmem-large", 8,
                                     budget=2)
        assert best_cfg

    def test_axis_product_one_dispatch_per_family(self):
        res = experiment.sweep(
            [HeMemSpec.make(), HeMemSpec.make(hot_threshold=4.0)],
            workloads=["gups", "silo-tpcc"],
            machines=["pmem-large", "dram-cxl-pmem"],
            k=16, T=50, n=128)
        assert res.shape == (2, 2, 2, 1)
        d = scan_engine.last_dispatch
        assert d["lanes"] == 8 and d["machines"] == 2 and d["synth"] is True
        assert d["axis_product"] is True
        # structured addressing by label and index agree
        assert res.at(policy=1, workload="silo-tpcc",
                      machine="dram-cxl-pmem") is res.grid[
            ((1 * 2 + 1) * 2 + 1) * 1]
        assert len(list(res.items())) == 8

    def test_lane_equals_single_run(self):
        wl = workload_spec.named("gups", T=50)
        res = experiment.sweep(
            [HeMemSpec.make()], workloads=[wl],
            machines=["pmem-large", "numa"], k=16, T=50, n=128, sim_seed=2)
        single = experiment.sweep([HeMemSpec.make()], workloads=[wl],
                                  machines="numa", k=16, T=50, n=128,
                                  sim_seed=2).grid[0]
        lane = res.at(machine="numa")
        assert (lane.promotions, lane.demotions, lane.wasteful) == \
            (single.promotions, single.demotions, single.wasteful)
        assert lane.exec_time_s == single.exec_time_s

    def test_seed_axis_varies_noise(self):
        # ARMS is sampling-noise sensitive (HeMem's coarse thresholds can
        # absorb small-seed noise into identical placements).
        res = experiment.sweep(["arms"], workloads=["silo-tpcc"],
                               machines=["pmem-large"], seeds=[0, 1, 2, 3],
                               k=32, T=100, n=256)
        times = {res.at(seed=s).exec_time_s for s in range(4)}
        assert len(times) > 1

    def test_trace_mode_matches_numpy(self):
        trace = workloads.make("btree", T=60, n=128)
        res = experiment.sweep([HeMemSpec.make()], trace=trace,
                               machines=["pmem-large"], k=16, sim_seed=4)
        u = uniform_field(60, 128, seed=4)
        ref = run(HeMemPolicy(), trace, "pmem-large", 16, sample_u=u)
        out = res.at()
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref.promotions, ref.demotions, ref.wasteful)

    def test_mixed_families_cover_grid(self):
        res = experiment.sweep(["hemem", "arms"], workloads=["gups"],
                               machines=["pmem-large"], k=16, T=40, n=128)
        assert res.shape == (2, 1, 1, 1)
        assert all(r is not None for r in res.grid)
        assert res.at(policy="arms").name.startswith("arms@")

    def test_at_rejects_out_of_range_indices(self):
        res = experiment.sweep(["hemem"], workloads=["gups"],
                               machines=["pmem-large", "numa"],
                               k=8, T=30, n=64)
        with pytest.raises(IndexError):
            res.at(machine=-1)       # would alias another axis block
        with pytest.raises(IndexError):
            res.at(machine=2)
        with pytest.raises(KeyError):
            res.at(machine="optane")

    def test_input_validation(self):
        with pytest.raises(ValueError):
            experiment.sweep(["hemem"], k=8)               # no workload/trace
        with pytest.raises(ValueError):
            experiment.sweep(["hemem"], workloads=["gups"],
                             trace=np.zeros((4, 8)), k=2, T=4, n=8)
        with pytest.raises(ValueError):
            experiment.sweep(["hemem"], workloads=["gups"], k=2)  # no T/n
        with pytest.raises(ValueError):
            experiment.sweep(["nimble"], workloads=["gups"], k=2, T=4, n=8)


class TestResolvedCaps:
    def test_encoding(self):
        m = machines.get("dram-cxl-pmem")
        caps = machine_spec.resolved_caps(m, n=1024, k=128)
        np.testing.assert_array_equal(caps, [128, 256, 1024])

    def test_two_tier_defaults(self):
        caps = machine_spec.resolved_caps(machines.get("pmem-large"),
                                          n=512, k=64)
        np.testing.assert_array_equal(caps, [64, 512])

    def test_absolute_and_clamped(self):
        m = machine_spec.make("t", [80, 200, 300], [1e11, 1e10, 1e9],
                              [1e11, 1e10, 1e9],
                              capacity_pages=[-1.0, 10_000.0, 0.0])
        caps = machine_spec.resolved_caps(m, n=256, k=32)
        np.testing.assert_array_equal(caps, [32, 256, 256])  # clamped to n
