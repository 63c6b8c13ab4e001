"""Scan-engine vs numpy-engine equivalence + batched-sweep behaviour.

The compiled ``lax.scan`` engine must be a faithful replacement for the
numpy reference engine on EVERY policy speaking the functional protocol:
under a shared common-random-number sampling field both engines see
bitwise-identical PEBS noise and interval arithmetic, so migration counts
must match EXACTLY and execution time to float32 accumulation error.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.arms_policy import ARMSPolicy, ARMSSpec
from repro.baselines.hemem import HeMemPolicy, HeMemSpec
from repro.baselines.memtis import MemtisPolicy, MemtisSpec
from repro.baselines.static import (AllSlowPolicy, AllSlowSpec, OraclePolicy,
                                    OracleSpec)
from repro.baselines.tpp import TPPPolicy, TPPSpec
from repro.core.state import ARMSConfig
from repro.simulator import experiment, scan_engine, tuning, workloads
from repro.simulator.engine import oracle_topk_masks, run
from repro.simulator.machine import NUMA, PMEM_LARGE
from repro.simulator.sampling import pebs_sample_from_uniform, uniform_field

T, N, K = 160, 512, 64

# (legacy numpy-engine policy, functional spec) per family, default knobs.
FAMILIES = [
    (HeMemPolicy, lambda: HeMemSpec.make()),
    (MemtisPolicy, lambda: MemtisSpec.make()),
    (TPPPolicy, lambda: TPPSpec.make()),
    (AllSlowPolicy, AllSlowSpec),
    (OraclePolicy, OracleSpec),
]


def _crn_pair(wl, machine=PMEM_LARGE, seed=0, cfg=None):
    trace = workloads.make(wl, T=T, n=N)
    u = uniform_field(T, N, seed=123)
    ref = run(ARMSPolicy(cfg), trace, machine, K, seed=seed, sample_u=u)
    out = experiment.sweep([ARMSSpec.make(base_cfg=cfg)], trace=trace,
                           machines=machine, k=K, sample_u=u,
                           timelines=True).grid[0]
    return ref, out


class TestEngineEquivalence:
    @pytest.mark.parametrize("wl", ["gups", "silo-tpcc"])
    def test_matches_numpy_reference(self, wl):
        ref, out = _crn_pair(wl)
        assert out.promotions == ref.promotions
        assert out.demotions == ref.demotions
        assert out.wasteful == ref.wasteful
        np.testing.assert_allclose(out.exec_time_s, ref.exec_time_s,
                                   rtol=1e-4)
        np.testing.assert_array_equal(out.timeline_promotions,
                                      ref.timeline_promotions)
        np.testing.assert_array_equal(out.timeline_mode, ref.timeline_mode)

    def test_matches_on_other_machine(self):
        ref, out = _crn_pair("gups", machine=NUMA)
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref.promotions, ref.demotions, ref.wasteful)
        np.testing.assert_allclose(out.exec_time_s, ref.exec_time_s,
                                   rtol=1e-4)

    def test_recall_and_hits_close(self):
        ref, out = _crn_pair("gups")
        np.testing.assert_allclose(out.hot_recall, ref.hot_recall, rtol=1e-4)
        np.testing.assert_allclose(out.fast_hit_frac, ref.fast_hit_frac,
                                   rtol=1e-4)

    @pytest.mark.parametrize("wl", ["gups", "silo-tpcc"])
    @pytest.mark.parametrize(
        "family", [f[0].__name__ for f in FAMILIES])
    def test_every_baseline_matches_numpy_reference(self, wl, family):
        """Cross-engine CRN equivalence for each functional-protocol policy:
        the scan engine and the numpy engine (via LegacyPolicyAdapter) must
        agree EXACTLY on promotions/demotions/wasteful counts."""
        policy_cls, make_spec = dict(
            (f[0].__name__, f) for f in FAMILIES)[family]
        trace = workloads.make(wl, T=T, n=N)
        u = uniform_field(T, N, seed=31)
        ref = run(policy_cls(), trace, PMEM_LARGE, K, sample_u=u)
        out = experiment.sweep([make_spec()], trace=trace,
                               machines=PMEM_LARGE, k=K, sample_u=u,
                               timelines=True).grid[0]
        assert (out.promotions, out.demotions, out.wasteful) == \
            (ref.promotions, ref.demotions, ref.wasteful)
        np.testing.assert_allclose(out.exec_time_s, ref.exec_time_s,
                                   rtol=1e-4)
        np.testing.assert_array_equal(out.timeline_promotions,
                                      ref.timeline_promotions)

    def test_arms_spec_through_generic_adapter(self):
        """ARMSSpec driven by the generic LegacyPolicyAdapter reproduces the
        hand-tuned ARMSPolicy wrapper exactly (same functional core)."""
        from repro.baselines.protocol import LegacyPolicyAdapter
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=5)
        a = run(ARMSPolicy(), trace, PMEM_LARGE, K, sample_u=u)
        b = run(LegacyPolicyAdapter(ARMSSpec.make()), trace, PMEM_LARGE, K,
                sample_u=u)
        assert (a.promotions, a.demotions, a.wasteful) == \
            (b.promotions, b.demotions, b.wasteful)
        np.testing.assert_array_equal(a.timeline_mode, b.timeline_mode)

    def test_kernel_and_jnp_score_paths_agree(self):
        """The fused Pallas path and the jnp escape hatch are one formula."""
        u = uniform_field(T, N, seed=9)
        trace = workloads.make("gups", T=T, n=N)
        kw = dict(trace=trace, machines=PMEM_LARGE, k=K, sample_u=u)
        a = experiment.sweep([ARMSSpec.make()], **kw).grid[0]
        b = experiment.sweep(
            [ARMSSpec.make(base_cfg=ARMSConfig(use_score_kernel=False))],
            **kw).grid[0]
        assert a.promotions == b.promotions
        assert a.wasteful == b.wasteful
        np.testing.assert_allclose(a.exec_time_s, b.exec_time_s, rtol=1e-5)


class TestSweeps:
    def test_seed_sweep_deterministic(self):
        kw = dict(trace=workloads.make("btree", T=T, n=N),
                  machines=PMEM_LARGE, seeds=[0, 1, 2], k=K, timelines=True)
        r1 = experiment.sweep(["arms"], **kw).grid
        r2 = experiment.sweep(["arms"], **kw).grid
        for a, b in zip(r1, r2):
            assert a.exec_time_s == b.exec_time_s
            assert a.promotions == b.promotions
            np.testing.assert_array_equal(a.timeline_promotions,
                                          b.timeline_promotions)

    def test_seed_sweep_lane_matches_single_run(self):
        """A seed lane is bitwise the same replay whatever lanes share its
        dispatch: lanes are keyed by their own seed."""
        kw = dict(trace=workloads.make("gups", T=T, n=N),
                  machines=PMEM_LARGE, k=K)
        single = experiment.sweep(["arms"], seeds=[3, 5], **kw).at(seed=0)
        lane = experiment.sweep(["arms"], seeds=[0, 3, 7], **kw).at(seed=1)
        assert lane.promotions == single.promotions
        assert lane.exec_time_s == single.exec_time_s

    def test_seed_sweep_varies_noise(self):
        trace = workloads.make("silo-tpcc", T=T, n=N)
        rows = experiment.sweep(["arms"], trace=trace, machines=PMEM_LARGE,
                                seeds=range(4), k=K).grid
        assert len({r.exec_time_s for r in rows}) > 1  # noise does vary

    def test_config_sweep_lane_matches_crn_single_run(self):
        """Config lane 0 (defaults) == a default-ARMS run on the sweep's
        CRN field, passed explicitly."""
        seed = 0
        trace = workloads.make("gups", T=T, n=N)
        u = uniform_field(T, N, seed=seed)
        rows = experiment.sweep(
            [ARMSSpec.make(dict(alpha_s=a)) for a in (0.7, 0.5)],
            trace=trace, machines=PMEM_LARGE, k=K, sim_seed=seed).grid
        ref = experiment.sweep([ARMSSpec.make()], trace=trace,
                               machines=PMEM_LARGE, k=K, sample_u=u).grid[0]
        assert rows[0].promotions == ref.promotions
        assert rows[0].exec_time_s == ref.exec_time_s

    def test_baseline_seed_sweep_runs_batched(self):
        trace = workloads.make("gups", T=80, n=256)
        rows = experiment.sweep([HeMemSpec.make()], trace=trace,
                                machines=PMEM_LARGE, seeds=range(3),
                                k=32).grid
        assert len(rows) == 3 and all(r.name.startswith("hemem")
                                      for r in rows)
        assert scan_engine.last_dispatch["lanes"] == 3

    def test_config_sweep_differentiates_configs(self):
        trace = workloads.make("gups", T=T, n=N)
        rows = experiment.sweep(
            [ARMSSpec.make(dict(access_scale=a)) for a in (10_000.0, 0.0)],
            trace=trace, machines=PMEM_LARGE, k=K).grid
        assert rows[0].promotions > 0
        assert rows[1].promotions == 0      # zero benefit -> gate rejects
        assert rows[1].exec_time_s > rows[0].exec_time_s

    def test_config_sweep_rejects_non_sweepable(self):
        trace = workloads.make("gups", T=40, n=64)
        with pytest.raises(ValueError, match="non-sweepable"):
            experiment.sweep([ARMSSpec.make(dict(bs_max=b)) for b in (32, 64)],
                             trace=trace, machines=PMEM_LARGE, k=8)

    def test_tune_arms_runs_batched(self):
        trace = workloads.make("gups", T=80, n=256)
        best_cfg, best_res, rows = tuning.tune_arms(trace, PMEM_LARGE, 32,
                                                    budget=6)
        assert len(rows) >= 6
        assert best_res.exec_time_s == min(r.exec_time_s for _, r in rows)
        assert set(best_cfg) == set(tuning.ARMS_SPACE)


class TestTuning:
    """The unified tune() entry: one compiled lane-batched sweep per family,
    scored identically to the sequential numpy path under a shared CRN
    field, with search noise decoupled from simulation noise."""

    def test_tune_hemem_matches_sequential_numpy_ranking(self):
        trace = workloads.make("silo-tpcc", T=T, n=N)
        sim_seed = 9
        best_cfg, best_res, rows = tuning.tune_hemem(
            trace, PMEM_LARGE, K, budget=6, search_seed=2, sim_seed=sim_seed)
        # ONE lane-batched dispatch covered the whole budget.
        assert scan_engine.last_dispatch["lanes"] == len(rows)
        assert scan_engine.last_dispatch["policy"] == "hemem"
        # every lane == its sequential numpy replay on the same CRN field
        u = uniform_field(T, N, seed=sim_seed)
        seq = []
        for cfg, res in rows:
            ref = run(HeMemPolicy(**cfg), trace, PMEM_LARGE, K, sample_u=u)
            assert (ref.promotions, ref.demotions, ref.wasteful) == \
                (res.promotions, res.demotions, res.wasteful)
            np.testing.assert_allclose(res.exec_time_s, ref.exec_time_s,
                                       rtol=1e-4)
            seq.append((ref.exec_time_s, cfg))
        # ... so the best-config ranking matches the sequential path.
        seq_ranking = [cfg for _, cfg in sorted(seq, key=lambda x: x[0])]
        assert [cfg for cfg, _ in rows] == seq_ranking
        assert best_cfg == seq_ranking[0]
        assert best_res.exec_time_s == min(r.exec_time_s for _, r in rows)

    @pytest.mark.parametrize("tune_fn,policy_cls", [
        (tuning.tune_memtis, MemtisPolicy), (tuning.tune_tpp, TPPPolicy)])
    def test_tune_baselines_match_sequential_numpy(self, tune_fn, policy_cls):
        trace = workloads.make("btree", T=80, n=256)
        k, sim_seed = 32, 4
        _, _, rows = tune_fn(trace, PMEM_LARGE, k, budget=4, sim_seed=sim_seed)
        assert scan_engine.last_dispatch["lanes"] == len(rows)
        u = uniform_field(80, 256, seed=sim_seed)
        for cfg, res in rows:
            ref = run(policy_cls(**cfg), trace, PMEM_LARGE, k, sample_u=u)
            assert (ref.promotions, ref.demotions, ref.wasteful) == \
                (res.promotions, res.demotions, res.wasteful)

    def test_search_seed_decoupled_from_sim_noise(self):
        """Changing the search seed must NOT change how a given config
        scores (the seed-coupling bug this PR fixes): the default config is
        drawn under every search seed and must score identically."""
        trace = workloads.make("gups", T=80, n=256)
        score = {}
        for search_seed in (0, 1):
            _, _, rows = tuning.tune_hemem(trace, PMEM_LARGE, 32, budget=4,
                                           search_seed=search_seed,
                                           sim_seed=3)
            score[search_seed] = {
                tuple(sorted(cfg.items())): r.exec_time_s for cfg, r in rows}
        shared = set(score[0]) & set(score[1])
        assert shared  # the always-inserted default config at minimum
        for cfg in shared:
            assert score[0][cfg] == score[1][cfg]

    def test_sim_seed_changes_noise(self):
        trace = workloads.make("silo-tpcc", T=80, n=256)
        a = tuning.tune_hemem(trace, PMEM_LARGE, 32, budget=3, sim_seed=0)[2]
        b = tuning.tune_hemem(trace, PMEM_LARGE, 32, budget=3, sim_seed=1)[2]
        assert any(ra.exec_time_s != rb.exec_time_s
                   for (_, ra), (_, rb) in zip(a, b))

    def test_tune_unknown_family_rejected(self):
        trace = workloads.make("gups", T=20, n=64)
        with pytest.raises(ValueError):
            tuning.tune("nimble", trace, PMEM_LARGE, 8, budget=2)


class TestSamplingTransform:
    def test_poisson_from_uniform_moments(self):
        """Inverse-CDF transform reproduces Poisson mean/variance."""
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.random(200_000), jnp.float32)
        for lam in (0.05, 0.8, 5.0, 40.0):
            x = np.asarray(pebs_sample_from_uniform(
                u, jnp.full(u.shape, lam * 1e4, jnp.float32), 1e4))
            assert abs(x.mean() - lam) < 0.05 * max(lam, 1.0)
            assert abs(x.var() - lam) < 0.1 * max(lam, 1.0)

    def test_zero_rate_yields_zero(self):
        u = jnp.asarray([0.01, 0.5, 0.999], jnp.float32)
        x = pebs_sample_from_uniform(u, jnp.zeros(3), 1e4)
        np.testing.assert_array_equal(np.asarray(x), 0.0)


class TestOracleMasks:
    def test_matches_per_interval_argpartition(self):
        trace = workloads.make("btree", T=40, n=128)
        masks = oracle_topk_masks(trace, 16)
        for t in range(0, 40, 7):
            topk = np.argpartition(trace[t], -16)[-16:]
            assert masks[t].sum() == 16
            assert masks[t][topk].all()
