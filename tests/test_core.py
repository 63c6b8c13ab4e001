"""Unit + property tests for the ARMS core (paper §4, Algorithms 1-2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core import (MODE_HISTORY, MODE_RECENCY, ARMSConfig, arms_step,
                        init_state, pht_update)
from repro.core import classifier, costbenefit, scheduler
from repro.core.state import init_pht

CFG = ARMSConfig()


# ---------------------------------------------------------------- classifier
class TestClassifier:
    def test_ewma_time_constants(self):
        """EWMA_s (alpha=0.7) must settle much faster than EWMA_l (alpha=0.1).

        Pins the DESIGN.md formula-note semantics: alpha weights the NEW
        sample (prose), not the old average (Alg. 1 as printed).
        """
        st_ = init_state(4, CFG)
        for _ in range(3):
            st_ = classifier.update_scores(st_, jnp.full(4, 10.0), CFG,
                                           jnp.int32(MODE_HISTORY))
        # after 3 steps of x=10: ewma_s = 10*(1-0.3^3) = 9.73, ewma_l = 2.71
        np.testing.assert_allclose(st_.ewma_s, 10 * (1 - 0.3**3), rtol=1e-5)
        np.testing.assert_allclose(st_.ewma_l, 10 * (1 - 0.9**3), rtol=1e-5)
        assert float(st_.ewma_s[0]) > float(st_.ewma_l[0])

    def test_score_is_weighted_sum_and_mode_dependent(self):
        st_ = init_state(2, CFG)
        st_h = classifier.update_scores(st_, jnp.array([5.0, 0.0]), CFG,
                                        jnp.int32(MODE_HISTORY))
        st_r = classifier.update_scores(st_, jnp.array([5.0, 0.0]), CFG,
                                        jnp.int32(MODE_RECENCY))
        ws, wl = CFG.w_s_history, CFG.w_l_history
        np.testing.assert_allclose(
            st_h.score, ws * st_h.ewma_s + wl * st_h.ewma_l, rtol=1e-6)
        # recency mode weights the (larger) short EWMA more -> higher score
        assert float(st_r.score[0]) > float(st_h.score[0])

    def test_topk_mask_exact_k(self):
        score = jnp.arange(100, dtype=jnp.float32)
        mask, idx = classifier.topk_hot_mask(score, 10)
        assert int(mask.sum()) == 10
        assert bool(mask[90:].all())

    def test_hot_age_counts_consecutive_topk(self):
        st_ = init_state(4, CFG)
        hot = jnp.array([True, True, False, False])
        st_ = classifier.update_hot_age(st_, hot)
        st_ = classifier.update_hot_age(st_, hot)
        st_ = classifier.update_hot_age(
            st_, jnp.array([True, False, True, False]))
        assert st_.hot_age.tolist() == [3, 0, 1, 0]


# ----------------------------------------------------------------------- PHT
class TestPHT:
    def test_no_alarm_on_stationary_signal(self):
        s = init_pht()
        rng = np.random.default_rng(1)
        for _ in range(200):
            s, alarm, _ = pht_update(s, 0.3 + 0.005 * rng.standard_normal(),
                                     CFG)
            assert not bool(alarm)

    def test_alarm_on_step_increase_then_reset(self):
        s = init_pht()
        for _ in range(50):
            s, alarm, _ = pht_update(s, 0.2, CFG)
            assert not bool(alarm)
        fired = False
        for i in range(50):
            s, alarm, _ = pht_update(s, 0.8, CFG)
            if bool(alarm):
                fired = True
                assert int(s.n) == 0  # reset after alarm
                break
        assert fired and i < 5  # detects within a few intervals

    def test_no_alarm_on_decrease(self):
        """PHT is configured for increase detection only (hot-set change =>
        MORE slow-tier traffic)."""
        s = init_pht()
        for _ in range(50):
            s, alarm, _ = pht_update(s, 0.8, CFG)
        for _ in range(50):
            s, alarm, _ = pht_update(s, 0.1, CFG)
            assert not bool(alarm)


# -------------------------------------------------------------- cost/benefit
class TestCostBenefit:
    def _steady(self, n=64, k=8, hot=None, intervals=6):
        hot = hot if hot is not None else range(k)
        st_ = init_state(n, CFG)
        counts = np.zeros(n)
        for p in hot:
            counts[p] = 50.0
        for _ in range(intervals):
            st_, plan = arms_step(st_, jnp.asarray(counts), 0.2, 0.1,
                                  cfg=CFG, k=k)
        return st_, plan

    def test_one_hit_wonder_never_promoted(self):
        """A single burst (hot for 1 interval) fails the hot_age>=2 filter."""
        n, k = 64, 8
        st_ = init_state(n, CFG)
        burst = np.zeros(n)
        burst[10] = 100.0
        st_, plan = arms_step(st_, jnp.asarray(burst), 0.2, 0.1, cfg=CFG, k=k)
        assert int(plan.count) == 0
        st_, plan = arms_step(st_, jnp.zeros(n), 0.2, 0.1, cfg=CFG, k=k)
        assert int(plan.count) == 0
        assert not bool(st_.in_fast[10])

    def test_sustained_hot_pages_promoted(self):
        st_, _ = self._steady()
        assert int(st_.in_fast[:8].sum()) == 8

    def test_cost_gate_blocks_marginal_promotions(self):
        """If migration cost dwarfs the latency benefit, nothing moves."""
        expensive = ARMSConfig(init_promo_cost_us=1e12,
                               init_demo_cost_us=1e12)
        n, k = 64, 8
        st_ = init_state(n, expensive)
        counts = np.zeros(n)
        counts[:k] = 50.0
        for _ in range(6):
            st_, plan = arms_step(st_, jnp.asarray(counts), 0.2, 0.1,
                                  cfg=expensive, k=k)
        assert int(st_.in_fast.sum()) == 0

    def test_free_slot_promotions_have_no_victim(self):
        n, k = 64, 8
        st_ = init_state(n, CFG)
        counts = np.zeros(n)
        counts[:4] = 50.0
        plans = []
        for _ in range(4):
            st_, plan = arms_step(st_, jnp.asarray(counts), 0.2, 0.1,
                                  cfg=CFG, k=k)
            plans.append(plan)
        executed = [p for p in plans if int(p.count) > 0]
        assert executed
        for p in executed:
            d = np.asarray(p.demote)[np.asarray(p.valid)]
            assert (d == -1).all()  # fast tier had free slots

    def test_victim_is_coldest(self):
        """When the fast tier is full, the demoted page is the coldest one."""
        n, k = 32, 4
        st_ = init_state(n, CFG)
        counts = np.zeros(n)
        counts[:4] = [60, 50, 40, 30.0]
        for _ in range(5):
            st_, _ = arms_step(st_, jnp.asarray(counts), 0.2, 0.1, cfg=CFG,
                               k=k)
        assert int(st_.in_fast[:4].sum()) == 4
        # page 10 becomes hottest; coldest resident (page 3) must be evicted
        counts2 = counts.copy()
        counts2[10] = 100.0
        counts2[3] = 0.0
        for _ in range(6):
            st_, plan = arms_step(st_, jnp.asarray(counts2), 0.2, 0.1,
                                  cfg=CFG, k=k)
        assert bool(st_.in_fast[10])
        assert not bool(st_.in_fast[3])


# ------------------------------------------------------------------ scheduler
class TestScheduler:
    def test_bs_formula(self):
        """BS = max(1, (BW_max-BW_app)/BW_max * BS_max), clamped."""
        assert int(scheduler.batch_size(0.0, 1.0, 64)) == 64
        assert int(scheduler.batch_size(1.0, 1.0, 64)) == 1
        assert int(scheduler.batch_size(0.5, 1.0, 64)) == 32
        assert int(scheduler.batch_size(2.0, 1.0, 64)) == 1  # over-saturated

    def test_plan_respects_bandwidth_throttle(self):
        """At high app bandwidth, migrations trickle instead of bursting."""
        n, k = 256, 64
        st_ = init_state(n, CFG)
        counts = np.zeros(n)
        counts[:k] = 50.0
        # app uses ~98.5% of bandwidth -> BS = 1
        for i in range(3):
            st_, plan = arms_step(st_, jnp.asarray(counts), 0.2, 0.985,
                                  cfg=CFG, k=k)
            assert int(plan.count) <= 1
        assert int(st_.in_fast.sum()) <= 3

    def test_priority_hottest_first(self):
        """The hottest eligible candidate occupies plan slot 0."""
        n, k = 64, 8
        st_ = init_state(n, CFG)
        counts = np.zeros(n)
        counts[:8] = np.arange(80, 0, -10)
        for _ in range(3):
            st_, plan = arms_step(st_, jnp.asarray(counts), 0.2, 0.99,
                                  cfg=CFG, k=k)  # BS=1
            if int(plan.count) == 1:
                assert int(plan.promote[0]) == 0  # page 0 is hottest
                break
        else:
            pytest.fail("no promotion happened")


# ------------------------------------------------------- property (hypothesis)
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n=st.integers(16, 96),
       kfrac=st.floats(0.1, 0.9),
       intervals=st.integers(1, 12))
def test_invariants_random_traces(seed, n, kfrac, intervals):
    """System invariants hold for arbitrary access traces:

    I1: fast-tier occupancy never exceeds k.
    I2: plans never promote an already-fast page nor demote a non-fast page.
    I3: plan count <= batch_size <= bs_max.
    I4: promote/demote indices are disjoint within a plan.
    """
    k = max(1, int(n * kfrac))
    rng = np.random.default_rng(seed)
    st_ = init_state(n, CFG)
    for _ in range(intervals):
        counts = rng.poisson(rng.uniform(0, 30), n).astype(np.float64)
        before_fast = np.asarray(st_.in_fast)
        st_, plan = arms_step(st_, jnp.asarray(counts),
                              float(rng.uniform(0, 1)),
                              float(rng.uniform(0, 1)), cfg=CFG, k=k)
        valid = np.asarray(plan.valid)
        promote = np.asarray(plan.promote)[valid]
        demote = np.asarray(plan.demote)[valid]
        # I2
        assert not before_fast[promote].any()
        real_demote = demote[demote >= 0]
        assert before_fast[real_demote].all()
        # I4
        assert not set(promote.tolist()) & set(real_demote.tolist())
        # I3
        assert int(plan.count) == valid.sum() <= int(plan.batch_size) \
            <= CFG.bs_max
        # I1
        assert int(st_.in_fast.sum()) <= k


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), steps=st.integers(1, 60))
def test_ewma_bounded_by_observed_range(seed, steps):
    """EWMAs stay within [0, max(x)] for non-negative inputs."""
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0, 100, (steps, 8))
    st_ = init_state(8, CFG)
    for x in xs:
        st_ = classifier.update_scores(st_, jnp.asarray(x), CFG,
                                       jnp.int32(MODE_HISTORY))
    hi = xs.max()
    assert (np.asarray(st_.ewma_s) <= hi + 1e-4).all()
    assert (np.asarray(st_.ewma_l) <= hi + 1e-4).all()
    assert (np.asarray(st_.ewma_s) >= 0).all()


def test_recency_mode_doubles_sampling_and_policy_rate():
    from repro.core import policy_every, sampling_period
    assert int(sampling_period(jnp.int32(MODE_RECENCY))) * 2 == \
        int(sampling_period(jnp.int32(MODE_HISTORY)))
    assert int(policy_every(jnp.int32(MODE_RECENCY))) < \
        int(policy_every(jnp.int32(MODE_HISTORY)))


# ------------------------------------------------------------------ top-k
@pytest.mark.parametrize("rows,n,k", [(1, 64, 5), (5, 1000, 37),
                                      (42, 512, 256), (8, 129, 129)])
def test_top_k_sorted_matches_lax_top_k(rows, n, k):
    """The TPU route of ``utils.topk.top_k`` (a two-key sort) keeps
    ``lax.top_k``'s values, order and tie rule on tie-heavy rows with
    signed zeros and -inf, and a row's answer does not depend on how many
    rows share the call."""
    from repro.utils.topk import top_k_sorted

    rng = np.random.default_rng(rows * n + k)
    x = np.floor(rng.random((rows, n)) * 6).astype(np.float32) - 2.0
    x[rng.random((rows, n)) < 0.1] = -np.inf
    x[rng.random((rows, n)) < 0.1] = -0.0
    x = jnp.asarray(x)
    want_v, want_i = jax.lax.top_k(x, k)
    got_v, got_i = top_k_sorted(x, k)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(np.signbit(got_v), np.signbit(want_v))
    np.testing.assert_array_equal(got_v, want_v)
    one_v, one_i = top_k_sorted(x[-1:], k)
    np.testing.assert_array_equal(one_i[0], got_i[-1])
