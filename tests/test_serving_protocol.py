"""Serving-on-the-protocol regressions (PR 9 tentpole).

* ARMS-via-protocol == frozen legacy ``arms_step`` serving loop on a fixed
  decode trace: plan-SEQUENCE equality (padded promote/demote arrays) plus
  the residency trajectory, step by step.
* Every POLICY_REGISTRY family drives a TieredPool (the ``--policy``
  acceptance surface) and preserves the capacity/single-residency
  invariants.
* The measured serving cost model (tiered_pool.serving_interval_outcome)
  is the byte-volume mirror of ``simjax._tier_times`` — cross-checked
  under the CACHELINE/PAGE_BYTES unit conversion — and the default
  serving machine's fast tier is pinned to the roofline HBM bandwidth.
* satellite (a): K and V slow pools DIVERGE under serving (the
  k_new-passed-twice bug regression).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import roofline
from repro.core import arms_step
from repro.core import init_state as arms_init
from repro.simulator import machines, simjax
from repro.simulator.experiment import POLICY_REGISTRY
from repro.simulator.simjax import CACHELINE, PAGE_BYTES
from repro.tiering import paged_kv as PK
from repro.tiering import tiered_pool as TP

CFG = PK.PagedKVConfig(page_size=8, n_pages=8, fast_pages=3, policy_every=4)
B, KV, H, DH = 2, 2, 4, 16


def _decode_trace(steps, seed=7, policy="arms"):
    """Drive serve_decode_step; return per-step (plan, in_fast, access)."""
    rng = np.random.default_rng(seed)
    kv = PK.init_paged_kv(CFG, B, KV, DH, dtype=jnp.float32, policy=policy)
    recs = []
    for t in range(steps):
        q = jnp.asarray(rng.standard_normal((B, H, DH)), jnp.float32)
        k_new = jnp.asarray(rng.standard_normal((B, KV, DH)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((B, KV, DH)), jnp.float32)
        _, kv, plan = PK.serve_decode_step(kv, q, k_new, v_new,
                                           jnp.int32(t), CFG)
        recs.append((np.asarray(plan.promote), np.asarray(plan.demote),
                     np.asarray(kv.in_fast), np.asarray(plan.access)))
    return kv, recs


class TestARMSLegacyEquality:
    """The tentpole regression: ARMS through the PolicySpec protocol and
    the shared TieredPool executor reproduces the pre-refactor
    ``core.arms_step`` serving loop bit-for-bit — same padded plan arrays
    at every policy fire, same residency after every decode step."""

    def test_plan_sequence_matches_frozen_legacy_loop(self):
        T = 48
        kv, recs = _decode_trace(T)
        n, k, E = CFG.n_pages, CFG.fast_pages, CFG.policy_every
        pb = PK.page_kv_bytes(kv)
        mach = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), machines.get(CFG.machine))

        # ---- frozen legacy serving loop (pre-refactor paged_kv.py),
        # driven with the same access stream and the same measured
        # bandwidth signals the pool computes -------------------------
        state = arms_init(n, CFG.arms)
        in_fast = jnp.zeros((n,), bool)
        counts = jnp.zeros((n,), jnp.float32)
        rf_w = jnp.zeros((), jnp.float32)
        rs_w = jnp.zeros((), jnp.float32)
        for t in range(T):
            promote_t, demote_t, fast_t, access_t = recs[t]
            # read volumes use this step's PRE-fire residency, exactly as
            # serve_decode_step computes them before pool_step
            n_valid = min(t // CFG.page_size + 1, n)
            valid = jnp.arange(n) < n_valid
            rf_w = rf_w + (valid & in_fast).sum().astype(jnp.float32) * pb
            rs_w = rs_w + (valid & ~in_fast).sum().astype(jnp.float32) * pb
            counts = counts + jnp.asarray(access_t, jnp.float32)
            if (t + 1) % E == 0:
                slow_bw = jnp.where(in_fast, 0.0, counts).sum() \
                    / jnp.maximum(counts.sum(), 1e-9)
                _, app_raw = TP.serving_interval_outcome(mach, rf_w, rs_w)
                app_bw = jnp.clip(app_raw, 0.0, 1.0)
                state, plan = arms_step(state, counts, slow_bw, app_bw,
                                        cfg=CFG.arms, k=k)
                promote = jnp.where(plan.valid, plan.promote,
                                    -1).astype(jnp.int32)
                demote = jnp.where(plan.valid & (plan.demote >= 0),
                                   plan.demote, -1).astype(jnp.int32)
                in_fast, _, _ = simjax.apply_padded_migrations(
                    in_fast, promote, demote, k)
                counts = jnp.zeros_like(counts)
                rf_w = jnp.zeros((), jnp.float32)
                rs_w = jnp.zeros((), jnp.float32)
                np.testing.assert_array_equal(np.asarray(promote), promote_t,
                                              err_msg=f"promote plan, t={t}")
                np.testing.assert_array_equal(np.asarray(demote), demote_t,
                                              err_msg=f"demote plan, t={t}")
            else:
                assert (promote_t == -1).all() and (demote_t == -1).all(), \
                    f"policy fired off-cadence at t={t}"
            np.testing.assert_array_equal(np.asarray(in_fast), fast_t,
                                          err_msg=f"residency, t={t}")

    def test_arms_resolves_to_serving_spec(self):
        """init_pool("arms") must pick the legacy-cadence serving spec,
        not the simulator-cadence ARMSSpec."""
        from repro.baselines.arms_policy import ARMSServeSpec
        pool = TP.init_pool("arms", 8, 3, pool_every=4)
        assert type(pool.spec) is ARMSServeSpec
        assert pool.spec.pool_every == 4


class TestAllFamiliesDriveThePool:
    """Acceptance: every POLICY_REGISTRY family must run the serving pool
    (the surface behind ``launch/serve.py --policy``)."""

    @pytest.mark.parametrize("name", sorted(POLICY_REGISTRY))
    def test_family_runs_and_keeps_invariants(self, name):
        n, k = 16, 4
        pool = TP.init_pool(name, n, k, pool_every=2)
        fast = jnp.asarray(np.arange(1, k + 2, dtype=np.float32)
                           .repeat(3).reshape(k + 1, 3)[:k])
        slow = jnp.zeros((n, 3), jnp.float32) \
            + jnp.arange(n, dtype=jnp.float32)[:, None]
        rng = np.random.default_rng(3)
        for t in range(8):
            acc = jnp.asarray(
                np.abs(rng.standard_normal(n)) * (np.arange(n) < 5),
                jnp.float32)
            pool, (buf,), plan = TP.pool_step(
                pool, acc, 4096.0, 65536.0, k=k, bufs=((fast, slow),),
                copy_back=True, page_bytes=4096.0)
            fast, slow = buf
        in_fast = np.asarray(pool.in_fast)
        slot = np.asarray(pool.slot)
        assert in_fast.sum() <= k
        fast_slots = slot[in_fast]
        assert len(set(fast_slots.tolist())) == len(fast_slots)
        assert (fast_slots < k).all()
        # fast-resident pages' data actually lives in their fast slot
        for page in np.flatnonzero(in_fast):
            np.testing.assert_allclose(np.asarray(fast[slot[page]]),
                                       float(page))
        tel = TP.telemetry(pool)
        assert tel["promotions"] >= 0 and 0.0 <= tel["thrash"] <= 1.0

    def test_serve_cli_exposes_every_family(self):
        """--policy choices == the registry (the CLI acceptance check)."""
        import inspect

        from repro.launch import serve as SV
        src = inspect.getsource(SV.main)
        assert "choices=sorted(POLICY_REGISTRY)" in src


class TestServingCostModel:
    """satellite (c): the hardcoded app_bw_frac=0.5 is gone — the signal
    derives from measured per-tier read volumes, and the serving cost
    arithmetic is the simulator's own bandwidth model."""

    def test_matches_simjax_tier_times_under_unit_conversion(self):
        mach = machines.get("hbm-pcie")
        mach32 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), mach)
        rng = np.random.default_rng(0)
        for _ in range(16):
            rf, rs, up_b, down_b = (float(x) for x in
                                    rng.uniform(0, 1e9, 4))
            wall, app_raw = TP.serving_interval_outcome(
                mach32, jnp.float32(rf), jnp.float32(rs),
                jnp.float32(up_b), jnp.float32(down_b))
            # simjax charges accesses in CACHELINEs and migrations in
            # PAGE_BYTES pages; convert byte volumes to those units.
            acc = [jnp.float32(rf / CACHELINE), jnp.float32(rs / CACHELINE)]
            mig_up = jnp.asarray([up_b / PAGE_BYTES], jnp.float32)
            mig_down = jnp.asarray([down_b / PAGE_BYTES], jnp.float32)
            _, times = simjax._tier_times(mach32, acc, mig_up, mig_down)
            np.testing.assert_allclose(
                float(app_raw), float(times[0] / jnp.maximum(times[1],
                                                             1e-12)),
                rtol=1e-5)
            np.testing.assert_allclose(
                float(wall),
                max(float(times[0]), float(times[1]), 1e-12), rtol=1e-5)

    def test_default_machine_fast_tier_is_roofline_hbm(self):
        mach = machines.get(TP.DEFAULT_MACHINE)
        assert float(np.asarray(mach.bw_read)[0]) == roofline.HBM_BW

    def test_app_bw_derives_from_measured_volumes(self):
        """Fast-heavy windows read high app_bw, slow-heavy read low — the
        signal moves with the measured traffic (no constant 0.5)."""
        pool = TP.init_pool("arms", 8, 3, pool_every=100)
        acc = jnp.ones((8,), jnp.float32)
        fast_heavy = TP.pool_observe(pool, acc, read_fast=1e9, read_slow=1e3)
        slow_heavy = TP.pool_observe(pool, acc, read_fast=1e3, read_slow=1e9)
        _, app_f = TP.pool_signals(fast_heavy)
        _, app_s = TP.pool_signals(slow_heavy)
        assert float(app_f) > 0.9
        assert float(app_s) < 0.1
        assert abs(float(app_f) - 0.5) > 0.1   # not the old constant


class TestKVDivergence:
    """satellite (a): serve.py once passed k_new as BOTH k_new and v_new;
    the K and V pools were bitwise-identical mirrors.  They must diverge
    under real (distinct) streams."""

    def test_serve_kv_pools_diverge(self):
        from repro.launch.serve import serve
        rep = serve("granite-8b", n_tokens=12, batch=1, page_size=8,
                    quiet=True)
        ks = np.asarray(rep.kv.k_slow)
        vs = np.asarray(rep.kv.v_slow)
        assert ks.any() and vs.any()
        assert not np.array_equal(ks, vs), \
            "K and V slow pools are identical — v_new regression"

    def test_write_token_keeps_streams_distinct(self):
        kv = PK.init_paged_kv(CFG, B, KV, DH, dtype=jnp.float32)
        rng = np.random.default_rng(5)
        for t in range(CFG.page_size):
            k_new = jnp.asarray(rng.standard_normal((B, KV, DH)),
                                jnp.float32)
            v_new = jnp.asarray(rng.standard_normal((B, KV, DH)),
                                jnp.float32)
            kv = PK.write_token(kv, k_new, v_new, jnp.int32(t), CFG)
        assert not np.array_equal(np.asarray(kv.k_slow),
                                  np.asarray(kv.v_slow))


class TestServePolicies:
    """serve() end-to-end under a binary baseline and a tier-native
    family (the full --policy surface; pool-level coverage above)."""

    @pytest.mark.parametrize("policy", ["memtis", "jenga"])
    def test_serve_with_family(self, policy):
        from repro.launch.serve import serve
        rep = serve("granite-8b", n_tokens=12, batch=1, page_size=8,
                    policy=policy, quiet=True)
        assert rep.policy == policy
        assert rep.fast_mass.shape == (12,)
        assert np.isfinite(rep.slowdown) and rep.slowdown > 0.0


class TestServeCompilesOnce:
    """The decode step is jitted once per (config, shapes): a warm
    ``serve()`` builds the same executables whatever its token count (the
    unjitted step re-traced its layer scan, one compile per token)."""

    def test_warm_serve_compiles_independent_of_tokens(self):
        from repro.launch.serve import serve
        from repro.utils.compilation import count_compiles
        serve("stablelm-1.6b", n_tokens=4, batch=2, quiet=True)   # warm
        built = {}
        for n in (8, 16):
            with count_compiles() as ctr:
                rep = serve("stablelm-1.6b", n_tokens=n, batch=2,
                            quiet=True)
            built[n] = ctr.count
            assert rep.decode_compiles == 0
            assert rep.tokens.shape == (2, n)
            assert np.isfinite(rep.last_logits).all()
        assert built[8] == built[16]
