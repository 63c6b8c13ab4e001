"""WorkloadSpec protocol: probs invariants, combinator laws, and bitwise
synth-vs-materialize / cross-engine equivalence.

The acceptance bar for the trace-synthesis path: for every named workload,
the scan engine synthesizing ``true = work * probs`` on device must be
BITWISE identical to replaying the host-materialized ``[T, n]`` f32 trace
(same CRN noise), and the numpy reference engine on that trace must agree
exactly on migration counts.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.baselines.arms_policy import ARMSSpec
from repro.baselines.hemem import HeMemPolicy, HeMemSpec
from repro.simulator import (experiment, scan_engine, scenarios, tuning,
                             workload_spec, workloads)
from repro.simulator.engine import oracle_topk_masks, run
from repro.simulator.machine import PMEM_LARGE
from repro.simulator.sampling import synth_noise_field

T, N, K = 96, 256, 32
NAMES = list(workload_spec.NAMED_WORKLOADS)
#: drifting workloads: the rank rows are rotated every interval
DRIFTING = {
    "drifting_hot": lambda: scenarios.drifting_hot(N, K, rate=5.0),
    "drift(xsbench,3)": lambda: workload_spec.drift(
        workload_spec.named("xsbench"), 3.0),
}


def _workload(name):
    return DRIFTING[name]() if name in DRIFTING else \
        workload_spec.named(name, T=T)


def _indexed(x, shift):
    """The drift's page index written out: ``x[..., (arange(n) - shift)
    % n]``, the oracle of ``workload_spec.rotate``."""
    n = x.shape[-1]
    return x[..., (jnp.arange(n, dtype=jnp.int32) - shift) % n]


@jax.jit
def _step_jit(spec, state, t):
    return type(spec).step(spec, state, t)


def _probs_seq(spec, T_, n, seed=0, ts=None):
    """{t: probs[n]} from the pure step protocol (jitted once per treedef)."""
    state = spec.init(n, jax.random.PRNGKey(seed))
    out = {}
    for t in range(T_):
        state, p = _step_jit(spec, state, jnp.int32(t))
        if ts is None or t in ts:
            out[t] = np.asarray(p)
    return out


class TestSpecProperties:
    @pytest.mark.parametrize("name", NAMES)
    def test_probs_nonneg_and_sum_to_one(self, name):
        spec = workload_spec.named(name, T=60)
        for t, p in _probs_seq(spec, 60, 128, ts={0, 1, 29, 30, 59}).items():
            assert (p >= 0).all(), (name, t)
            np.testing.assert_allclose(p.sum(), 1.0, atol=1e-4)

    def test_composed_probs_sum_to_one(self):
        spec = workload_spec.mix(
            [workload_spec.drift(workload_spec.named("xsbench"), 1.5),
             workload_spec.phases([workload_spec.named("gups"),
                                   workload_spec.named("silo-tpcc")], [20])],
            [0.3, 0.7])
        for _, p in _probs_seq(spec, 45, 128, ts={0, 19, 20, 44}).items():
            assert (p >= 0).all()
            np.testing.assert_allclose(p.sum(), 1.0, atol=1e-4)

    def test_deterministic_and_f32(self):
        a = workloads.make("gups", T=40, n=128)
        b = workloads.make("gups", T=40, n=128)
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)

    def test_gups_hot_set_relocates(self):
        spec = workload_spec.gups_spec(shift_every=10)
        ps = _probs_seq(spec, 25, 128, ts={9, 10})
        assert not np.array_equal(ps[9], ps[10])  # event at t=10

    def test_btree_reshuffles_exactly_once(self):
        """Legacy one-shot semantics: a reshuffle at T // 2 and NOTHING
        after, even when T > 2 * (T // 2) (odd T)."""
        tr = workloads.make("btree", T=101, n=64)
        assert np.array_equal(tr[0], tr[49])          # stable before
        assert not np.array_equal(tr[49], tr[50])     # reshuffle at 50
        assert np.array_equal(tr[50], tr[100])        # stable after (t=100!)


class TestCombinators:
    def test_phases_hits_boundaries(self):
        gups = workload_spec.named("gups")
        tpcc = workload_spec.named("silo-tpcc")
        combo = workload_spec.phases([gups, tpcc], [30])
        pc = _probs_seq(combo, 60, 128, ts={0, 29, 30, 59})
        pg = _probs_seq(gups, 60, 128, ts={0, 29})
        pt = _probs_seq(tpcc, 60, 128, ts={30, 59})
        np.testing.assert_allclose(pc[29], pg[29], rtol=1e-5, atol=1e-9)
        np.testing.assert_allclose(pc[30], pt[30], rtol=1e-5, atol=1e-9)
        assert not np.allclose(pc[29], pc[30])

    def test_phases_validates_boundaries(self):
        g = workload_spec.named("gups")
        with pytest.raises(ValueError):
            workload_spec.phases([g, g], [10, 20])
        with pytest.raises(ValueError):
            workload_spec.phases([g, g, g], [20, 10])

    def test_mix_weights_normalize(self):
        a = workload_spec.named("gups")
        b = workload_spec.named("silo-ycsb")
        m1 = workload_spec.mix([a, b], [2.0, 2.0])
        m2 = workload_spec.mix([a, b], [1.0, 1.0])
        np.testing.assert_array_equal(np.asarray(m1.weight),
                                      np.asarray(m2.weight))
        pm = _probs_seq(m1, 3, 128, ts={2})[2]
        pa = _probs_seq(a, 3, 128, ts={2})[2]
        pb = _probs_seq(b, 3, 128, ts={2})[2]
        np.testing.assert_allclose(pm, 0.5 * pa + 0.5 * pb,
                                   rtol=1e-4, atol=1e-9)
        with pytest.raises(ValueError):
            workload_spec.mix([a, b], [1.0])
        with pytest.raises(ValueError):
            workload_spec.mix([a, b], [0.0, 0.0])

    def test_scale_multiplies_work(self):
        a = workload_spec.named("gups")
        s = workload_spec.scale(a, 2.5)
        st_ = s.init(64, jax.random.PRNGKey(0))
        assert float(s.work_of(st_, jnp.int32(0))) == pytest.approx(
            2.5 * float(a.work_of(a.init(64, jax.random.PRNGKey(0)),
                                  jnp.int32(0))), rel=1e-6)

    def test_drift_rolls_distribution(self):
        a = workload_spec.named("xsbench")     # stationary -> drift visible
        d = workload_spec.drift(a, 3.0)
        pa = _probs_seq(a, 11, 128, ts={10})[10]
        pd = _probs_seq(d, 11, 128, ts={10})[10]
        np.testing.assert_allclose(pd, np.roll(pa, 30), rtol=1e-5, atol=1e-9)

    def test_mixed_structure_specs_stack(self):
        """Different component counts pad and sweep in one dispatch."""
        combo = workload_spec.phases([workload_spec.named("gups"),
                                      workload_spec.named("liblinear")], [40])
        rows = scan_engine.sweep_workloads(
            [combo, workload_spec.named("btree", T=80)],
            PMEM_LARGE, K, 80, N)
        assert len(rows) == 2
        assert scan_engine.last_dispatch["lanes"] == 2
        assert scan_engine.last_dispatch["synth"] is True


class TestDegenerateKnobs:
    """Legacy generators crashed at hot_frac=1.0 (gups divided by n-k_hot)
    and window_frac=1.0 (silo_tpcc took % (n-w)); the spec knobs clamp."""

    def test_gups_full_hot_frac(self):
        tr = workloads.gups(20, n=64, hot_frac=1.0)
        assert np.isfinite(tr).all() and (tr >= 0).all()
        np.testing.assert_allclose(tr.sum(axis=1),
                                   workloads.DEFAULT_WORK, rtol=1e-4)
        # every page hot == uniform (not a concentrated leftover page)
        np.testing.assert_allclose(tr, workloads.DEFAULT_WORK / 64,
                                   rtol=1e-4)

    def test_tpcc_full_window_frac(self):
        tr = workloads.silo_tpcc(20, n=64, window_frac=1.0)
        assert np.isfinite(tr).all() and (tr >= 0).all()
        np.testing.assert_allclose(tr.sum(axis=1),
                                   workloads.DEFAULT_WORK, rtol=1e-4)

    @pytest.mark.parametrize("frac", [0.0, 1.0])
    def test_extreme_fracs_all_kinds(self, frac):
        specs = [workload_spec.gups_spec(hot_frac=frac),
                 workload_spec.xsbench_spec(hot_frac=frac),
                 workload_spec.tpcc_spec(window_frac=frac),
                 workload_spec.gapbs_spec(boost_frac=frac)]
        for sp in specs:
            tr = sp.materialize(10, 32)
            assert np.isfinite(tr).all() and (tr >= 0).all()


class TestSynthMaterializeEquivalence:
    """The acceptance bar: synth == materialized replay, bitwise, for every
    named workload — in the scan engine and against the numpy engine."""

    @pytest.mark.parametrize("name", NAMES + list(DRIFTING))
    def test_bitwise_across_paths(self, name, monkeypatch):
        wl = _workload(name)
        u = synth_noise_field(T, N, seed=7)
        synth = scan_engine.simulate_workload(
            HeMemSpec.make(), wl, PMEM_LARGE, K, T, N, sim_seed=7)
        trace = wl.materialize(T, N)
        assert trace.dtype == np.float32
        mat = scan_engine.simulate(HeMemSpec.make(), trace, PMEM_LARGE, K,
                                   sample_u=u)
        # scan engine: synthesized and materialized replays are BITWISE one
        assert synth.exec_time_s == mat.exec_time_s
        assert (synth.promotions, synth.demotions, synth.wasteful) == \
            (mat.promotions, mat.demotions, mat.wasteful)
        assert synth.hot_recall == mat.hot_recall
        assert synth.fast_hit_frac == mat.fast_hit_frac
        np.testing.assert_array_equal(synth.timeline_promotions,
                                      mat.timeline_promotions)
        np.testing.assert_array_equal(synth.timeline_slow_bw,
                                      mat.timeline_slow_bw)
        # numpy reference engine on the same trace + CRN field: exact counts
        ref = run(HeMemPolicy(), trace, PMEM_LARGE, K, sample_u=u)
        assert (synth.promotions, synth.demotions, synth.wasteful) == \
            (ref.promotions, ref.demotions, ref.wasteful)
        np.testing.assert_allclose(synth.exec_time_s, ref.exec_time_s,
                                   rtol=1e-4)
        if name in DRIFTING:
            # the rotated rows against the indexed formula: the same trace,
            # bit for bit, from a fresh compile with the gather put back
            monkeypatch.setattr(workload_spec, "rotate", _indexed)
            indexed = jax.jit(workload_spec._materialize_jit.__wrapped__,
                              static_argnames=("T", "n", "with_boost",
                                               "with_drift"))
            want = indexed(wl, T, N, jax.random.PRNGKey(0), wl.has_boost(),
                           wl.has_drift())
            np.testing.assert_array_equal(trace, np.asarray(want))

    def test_arms_synth_matches_materialized(self):
        wl = workload_spec.named("gups", T=T)
        u = synth_noise_field(T, N, seed=3)
        synth = scan_engine.simulate_workload(
            ARMSSpec.make(), wl, PMEM_LARGE, K, T, N, sim_seed=3)
        mat = scan_engine.simulate(ARMSSpec.make(), wl.materialize(T, N),
                                   PMEM_LARGE, K, sample_u=u)
        assert synth.exec_time_s == mat.exec_time_s
        assert (synth.promotions, synth.demotions, synth.wasteful) == \
            (mat.promotions, mat.demotions, mat.wasteful)
        np.testing.assert_array_equal(synth.timeline_mode, mat.timeline_mode)

    def test_device_oracle_matches_host_tie_rule(self):
        rng = np.random.default_rng(0)
        for _ in range(20):
            x = rng.integers(0, 4, size=64).astype(np.float32)  # many ties
            k = int(rng.integers(1, 64))
            host = oracle_topk_masks(x[None], k)[0]
            dev = np.asarray(scan_engine._topk_mask(jnp.asarray(x), k))
            np.testing.assert_array_equal(host, dev)
            assert host.sum() == k

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 80), st.integers(0, 2 ** 31 - 1))
    def test_oracle_tie_rule_property(self, n, seed):
        rng = np.random.default_rng(seed)
        x = rng.integers(0, max(2, n // 4), size=n).astype(np.float32)
        k = int(rng.integers(1, n + 1))
        host = oracle_topk_masks(x[None], k)[0]
        dev = np.asarray(scan_engine._topk_mask(jnp.asarray(x), k))
        np.testing.assert_array_equal(host, dev)


class TestWorkloadSweeps:
    def test_lane_matches_single_run(self):
        """Lane (w, b) of a W x B sweep == the standalone synth run."""
        wls = [workload_spec.named("gups", T=T),
               workload_spec.named("silo-tpcc", T=T)]
        cfgs = [dict(hot_threshold=4), dict(hot_threshold=16)]
        grid = scan_engine.sweep_workload_configs(
            HeMemSpec.make, cfgs, wls, PMEM_LARGE, K, T, N, sim_seed=5)
        assert scan_engine.last_dispatch["lanes"] == 4
        assert scan_engine.last_dispatch["workloads"] == 2
        assert scan_engine.last_dispatch["configs"] == 2
        single = scan_engine.simulate_workload(
            HeMemSpec.make(hot_threshold=16), wls[1], PMEM_LARGE, K, T, N,
            sim_seed=5)
        lane = grid[1][1]
        assert lane.exec_time_s == single.exec_time_s
        assert lane.promotions == single.promotions

    def test_sweep_never_materializes(self):
        before = workload_spec.MATERIALIZE_CALLS
        scan_engine.sweep_workload_configs(
            HeMemSpec.make, [dict(), dict(hot_threshold=4)],
            [workload_spec.named("gups", T=40)], PMEM_LARGE, 16, 40, 128)
        assert workload_spec.MATERIALIZE_CALLS == before

    def test_tune_workload_lanes(self):
        out = tuning.tune("hemem", None, PMEM_LARGE, K, budget=3,
                          sim_seed=2, workloads=["gups", "xsbench"],
                          T=64, n=N)
        assert set(out) == {"gups", "xsbench"}
        lanes = scan_engine.last_dispatch["lanes"]
        assert lanes == 2 * len(out["gups"][2])
        for _nm, (best_cfg, best_res, rows) in out.items():
            assert best_res.exec_time_s == min(r.exec_time_s
                                               for _, r in rows)
            assert best_cfg == rows[0][0]

    def test_tune_disambiguates_duplicate_labels(self):
        """Two combinator scenarios sharing an auto-label must not
        overwrite each other's rows in the result dict."""
        a = workload_spec.phases([workload_spec.named("gups"),
                                  workload_spec.named("silo-tpcc")], [10])
        b = workload_spec.phases([workload_spec.named("gups"),
                                  workload_spec.named("silo-tpcc")], [30])
        out = tuning.tune("hemem", None, PMEM_LARGE, 16, budget=2,
                          workloads=[a, b], T=40, n=128)
        assert len(out) == 2

    def test_tune_rejects_trace_plus_workloads(self):
        with pytest.raises(ValueError):
            tuning.tune("hemem", np.zeros((4, 8)), PMEM_LARGE, 2,
                        workloads=["gups"], T=4, n=8)
        with pytest.raises(ValueError):
            tuning.tune("hemem", None, PMEM_LARGE, 2, workloads=["gups"])


class TestRankRotation:
    """The rank rows at the drift shift: read as they are where nothing
    drifts, rotated gather-free where something does; both bitwise the
    indexed formula ``rank[(arange(n) - shift) % n]``."""

    @pytest.mark.parametrize("n", [1, 2, 3, 7, 64, 65, 256, 1000, 4096,
                                   65536])
    def test_rotate_matches_indexed_formula(self, n):
        rng = np.random.default_rng(n)
        rank = jnp.asarray(np.stack([np.stack([rng.permutation(n)
                                               for _ in range(2)])
                                     for _ in range(3)]), jnp.int32)
        # shifts 0, 1, n - 1, a random draw, and rates past n
        rate = jnp.asarray([[0.0, 1.0], [n - 1.0, rng.uniform(0, n)],
                            [n + 3.5, 2.5 * n + 1.0]], jnp.float32)
        t = jnp.int32(1)

        def shift_of(r):       # the drift shift of _comp_probs
            return jnp.floor(r * t.astype(jnp.float32)).astype(
                jnp.int32) % n

        got = jax.vmap(jax.vmap(
            lambda row, r: workload_spec.rotate(row, shift_of(r))))(
            rank, rate)
        want = jax.vmap(jax.vmap(
            lambda row, r: _indexed(row, shift_of(r))))(rank, rate)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        shifts = np.asarray(jax.vmap(jax.vmap(shift_of))(rate))
        if n > 2:
            assert {0, 1, n - 1} <= set(shifts.ravel().tolist())

    @pytest.mark.parametrize("name", NAMES)
    def test_still_rows_read_directly(self, name):
        spec = workload_spec.named(name, T=60)
        assert not spec.has_drift()
        assert workload_spec.drift(spec, 3.0).has_drift()
        state = spec.init(128, jax.random.PRNGKey(0))
        for t in (0, 7, 59):
            t = jnp.int32(t)
            np.testing.assert_array_equal(
                np.asarray(spec.probs_of(state, t, with_drift=False)),
                np.asarray(spec.probs_of(state, t, with_drift=True)))

    @pytest.mark.parametrize("drifting", [False, True])
    def test_program_gathers_no_rank_rows(self, monkeypatch, drifting):
        """The compiled sweep program holds no gather over the ``[W, S,
        n]`` rank stack; where a lane drifts, the rotation carries the
        ``synth/rotate`` scope."""
        n = 256
        wls = ["gups", "btree",
               scenarios.drifting_hot(n, 32) if drifting else "xsbench"]
        calls, jitted = [], scan_engine._sim_synth_jit

        def record(*a, **kw):
            calls.append((a, kw))
            return jitted(*a, **kw)

        monkeypatch.setattr(scan_engine, "_sim_synth_jit", record)
        experiment.sweep(["arms"], workloads=wls, machines=["pmem-large"],
                         k=32, T=4, n=n)
        (a, kw), = calls
        assert kw["wl_drift"] is drifting
        text = jitted.lower(*a, **kw).compile().as_text()
        shape = {m.group(1): m.group(2) for m in re.finditer(
            r"%(\S+) = \w+\[([\d,]*)\]", text)}
        gathers = re.findall(r" gather\(%(\S+?),.*op_name=\"([^\"]*)\"",
                             text)
        assert gathers           # the policies' own row reads are there
        for operand, op_name in gathers:
            assert shape[operand] != f"3,2,{n}", operand
            assert "/synth/" not in op_name, op_name
        rotate = re.findall(r'op_name="[^"]*/synth/[^"]*rotate\)*/', text)
        assert bool(rotate) is drifting
