"""Fused interval fast path (kernels/interval_step): kernel-vs-ref
property tests across odd shapes, and fused-vs-unfused scan-engine
bitwise equivalence for every policy family on 2- and 3-tier machines.

Integer/bool outputs (masks, tiers, executed plans, migration counts)
must match BITWISE between the interpret-mode Pallas kernels and the jnp
references.  f32 outputs are held to a tight allclose only: XLA contracts
fma / reciprocal-division differently across separately compiled
programs, so last-ulp deviation between the interpret kernel and the
plain-jnp reference is expected (the CPU scan route uses the references
themselves, so engine-level equivalence stays bitwise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.interval_step import kernel, ref
from repro.kernels.migrate.kernel import migrate_kernel
from repro.kernels.migrate.ref import migrate_ref
from repro.simulator import experiment, machines, scan_engine
from repro.simulator.sampling import uniform_field

F32 = dict(rtol=1e-6, atol=1e-6)


def _tiered(rng, B, n, k):
    """Lane-batched 2-tier machine + caps for accounting tests."""
    mach, caps = scan_engine._mach_lanes("pmem-large", B, n, k)
    return mach, caps


class TestTopkMask:
    # odd n (not multiples of 8/128), k at both extremes, heavy ties
    @pytest.mark.parametrize("B,n,k", [(1, 7, 1), (3, 37, 5), (2, 37, 37),
                                       (2, 200, 64), (1, 128, 128),
                                       (4, 513, 1)])
    def test_ref_matches_lax_topk(self, B, n, k):
        rng = np.random.default_rng(B * 1000 + n)
        # quantized values force threshold-equal groups the tie rule
        # must break identically to lax.top_k
        x = jnp.asarray(rng.integers(0, 5, (B, n)), jnp.float32) * 0.25
        want = jax.vmap(lambda r: scan_engine._topk_mask(r, k))(x)
        got = ref.topk_mask_ref(x, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("B,n,k", [(1, 7, 1), (3, 37, 5), (2, 37, 37),
                                       (2, 200, 64), (1, 128, 128)])
    def test_kernel_vs_ref_bitwise(self, B, n, k):
        rng = np.random.default_rng(n + k)
        x = jnp.asarray(rng.integers(0, 4, (B, n)), jnp.float32) * 0.5
        want = ref.topk_mask_ref(x, k)
        got = kernel.topk_mask_kernel(x, k, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_negative_and_signed_zero_ties(self):
        x = jnp.asarray([[-1.5, 0.0, -0.0, 2.0, -1.5, 0.0, -3.0]],
                        jnp.float32)
        for k in range(1, 8):
            want = jax.vmap(lambda r: scan_engine._topk_mask(r, k))(x)
            np.testing.assert_array_equal(
                np.asarray(ref.topk_mask_ref(x, k)), np.asarray(want))
            np.testing.assert_array_equal(
                np.asarray(kernel.topk_mask_kernel(x, k, interpret=True)),
                np.asarray(want))


def _plans(rng, B, n, P, D):
    """Sentinel-padded plans honouring the unique-valid-index contract."""
    promote = np.full((B, P), -1, np.int64)
    demote = np.full((B, D), -1, np.int64)
    for b in range(B):
        perm = rng.permutation(n)
        npro = rng.integers(0, min(P, n) + 1) if P else 0
        nde = rng.integers(0, min(D, n - npro) + 1) if D else 0
        promote[b, :npro] = perm[:npro]
        demote[b, :nde] = perm[npro:npro + nde]
    return jnp.asarray(promote, jnp.int32), jnp.asarray(demote, jnp.int32)


class TestTierMigrate:
    @pytest.mark.parametrize("B,n,R,P,D",
                             [(2, 13, 2, 3, 4), (3, 29, 3, 5, 5),
                              (1, 16, 4, 0, 0), (2, 10, 3, 1, 10),
                              (1, 7, 2, 7, 7)])
    def test_kernel_vs_ref_bitwise(self, B, n, R, P, D):
        rng = np.random.default_rng(B * 100 + n + R)
        tier = jnp.asarray(rng.integers(0, R, (B, n)), jnp.int32)
        caps = jnp.asarray(
            np.stack([np.append(rng.integers(1, n, R - 1), n)
                      for _ in range(B)]), jnp.int32)
        promote, demote = _plans(rng, B, n, P, D)
        want = ref.tier_migrate_ref(tier, promote, demote, caps)
        got = kernel.tier_migrate_kernel(tier, promote, demote, caps,
                                         interpret=True)
        for g, w, nm in zip(got, want,
                            ("tier", "pexec", "dexec", "up", "down")):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=nm)

    def test_empty_plans_are_noop(self):
        tier = jnp.asarray([[1, 0, 1, 1, 0]], jnp.int32)
        caps = jnp.asarray([[2, 5]], jnp.int32)
        empty = jnp.zeros((1, 0), jnp.int32)
        t, pex, dex, up, down = kernel.tier_migrate_kernel(
            tier, empty, empty, caps, interpret=True)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(tier))
        assert int(up.sum()) == 0 and int(down.sum()) == 0


class TestIntervalAccount:
    @pytest.mark.parametrize("B,n", [(1, 7), (3, 130), (2, 37)])
    def test_kernel_vs_ref(self, B, n):
        k = max(1, n // 4)
        rng = np.random.default_rng(n)
        mach, caps = _tiered(rng, B, n, k)
        R = caps.shape[-1]
        true = jnp.asarray(rng.gamma(1.5, 2.0, (B, n)), jnp.float32)
        tier = jnp.asarray(rng.integers(0, R, (B, n)), jnp.int32)
        up = jnp.asarray(rng.integers(0, 5, (B, R - 1)), jnp.float32)
        down = jnp.asarray(rng.integers(0, 5, (B, R - 1)), jnp.float32)
        oracle = ref.topk_mask_ref(true, k)
        want = ref.interval_account_ref(mach, true, tier, up, down,
                                        oracle, k)
        got = kernel.interval_account_kernel(
            mach.lat_ns, mach.bw_read, mach.bw_write, mach.mlp, true, tier,
            up, down, oracle, k, interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **F32)


class TestEwmaUpdate:
    @pytest.mark.parametrize("B,n", [(1, 17), (3, 1000), (2, 129)])
    @pytest.mark.parametrize("lane_params", [False, True])
    def test_kernel_vs_ref(self, B, n, lane_params):
        rng = np.random.default_rng(B + n)
        s = jnp.asarray(rng.random((B, n)), jnp.float32)
        l = jnp.asarray(rng.random((B, n)), jnp.float32)
        c = jnp.asarray(rng.poisson(5, (B, n)), jnp.float32)
        if lane_params:
            kw = dict(alpha_s=jnp.asarray(rng.random(B), jnp.float32),
                      alpha_l=jnp.asarray(rng.random(B), jnp.float32),
                      w_s=jnp.asarray(rng.random(B), jnp.float32),
                      w_l=jnp.asarray(rng.random(B), jnp.float32))
        else:
            kw = dict(alpha_s=0.7, alpha_l=0.1, w_s=0.2, w_l=0.8)
        want = ref.ewma_score_update_ref(s, l, c, **kw)
        got = kernel.ewma_update_kernel(s, l, c, interpret=True, **kw)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **F32)


@pytest.fixture
def small_blocks(monkeypatch):
    """The blocked kernels walk rows in blocks of 8 page rows (1024
    pages); JAX's caches are cleared so that every kernel is traced, and
    records its blocks, under that budget."""
    monkeypatch.setattr(kernel, "BLOCK_ROWS", 8)
    jax.clear_caches()
    yield
    jax.clear_caches()


#: 3001 pages: three blocks of 1024 under ``small_blocks``, the last
#: ending off the block and off the 128-page tile
N_BLOCKED = 3001


class TestBlockedRows:
    """Rows of several page blocks: each kernel against its reference,
    integers bitwise, f32 to the file's ulp contract."""

    @pytest.mark.parametrize("k", [1, 700, 1024, 1500, 2048, 2999, 3001])
    @pytest.mark.parametrize("levels", [1, 3, 0],
                             ids=["all-tied", "3-levels", "distinct"])
    def test_topk_mask(self, small_blocks, k, levels):
        rng = np.random.default_rng(k + levels)
        if levels:
            # threshold-equal runs across every block boundary: the
            # boundary tie falls at a block's edge (k = 1024, 2048) or
            # inside one
            x = rng.integers(0, levels, (2, N_BLOCKED)) * 0.5 - 0.5
        else:
            x = rng.standard_normal((2, N_BLOCKED))
        x = jnp.asarray(x, jnp.float32)
        with kernel.count_blocks() as blocks:
            got = kernel.topk_mask_kernel(x, k, interpret=True)
        assert blocks == {"topk_mask_kernel": [3]}
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.topk_mask_ref(x, k)))

    def test_topk_mask_signed_zero_ties(self, small_blocks):
        # -0.0 below +0.0, and both below every positive key, across blocks
        row = np.zeros(N_BLOCKED, np.float32)
        row[::2] = -0.0
        row[1500] = 1.0
        x = jnp.asarray(np.stack([row, row[::-1]]))
        for k in (1, 2, 1400, 1501, 3000):
            np.testing.assert_array_equal(
                np.asarray(kernel.topk_mask_kernel(x, k, interpret=True)),
                np.asarray(ref.topk_mask_ref(x, k)), err_msg=str(k))

    @pytest.mark.parametrize("B,n", [(3, N_BLOCKED), (2, 2048)])
    def test_interval_account(self, small_blocks, B, n):
        k = n // 4
        rng = np.random.default_rng(n + B)
        mach, caps = _tiered(rng, B, n, k)
        R = caps.shape[-1]
        true = jnp.asarray(rng.gamma(1.5, 2.0, (B, n)), jnp.float32)
        tier = jnp.asarray(rng.integers(0, R, (B, n)), jnp.int32)
        up = jnp.asarray(rng.integers(0, 5, (B, R - 1)), jnp.float32)
        down = jnp.asarray(rng.integers(0, 5, (B, R - 1)), jnp.float32)
        oracle = ref.topk_mask_ref(true, k)
        want = ref.interval_account_ref(mach, true, tier, up, down,
                                        oracle, k)
        with kernel.count_blocks() as blocks:
            got = kernel.interval_account_kernel(
                mach.lat_ns, mach.bw_read, mach.bw_write, mach.mlp, true,
                tier, up, down, oracle, k, interpret=True)
        assert blocks == {"interval_account_kernel": [-(-n // 1024)]}
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **F32)

    @pytest.mark.parametrize("lane_params", [False, True])
    def test_ewma_update(self, small_blocks, lane_params):
        B, n = 2, N_BLOCKED
        rng = np.random.default_rng(n + lane_params)
        s, l = (jnp.asarray(rng.random((B, n)), jnp.float32)
                for _ in range(2))
        c = jnp.asarray(rng.poisson(5, (B, n)), jnp.float32)
        if lane_params:
            kw = {nm: jnp.asarray(rng.random(B), jnp.float32)
                  for nm in ("alpha_s", "alpha_l", "w_s", "w_l")}
        else:
            kw = dict(alpha_s=0.7, alpha_l=0.1, w_s=0.2, w_l=0.8)
        want = ref.ewma_score_update_ref(s, l, c, **kw)
        with kernel.count_blocks() as blocks:
            got = kernel.ewma_update_kernel(s, l, c, interpret=True, **kw)
        assert blocks == {"ewma_update_kernel": [3]}
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), **F32)

    @pytest.mark.parametrize("n,blocks", [(65536, 1), (131072, 1),
                                          (131073, 2), (2097152, 16)])
    def test_block_count_from_shape(self, n, blocks):
        """The benchmark's 65,536-page rows keep one block; 2**21 pages
        take 16 (traced only, nothing runs)."""
        jax.clear_caches()
        f32, B = jnp.float32, 2
        row = jax.ShapeDtypeStruct((B, n), f32)
        lane = jax.ShapeDtypeStruct((B,), f32)
        with kernel.count_blocks() as got:
            jax.eval_shape(lambda x: kernel.topk_mask_kernel(x, n // 4),
                           row)
            jax.eval_shape(
                lambda s, l, c, a: kernel.ewma_update_kernel(
                    s, l, c, alpha_s=a, alpha_l=0.1, w_s=a, w_l=0.5),
                row, row, row, lane)
        assert got == {"topk_mask_kernel": [blocks],
                       "ewma_update_kernel": [blocks]}
        nb, rows = kernel._blocks(n)
        assert nb * rows * kernel.LANE >= n and rows <= kernel.BLOCK_ROWS
        assert nb == 1 or rows % 8 == 0


class TestMigrateKernelOddShapes:
    """Existing kernels/migrate data-plane kernel: odd page/feat tiles
    (not multiples of the f32 8x128 TPU tile) and empty batches."""

    @pytest.mark.parametrize("Ps,Pd,M,page,feat",
                             [(5, 3, 2, 3, 17), (7, 7, 7, 1, 1),
                              (6, 9, 5, 13, 31)])
    def test_vs_ref_odd(self, Ps, Pd, M, page, feat):
        rng = np.random.default_rng(Ps * 10 + feat)
        src = jnp.asarray(rng.standard_normal((Ps, page, feat)),
                          jnp.float32)
        dst = jnp.asarray(rng.standard_normal((Pd, page, feat)),
                          jnp.float32)
        src_idx = jnp.asarray(rng.choice(Ps, M, replace=False), jnp.int32)
        dst_idx = jnp.asarray(rng.choice(Pd, M, replace=False), jnp.int32)
        valid = jnp.asarray(rng.random(M) < 0.6)
        want = migrate_ref(src, dst, src_idx, dst_idx, valid)
        got = migrate_kernel(src, dst, src_idx, dst_idx, valid,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_empty_batch_is_noop(self):
        rng = np.random.default_rng(3)
        src = jnp.asarray(rng.standard_normal((3, 5, 17)), jnp.float32)
        dst = jnp.asarray(rng.standard_normal((4, 5, 17)), jnp.float32)
        e = jnp.zeros(0, jnp.int32)
        got = migrate_kernel(src, dst, e, e, jnp.zeros(0, bool),
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(dst))


FAMILIES = ["arms", "hemem", "memtis", "tpp", "all-slow", "oracle"]
MACHS = ["pmem-large", "dram-cxl-pmem"]      # 2-tier and 3-tier
T_, N_, K_ = 64, 128, 16


def _exact(a, b):
    assert a.name == b.name
    assert a.exec_time_s == b.exec_time_s
    assert a.promotions == b.promotions
    assert a.demotions == b.demotions
    assert a.wasteful == b.wasteful
    assert a.hot_recall == b.hot_recall
    assert a.fast_hit_frac == b.fast_hit_frac
    np.testing.assert_array_equal(a.timeline_slow_bw, b.timeline_slow_bw)
    np.testing.assert_array_equal(a.timeline_fast_hits,
                                  b.timeline_fast_hits)
    np.testing.assert_array_equal(a.timeline_promotions,
                                  b.timeline_promotions)
    np.testing.assert_array_equal(a.timeline_mode, b.timeline_mode)


class TestFusedScanEquivalence:
    """The headline guarantee: ``use_interval_kernel`` never changes a
    bit of any simulation — every policy family, 2- AND 3-tier chains,
    trace-replay AND device-synthesis modes."""

    def test_all_families_all_machines_trace_mode(self):
        rng = np.random.default_rng(0)
        trace = rng.gamma(1.5, 2.0, size=(T_, N_)).astype(np.float32)
        u = uniform_field(T_, N_, seed=7)
        fused = experiment.sweep(FAMILIES, trace=trace, machines=MACHS,
                                 k=K_, sample_u=u, timelines=True)
        plain = experiment.sweep(FAMILIES, trace=trace, machines=MACHS,
                                 k=K_, sample_u=u, timelines=True,
                                 use_interval_kernel=False)
        assert experiment.scan_engine.last_dispatch["interval_kernel"] \
            is False
        for p in FAMILIES:
            for m in MACHS:
                _exact(fused.at(policy=p, machine=m),
                       plain.at(policy=p, machine=m))

    def test_synth_mode(self):
        fused = experiment.sweep(["arms", "hemem"], workloads=["gups"],
                                 machines=MACHS, k=K_, T=T_, n=N_,
                                 timelines=True)
        plain = experiment.sweep(["arms", "hemem"], workloads=["gups"],
                                 machines=MACHS, k=K_, T=T_, n=N_,
                                 timelines=True, use_interval_kernel=False)
        for p in ("arms", "hemem"):
            for m in MACHS:
                _exact(fused.at(policy=p, machine=m),
                       plain.at(policy=p, machine=m))


class TestStreamingReduce:
    def test_stream_matches_stack_scalars(self):
        rng = np.random.default_rng(1)
        trace = rng.gamma(1.5, 2.0, size=(T_, N_)).astype(np.float32)
        u = uniform_field(T_, N_, seed=2)
        stream = experiment.sweep(["arms", "tpp"], trace=trace, k=K_,
                                  sample_u=u)
        assert experiment.scan_engine.last_dispatch["reduce"] == "stream"
        stack = experiment.sweep(["arms", "tpp"], trace=trace, k=K_,
                                 sample_u=u, timelines=True)
        for p in ("arms", "tpp"):
            a, b = stream.at(policy=p), stack.at(policy=p)
            assert a.exec_time_s == b.exec_time_s
            assert a.promotions == b.promotions
            assert a.demotions == b.demotions
            assert a.wasteful == b.wasteful
            assert a.hot_recall == b.hot_recall
            assert a.timeline_slow_bw is None        # nothing [T]-shaped
            assert b.mean_slow_bw is None
            np.testing.assert_allclose(
                a.mean_slow_bw, float(np.mean(b.timeline_slow_bw)),
                rtol=1e-6)
            np.testing.assert_allclose(
                a.mean_fast_hits, float(np.mean(b.timeline_fast_hits)),
                rtol=1e-6)
            assert a.max_promotions_interval \
                == int(b.timeline_promotions.max())

    def test_stream_allocates_nothing_T_shaped(self):
        """Abstract-evaluate the synth-mode engine at bench scale
        (T=4096, n=65536): under reduce="stream" no output leaf may have
        a T-sized axis, proving O(1)-in-T output memory."""
        from repro.baselines.hemem import HeMemSpec
        from repro.simulator import workload_spec as wspec

        T, n, k = 4096, 65536, 4096
        wl = scan_engine._stack_workloads([wspec.named("gups", T=T)])
        mach, caps = scan_engine._mach_lanes("pmem-large", 1, n, k)
        spec = scan_engine._lane_specs(HeMemSpec.make(), 1)
        keys = jax.random.PRNGKey(0)[None]
        sample = jax.ShapeDtypeStruct((T, 1), jnp.float32)

        def run(reduce):
            return jax.eval_shape(
                lambda s: scan_engine._simulate(
                    spec, None, None, k, mach, caps, keys, s, "crn_prng",
                    False, wl=wl, wl_keys=keys,
                    noise_key=jax.random.PRNGKey(0), wl_rep=1, n=n,
                    reduce=reduce), sample)

        stream_leaves = jax.tree_util.tree_leaves(run("stream"))
        assert all(T not in leaf.shape for leaf in stream_leaves)
        stack_leaves = jax.tree_util.tree_leaves(run("stack"))
        assert any(T in leaf.shape for leaf in stack_leaves)  # sanity
