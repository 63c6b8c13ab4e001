"""Per-kernel allclose sweeps: Pallas (interpret mode on CPU) vs ref.py
oracles, across shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.migrate.kernel import migrate_kernel
from repro.kernels.migrate.ref import migrate_ref
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.score_update.kernel import score_update_kernel
from repro.kernels.score_update.ref import score_update_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


class TestPagedAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,H,KV,dh,page,npp",
        [(2, 8, 4, 128, 16, 4),
         (1, 4, 4, 64, 32, 2),     # MHA, small head
         (3, 16, 2, 128, 8, 8),    # high GQA ratio
         (2, 8, 8, 128, 64, 2)])
    def test_vs_ref(self, B, H, KV, dh, page, npp, dtype):
        rng = np.random.default_rng(B * 1000 + H)
        P = npp * B + 3
        q = _rand(rng, (B, H, dh), dtype)
        k = _rand(rng, (P, page, KV, dh), dtype)
        v = _rand(rng, (P, page, KV, dh), dtype)
        tables = jnp.asarray(
            rng.choice(P, (B, npp), replace=False), jnp.int32)
        lens = jnp.asarray(rng.integers(1, npp * page + 1, B), jnp.int32)
        ref = paged_attention_ref(q, k, v, tables, lens)
        out = paged_attention_kernel(q, k, v, tables, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOL[dtype])


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize(
        "B,S,H,KV,dh,bq,bk",
        [(2, 128, 4, 2, 64, 64, 64),
         (1, 256, 8, 8, 128, 128, 128),
         (1, 64, 4, 1, 128, 32, 32)])
    def test_vs_ref(self, B, S, H, KV, dh, bq, bk, causal, dtype):
        rng = np.random.default_rng(S + H)
        q = _rand(rng, (B, S, H, dh), dtype)
        k = _rand(rng, (B, S, KV, dh), dtype)
        v = _rand(rng, (B, S, KV, dh), dtype)
        ref = flash_attention_ref(q, k, v, causal=causal)
        out = flash_attention_kernel(q, k, v, causal=causal, bq=bq, bk=bk,
                                     interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **TOL[dtype])

    def test_windowed(self):
        rng = np.random.default_rng(0)
        q = _rand(rng, (1, 128, 4, 64), jnp.float32)
        k = _rand(rng, (1, 128, 2, 64), jnp.float32)
        v = _rand(rng, (1, 128, 2, 64), jnp.float32)
        ref = flash_attention_ref(q, k, v, causal=True, window=32)
        out = flash_attention_kernel(q, k, v, causal=True, window=32,
                                     bq=32, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestMigrate:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.int32])
    @pytest.mark.parametrize("Ps,Pd,M,page,feat",
                             [(16, 8, 4, 16, 128),
                              (4, 4, 4, 8, 256),
                              (32, 32, 12, 64, 128)])
    def test_vs_ref(self, Ps, Pd, M, page, feat, dtype):
        rng = np.random.default_rng(Ps + M)
        if dtype == jnp.int32:
            src = jnp.asarray(rng.integers(0, 100, (Ps, page, feat)),
                              jnp.int32)
            dst = jnp.asarray(rng.integers(0, 100, (Pd, page, feat)),
                              jnp.int32)
        else:
            src = _rand(rng, (Ps, page, feat), dtype)
            dst = _rand(rng, (Pd, page, feat), dtype)
        src_idx = jnp.asarray(rng.choice(Ps, M, replace=False), jnp.int32)
        dst_idx = jnp.asarray(rng.choice(Pd, M, replace=False), jnp.int32)
        valid = jnp.asarray(rng.random(M) < 0.7)
        ref = migrate_ref(src, dst, src_idx, dst_idx, valid)
        out = migrate_kernel(src, dst, src_idx, dst_idx, valid,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_no_valid_entries_is_noop(self):
        rng = np.random.default_rng(1)
        src = _rand(rng, (4, 8, 128), jnp.float32)
        dst = _rand(rng, (4, 8, 128), jnp.float32)
        idx = jnp.zeros(3, jnp.int32)
        out = migrate_kernel(src, dst, idx, idx, jnp.zeros(3, bool),
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dst))


class TestScoreUpdate:
    @pytest.mark.parametrize("n", [17, 4096, 10_000])
    def test_vs_ref(self, n):
        rng = np.random.default_rng(n)
        s = jnp.asarray(rng.random(n), jnp.float32)
        l = jnp.asarray(rng.random(n), jnp.float32)
        c = jnp.asarray(rng.poisson(5, n), jnp.float32)
        kw = dict(alpha_s=0.7, alpha_l=0.1, w_s=0.2, w_l=0.8)
        ref = score_update_ref(s, l, c, **kw)
        out = score_update_kernel(s, l, c, interpret=True, **kw)
        for o, r in zip(out, ref):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-6, atol=1e-6)


class TestMambaScan:
    """Fused SSD scan kernel (kernels/mamba_scan) vs the chunked oracle
    (itself pinned to the naive recurrence in test_models_smoke)."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,H,P,N,Q",
                             [(2, 128, 3, 16, 32, 32),
                              (1, 64, 2, 64, 128, 16),
                              (3, 256, 1, 32, 64, 64)])
    def test_vs_ref(self, B, S, H, P, N, Q, dtype):
        from repro.kernels.mamba_scan.kernel import mamba_scan_kernel
        from repro.kernels.mamba_scan.ref import mamba_scan_ref
        rng = np.random.default_rng(S + P)
        x = _rand(rng, (B, S, H, P), dtype)
        dt = jnp.asarray(rng.uniform(0.1, 0.9, (B, S, H)), jnp.float32)
        A = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        Bm = _rand(rng, (B, S, N), jnp.float32)
        Cm = _rand(rng, (B, S, N), jnp.float32)
        y_ref, h_ref = mamba_scan_ref(x.astype(jnp.float32), dt, A, Bm, Cm,
                                      Q)
        y, h = mamba_scan_kernel(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   **TOL[dtype])
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4)


class TestBackendRouting:
    """On a TPU the kernels always run compiled: the interpret switch is
    an error there, and the chip smoke check refuses any other platform."""

    @pytest.fixture
    def fresh_probe(self, monkeypatch):
        from repro.kernels import _backend
        monkeypatch.setattr(_backend, "_INTERPRET", None)
        return _backend

    def test_force_interpret_on_tpu_raises(self, fresh_probe, monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="REPRO_FORCE_INTERPRET"):
            fresh_probe.interpret_mode()

    def test_force_interpret_off_tpu_interprets(self, fresh_probe,
                                                monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        assert jax.default_backend() == "cpu"
        assert fresh_probe.interpret_mode()

    def test_chip_smoke_refuses_cpu(self):
        import importlib.util
        import os
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(root, "chip_smoke.py"))
        chip_smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(chip_smoke)
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main([])
        assert exc.value.code not in (0, None)
        assert "'cpu'" in str(exc.value.code)


class TestCompileCache:
    """The entry points' cache helper keeps a directory the environment
    names and otherwise picks one fixed path inside the checkout."""

    @pytest.fixture
    def restore_cache_dir(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_is_kept(self, monkeypatch, tmp_path,
                             restore_cache_dir):
        from repro.utils import compilation
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compilation.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_repo_path(self, monkeypatch,
                                        restore_cache_dir):
        import os
        from repro.utils import compilation
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".jax_cache")
        assert compilation.setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compilation.setup_compile_cache() == want     # stable
