"""Smoke check of the main path on a TPU, in one process.

Runs the system through the entry points a user calls and fails at the
first phase that goes wrong:

  * device — a TPU is present, and the interval ops take the compiled
    Pallas route (no interpret mode);
  * sweep — the robustness board of ``benchmarks/bench_robustness.py``
    (8 policy families x the 7-scenario adversarial suite x 3 machines =
    168 lanes) through ``experiment.sweep`` at n = 65536 pages (128 GiB
    of 2 MiB pages), k = 16384 fast pages, T = 256 intervals, once with
    the fused interval kernels and once on the unfused path: integer
    statistics must be equal, float statistics within FLOAT_RTOL /
    FLOAT_ATOL;
  * migrate — the same comparison for ARMS alone (21 lanes, T = 128):
    a single-family sweep takes the fused route, whose ``tier_migrate``
    kernel runs on every policy pass (the mixed board's tier-targeted
    executor does not use it);
  * serving — ``launch.serve.serve`` decoding 32 tokens x 4 sequences of
    stablelm-1.6b at its published widths (24 layers, d_model 2048,
    vocab 100352, bf16, random weights from seed 0): finite logits, no
    executable built after the first token, and the last step's logits
    within LOGIT_REL_L2 of a full-sequence forward over the same tokens.

``--chips 4`` runs only the lane-sharded board (T = 16) at mesh = 4 and
at mesh = 1 and requires every statistic to be bitwise equal.  While
``fabric.resolve_mesh`` refuses mesh sizes above 1 on a TPU, it fails
at its first sweep; lift that guard to run it.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; every earlier
line is a phase record.  Without a TPU the script exits non-zero and
prints no result.

Usage:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from benchmarks import bench_robustness                     # noqa: E402
from repro.configs import registry                          # noqa: E402
from repro.kernels import _backend                          # noqa: E402
from repro.launch import serve as serve_mod                 # noqa: E402
from repro.models import model as M                         # noqa: E402
from repro.simulator import experiment, fabric, scan_engine  # noqa: E402
from repro.simulator import scenarios                       # noqa: E402
from repro.utils.compilation import (count_compiles,        # noqa: E402
                                     setup_compile_cache)

#: board geometry.  T = 256: one v5e chip runs the 168-lane board at
#: about 1 s per interval per route, and both routes plus serving must
#: finish inside 20 minutes.  The four-chip check is a bitwise comparison
#: at four times the chip cost per second, so it runs T_MESH intervals.
N_PAGES, K_FAST, T, T_MESH = 65536, 16384, 256, 16
#: the single-family phase: ARMS's plans (64 pages each way) keep the
#: migration kernel inside its SMEM budget at n = 65536.
MIGRATE_POLICIES, T_MIGRATE = ("arms",), 128
#: bound on |fused - unfused| <= FLOAT_ATOL + FLOAT_RTOL * |unfused| for
#: every float statistic.  With equal integer statistics both routes take
#: the same decisions, and floats differ only where the fused kernels'
#: f32 row sums associate differently from XLA's: ~1e-6 relative on a
#: lane's access total.  The slower tiers' count is the remainder
#: ``total - fast``, which amplifies that by total / slow (up to ~100
#: where 99% of accesses hit the fast tier); the fraction-valued
#: statistics lie in [0, 1].
FLOAT_RTOL, FLOAT_ATOL = 1e-4, 1e-6
#: bound on ||decode - forward|| / ||forward|| for the last step's logits.
#: Both run in bf16 (8 mantissa bits) and re-round activations along two
#: computation orders through 24 layers; on CPU at reduced widths the
#: error measured 0.015-0.064.
LOGIT_REL_L2 = 0.125
SERVE_ARCH, SERVE_TOKENS, SERVE_BATCH = "stablelm-1.6b", 32, 4
INT_STATS = ("promotions", "demotions", "wasteful")
FLOAT_STATS = ("exec_time_s", "hot_recall", "fast_hit_frac",
               "mean_slow_bw", "mean_fast_hits", "mean_mode")


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def _emit(phase: str, **rec) -> None:
    print(f"{phase}: {json.dumps(rec)}", flush=True)


@contextlib.contextmanager
def _recorded_calls(module, name: str):
    """Record the abstract arguments of every call of the jitted
    ``module.name`` inside the block; ``_lowered_text`` lowers them
    afterwards, outside any timed region.  Arrays are recorded as shapes
    because the call may donate them."""
    jitted = getattr(module, name)
    calls = []

    def recording(*args, **kwargs):
        calls.append(jax.tree_util.tree_map(
            lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                       if isinstance(x, jax.Array) else x), (args, kwargs)))
        return jitted(*args, **kwargs)

    setattr(module, name, recording)
    try:
        yield calls
    finally:
        setattr(module, name, jitted)


def _lowered_text(module, name: str, call) -> str:
    args, kwargs = call
    return getattr(module, name).lower(*args, **kwargs).as_text()


def check_device() -> jax.Device:
    dev = jax.devices()[0]
    _check(dev.platform == "tpu",
           f"no TPU: JAX's first device is on platform {dev.platform!r}")
    _check(not _backend.interpret_mode(),
           "interval kernels would run in interpret mode")
    _emit("device", platform=dev.platform, kind=dev.device_kind,
          count=len(jax.devices()))
    return dev


def _board(intervals, policies=bench_robustness.POLICIES, mesh=None,
           use_interval_kernel=True):
    """One timed sweep of ``policies`` x the adversarial suite x the
    three machines; returns (result, record)."""
    suite = scenarios.suite(N_PAGES, K_FAST)
    with scan_engine.count_dispatches() as disp, count_compiles() as comp:
        t0 = time.perf_counter()
        res = experiment.sweep(
            list(policies), workloads=suite,
            machines=list(bench_robustness.MACHINES), k=K_FAST,
            T=intervals, n=N_PAGES, use_interval_kernel=use_interval_kernel,
            mesh=mesh)
        wall = time.perf_counter() - t0    # results are host floats here
    return res, dict(lanes=len(res.grid), T=intervals, n=N_PAGES, k=K_FAST,
                     policies=list(policies), mesh=mesh,
                     interval_kernel=use_interval_kernel,
                     dispatches=disp.count, compiles=comp.count,
                     compile_s=comp.seconds, run_s=wall - comp.seconds,
                     wall_s=wall)


def _stats(res, names):
    return np.array([[float(getattr(r, f)) for f in names]
                     for r in res.grid])


def _fused_vs_unfused(dev, phase, policies, intervals, kernel_name):
    """Sweep ``policies`` on the fused interval kernels and on the unfused
    path; the fused program must hold ``kernel_name``'s compiled Pallas
    kernel, integer statistics must be equal and float statistics within
    FLOAT_RTOL / FLOAT_ATOL."""
    with _recorded_calls(scan_engine, "_sim_synth_jit") as calls:
        fused, rec = _board(intervals, policies)
    _emit(phase, **rec)
    _check(len(calls) == 1, f"{phase}: {len(calls)} fused dispatches")
    text = _lowered_text(scan_engine, "_sim_synth_jit", calls[0])
    _check("tpu_custom_call" in text and kernel_name in text,
           f"{phase}: the fused program holds no compiled {kernel_name}")
    plain, rec = _board(intervals, policies, use_interval_kernel=False)
    _emit(phase, **rec)
    lanes = len(policies) * 7 * len(bench_robustness.MACHINES)
    _check(len(fused.grid) == lanes and len(plain.grid) == lanes,
           f"{phase}: expected {lanes} lanes, got "
           f"{len(fused.grid)}/{len(plain.grid)}")

    ints_f, ints_p = _stats(fused, INT_STATS), _stats(plain, INT_STATS)
    fl_f, fl_p = _stats(fused, FLOAT_STATS), _stats(plain, FLOAT_STATS)
    _check(bool(np.isfinite(fl_f).all() and np.isfinite(fl_p).all()),
           f"{phase}: non-finite sweep statistics")
    diff = np.abs(fl_f - fl_p)
    excess = diff - (FLOAT_ATOL + FLOAT_RTOL * np.abs(fl_p))
    int_equal = bool((ints_f == ints_p).all())
    _emit(f"{phase}_compare", lanes=lanes, kernel=kernel_name,
          int_stats=list(INT_STATS), int_equal=int_equal,
          int_cells_differing=int((ints_f != ints_p).any(axis=1).sum()),
          total_migrations=int(ints_f[:, :2].sum()),
          float_stats=list(FLOAT_STATS),
          float_max_abs_diff=dict(zip(FLOAT_STATS,
                                      diff.max(axis=0).tolist())),
          float_max_rel_diff=dict(zip(FLOAT_STATS, (diff / np.maximum(
              np.abs(fl_p), 1e-30)).max(axis=0).tolist())),
          float_rtol=FLOAT_RTOL, float_atol=FLOAT_ATOL,
          peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"])
    _check(int(ints_f[:, :2].sum()) > 0, f"{phase}: no page migrated")
    _check(int_equal, f"{phase}: integer statistics differ between the "
           "fused and unfused routes")
    _check(bool((excess <= 0).all()),
           f"{phase}: float statistics differ beyond rtol={FLOAT_RTOL}, "
           f"atol={FLOAT_ATOL}")


def sweep_phase(dev: jax.Device) -> None:
    """The 168-lane mixed-family board: one union program, whose
    tier-targeted executor takes the top-k and accounting kernels."""
    _fused_vs_unfused(dev, "sweep", bench_robustness.POLICIES, T,
                      "_account_body")


def migrate_phase(dev: jax.Device) -> None:
    """ARMS alone: the single-family fused route, which runs the
    ``tier_migrate`` kernel on every policy pass."""
    _fused_vs_unfused(dev, "migrate", MIGRATE_POLICIES, T_MIGRATE,
                      "_migrate_body")


def serve_phase(dev: jax.Device) -> None:
    with count_compiles() as comp:
        t0 = time.perf_counter()
        rep = serve_mod.serve(SERVE_ARCH, n_tokens=SERVE_TOKENS,
                              batch=SERVE_BATCH, full=True, policy="arms",
                              quiet=True)
        wall = time.perf_counter() - t0
    cfg = registry.get_arch(SERVE_ARCH)
    _check(rep.tokens.shape == (SERVE_BATCH, SERVE_TOKENS),
           f"generated token shape {rep.tokens.shape}")
    _check(rep.last_logits.shape == (SERVE_BATCH, cfg.vocab_size),
           f"logit shape {rep.last_logits.shape}")
    _check(bool(np.isfinite(rep.last_logits).all()), "non-finite logits")

    # full-sequence forward over the tokens the decode loop consumed: the
    # start token 0, then every generated token but the last.
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    seq = np.concatenate([np.zeros((SERVE_BATCH, 1), np.int32),
                          rep.tokens[:, :-1]], axis=1)
    logits, _ = jax.jit(M.forward, static_argnums=(2,))(
        params, {"tokens": jnp.asarray(seq)}, cfg)
    ref = np.asarray(logits[:, -1], np.float32)
    rel_l2 = float(np.linalg.norm(rep.last_logits - ref)
                   / np.linalg.norm(ref))
    _emit("serve", arch=SERVE_ARCH, layers=cfg.n_layers,
          d_model=cfg.d_model, vocab=cfg.vocab_size, dtype=cfg.dtype,
          tokens=SERVE_TOKENS, batch=SERVE_BATCH,
          compiles=comp.count, compile_s=comp.seconds, wall_s=wall,
          compiles_after_first_token=rep.decode_compiles,
          promotions=rep.promotions, demotions=rep.demotions,
          logits_rel_l2_vs_forward=rel_l2, logit_rel_l2_bound=LOGIT_REL_L2,
          argmax_agreement=float((rep.last_logits.argmax(-1)
                                  == ref.argmax(-1)).mean()),
          peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"])
    _check(rep.decode_compiles == 0,
           f"{rep.decode_compiles} executables built after the first token")
    _check(rel_l2 <= LOGIT_REL_L2,
           f"decode logits differ from the forward by {rel_l2} (relative "
           f"L2) > {LOGIT_REL_L2}")


def mesh_phase(dev: jax.Device, chips: int) -> None:
    _check(len(jax.devices()) >= chips,
           f"--chips {chips} but {len(jax.devices())} device(s) present")
    with _recorded_calls(fabric, "_fab_synth_jit") as calls:
        sharded, rec = _board(T_MESH, mesh=chips)
    _emit("sweep", **rec)
    _check(len(calls) == 1 and "tpu_custom_call" in _lowered_text(
        fabric, "_fab_synth_jit", calls[0]),
        "the sharded sweep program holds no compiled Pallas kernel")
    single, rec = _board(T_MESH, mesh=1)
    _emit("sweep", **rec)
    fields = [f.name for f in dataclasses.fields(type(single.grid[0]))
              if f.name != "name"]
    differing = [(a.name, f) for a, b in zip(sharded.grid, single.grid)
                 for f in fields
                 if not np.array_equal(np.asarray(getattr(a, f)),
                                       np.asarray(getattr(b, f)))]
    _emit("mesh_compare", lanes=len(single.grid), meshes=[chips, 1],
          fields=len(fields), bitwise_equal=not differing,
          differing=differing[:8],
          peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"])
    _check(not differing, f"mesh={chips} differs from mesh=1 in "
           f"{len(differing)} (cell, field) pairs")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded sweep at mesh=4 "
                         "against mesh=1")
    args = ap.parse_args(argv)
    dev = check_device()
    setup_compile_cache()
    if args.chips == 1:
        sweep_phase(dev)
        migrate_phase(dev)
        serve_phase(dev)
    else:
        mesh_phase(dev, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
