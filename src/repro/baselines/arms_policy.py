"""ARMS as a simulator policy (the paper's system, §4-5), in both forms:

* ``ARMSSpec`` — the functional-protocol spec (baselines/protocol.py): pure
  init/observe/fires/policy over pytree state, with the ARMSConfig float
  knobs under sweep (``cfg_names``/``cfg_vals``) living as traceable leaves
  so a whole tuning grid runs lane-batched in the compiled scan engine.
* ``ARMSPolicy`` — the hand-tuned stateful wrapper for the numpy reference
  engine.  It predates ``LegacyPolicyAdapter`` and stays separate because
  ARMS's sampling period / cadence are mode-dependent: the generic adapter
  would poll them from device state every interval, while this wrapper
  caches them on the HOST and refreshes once per policy invocation (mode
  only changes inside ``arms_step``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.base import Policy
from repro.baselines.protocol import PolicySpec
from repro.core import ARMSConfig, arms_step, init_state
from repro.core.controller import (POLICY_EVERY_HISTORY,
                                   POLICY_EVERY_RECENCY,
                                   SAMPLING_PERIOD_HISTORY,
                                   SAMPLING_PERIOD_RECENCY, arms_step_impl,
                                   policy_every, sampling_period)
from repro.core.scheduler import observe_migration_cost
from repro.core.state import MODE_HISTORY, MODE_RECENCY, TieringState
from repro.utils.pytree import pytree_dataclass

# ARMSConfig float knobs that may be batched (traced) in a config sweep.
# Shape-determining ints (bs_max) and the kernel flag must stay static.
SWEEPABLE = frozenset({
    "alpha_s", "alpha_l", "w_s_history", "w_l_history", "w_s_recency",
    "w_l_recency", "pht_delta", "pht_lambda", "stabilize_eps", "noise_z",
    "latency_fast_us", "latency_slow_us", "access_scale",
    "migrate_cost_alpha", "init_promo_cost_us", "init_demo_cost_us",
})


@pytree_dataclass
class ARMSRunState:
    inner: TieringState
    buf: jnp.ndarray       # f32 [n] counts accumulated since last policy run
    t: jnp.ndarray         # i32 simulator-interval counter
    promo_us: jnp.ndarray  # f32 measured per-page migration latencies for
    demo_us: jnp.ndarray   # the §4.3 self-calibration feedback


@pytree_dataclass(meta=("cfg_names", "base_cfg"))
class ARMSSpec(PolicySpec):
    """Functional-protocol ARMS.  ``cfg_vals[i]`` overrides ARMSConfig field
    ``cfg_names[i]`` — the overridden floats are pytree leaves, so sweep
    lanes batch over them while ``base_cfg`` (and every shape-determining
    int) stays static."""

    cfg_vals: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((0,), jnp.float32))
    cfg_names: tuple = ()
    base_cfg: ARMSConfig = ARMSConfig()

    name = "arms"
    dynamic_sampling_period = True
    has_mode = True

    @classmethod
    def make(cls, overrides: dict | None = None,
             base_cfg: ARMSConfig | None = None) -> "ARMSSpec":
        overrides = overrides or {}
        bad = set(overrides) - SWEEPABLE
        if bad:
            raise ValueError(
                f"non-sweepable ARMSConfig fields {sorted(bad)}; sweepable: "
                f"{sorted(SWEEPABLE)}")
        names = tuple(sorted(overrides))
        vals = jnp.asarray([float(overrides[nm]) for nm in names],
                           jnp.float32)
        return cls(cfg_vals=vals, cfg_names=names,
                   base_cfg=base_cfg or ARMSConfig())

    def cfg(self) -> ARMSConfig:
        if not self.cfg_names:
            return self.base_cfg
        return dataclasses.replace(
            self.base_cfg,
            **{nm: self.cfg_vals[i] for i, nm in enumerate(self.cfg_names)})

    def pad_promote(self, n, k):
        return max(1, min(n, self.base_cfg.bs_max))

    pad_demote = pad_promote

    def init(self, n_pages, k, machine):
        # machine is a TieredMachineSpec (a host name/MachineSpec resolves
        # here for direct callers); the path sums (full bottom-to-top hop
        # chain) are the N-tier generalization of the legacy per-page
        # promo/demo latencies and equal them bitwise at N=2 (the pair
        # costs are host-precomputed f64 -> f32 leaves, machine_spec.py).
        from repro.simulator import machines
        machine = machines.get(machine)
        return ARMSRunState(
            inner=init_state(n_pages, self.cfg()),
            buf=jnp.zeros((n_pages,), jnp.float32),
            t=jnp.zeros((), jnp.int32),
            promo_us=jnp.asarray(machine.promo_path_us(), jnp.float32),
            demo_us=jnp.asarray(machine.demo_path_us(), jnp.float32))

    def observe(self, state, observed):
        return state.replace(buf=state.buf + observed, t=state.t + 1)

    def fires(self, state):
        return (state.t % policy_every(state.inner.mode)) == 0

    def sampling_period(self, state):
        return sampling_period(state.inner.mode).astype(jnp.float32)

    def min_sampling_period(self):
        return float(SAMPLING_PERIOD_RECENCY)

    def mode_of(self, state):
        return state.inner.mode

    def policy(self, state, slow_bw, app_bw, k):
        cfg = self.cfg()
        # normalize accumulated counts to per-interval rate so the EWMA
        # scale is mode-independent (500ms vs 100ms policy cadence, §5).
        every = policy_every(state.inner.mode).astype(jnp.float32)
        counts = state.buf / every
        inner, plan = arms_step_impl(state.inner, counts, slow_bw, app_bw,
                                     cfg=cfg, k=k)
        # §4.3: self-calibrating migration-cost feedback
        inner = jax.lax.cond(
            plan.count > 0,
            lambda s: observe_migration_cost(s, state.promo_us,
                                             state.demo_us, cfg),
            lambda s: s, inner)
        promote = jnp.where(plan.valid, plan.promote, -1).astype(jnp.int32)
        demote = jnp.where(plan.valid & (plan.demote >= 0), plan.demote,
                           -1).astype(jnp.int32)
        state = state.replace(inner=inner, buf=jnp.zeros_like(state.buf))
        return state, promote, demote


@pytree_dataclass(meta=("cfg_names", "base_cfg", "pool_every"))
class ARMSServeSpec(ARMSSpec):
    """ARMS exactly as the pre-refactor serving layer ran it.

    The serving pools (tiering/tiered_pool.py) historically called
    ``core.arms_step`` directly: RAW accumulated counts (no per-interval
    normalization), a FIXED ``policy_every`` cadence (not the
    mode-dependent 5/1 simulator cadence), and no §4.3 migration-cost
    feedback.  This spec reproduces that path bit-for-bit through the
    PolicySpec protocol — the legacy-equivalence regression in
    tests/test_serving_protocol.py asserts plan-sequence equality against
    a frozen copy of the old ``arms_step`` serving loop.  Use plain
    ``ARMSSpec`` for simulator sweeps; use this inside serving pools.
    """

    pool_every: int = 8

    name = "arms"
    dynamic_sampling_period = False

    @classmethod
    def make_serving(cls, base_cfg: ARMSConfig, pool_every: int,
                     overrides: dict | None = None) -> "ARMSServeSpec":
        spec = cls.make(overrides, base_cfg=base_cfg)
        return dataclasses.replace(spec, pool_every=int(pool_every))

    def fires(self, state):
        # observe() increments t first, so the first fire lands on interval
        # pool_every — the legacy ``kv.step % cfg.policy_every == 0`` gate.
        return (state.t % self.pool_every) == 0

    def sampling_period(self, state):
        return jnp.float32(self.DEFAULT_SAMPLE_PERIOD)

    def policy(self, state, slow_bw, app_bw, k):
        # raw counts, no normalization, no migration-cost feedback: the
        # legacy serving semantics (class docstring).
        inner, plan = arms_step_impl(state.inner, state.buf, slow_bw,
                                     app_bw, cfg=self.cfg(), k=k)
        promote = jnp.where(plan.valid, plan.promote, -1).astype(jnp.int32)
        demote = jnp.where(plan.valid & (plan.demote >= 0), plan.demote,
                           -1).astype(jnp.int32)
        state = state.replace(inner=inner, buf=jnp.zeros_like(state.buf))
        return state, promote, demote


class ARMSPolicy(Policy):
    name = "arms"

    def __init__(self, cfg: ARMSConfig | None = None):
        self.base_cfg = cfg or ARMSConfig()

    @property
    def migration_limit(self):  # batched migrations: up to BS_max per pass
        return self.base_cfg.bs_max

    def reset(self, n_pages, k, machine):
        from repro.simulator import machines
        machine = machines.get(machine)
        self.n, self.k = n_pages, k
        self.cfg = self.base_cfg
        self.state = init_state(n_pages, self.cfg)
        self.buf = np.zeros(n_pages)
        self.t = 0
        self._machine = machine
        # f32 path sums, matching ARMSSpec.init (and the legacy f64->f32
        # per-page costs bitwise at N=2).
        self._promo_us = float(
            np.sum(np.asarray(machine.promo_pair_us, np.float32)))
        self._demo_us = float(
            np.sum(np.asarray(machine.demo_pair_us, np.float32)))
        self._set_mode(MODE_HISTORY)

    def _set_mode(self, mode: int):
        """Host-side cadence cache, refreshed once per policy invocation."""
        self._mode = int(mode)
        recency = self._mode == MODE_RECENCY
        self._every = POLICY_EVERY_RECENCY if recency else POLICY_EVERY_HISTORY
        self._period = float(SAMPLING_PERIOD_RECENCY if recency
                             else SAMPLING_PERIOD_HISTORY)

    def sampling_period(self):
        return self._period

    def step(self, observed, slow_bw_frac, app_bw_frac):
        self.t += 1
        self.buf += observed
        every = self._every
        if self.t % every:
            return np.empty(0, np.int64), np.empty(0, np.int64)

        # normalize accumulated counts to per-interval rate so the EWMA scale
        # is mode-independent (500ms vs 100ms policy cadence, §5).  f32 in,
        # f32 divide: the controller computes in f32 either way, and dividing
        # after the cast keeps this bitwise-aligned with the scan engine.
        counts = self.buf.astype(np.float32) / np.float32(every)
        self.state, plan = arms_step(
            self.state, counts, float(slow_bw_frac),
            float(app_bw_frac), cfg=self.cfg, k=self.k)
        self.buf[:] = 0.0

        valid = np.asarray(plan.valid)
        promote = np.asarray(plan.promote)[valid]
        demote = np.asarray(plan.demote)[valid]
        demote = demote[demote >= 0]
        if len(promote):   # §4.3: self-calibrating migration-cost feedback
            self.state = observe_migration_cost(
                self.state, self._promo_us, self._demo_us, self.cfg)
        self._set_mode(int(self.state.mode))
        return promote.astype(np.int64), demote.astype(np.int64)

    @property
    def mode(self) -> int:
        return self._mode
