"""ARMS controller: one policy interval end-to-end (paper Fig. 6).

``arms_step`` is the composable entry point used by the simulator, the paged
KV-cache tier, the MoE expert tier and the embedding tier.  It is pure and
jittable: (state, access_counts, slow_bw_frac, app_bw_frac) -> (state, plan).

Pipeline per interval:
  1. PHT on slow-tier bandwidth -> history/recency mode (§4.2); recency mode
     doubles the sampling rate (surfaced via ``sampling_period``) and runs the
     policy 5x more often (surfaced via ``policy_every``).
  2. dual-EWMA score update (Alg. 1), with mode-dependent weights.
  3. top-k ranking (k = fast tier capacity) + hot-age update.
  4. multi-round filter + cost/benefit gate (Alg. 2).
  5. bandwidth-aware batched, priority-ordered migration plan (§4.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import classifier, costbenefit, scheduler
from repro.core.pht import pht_update
from repro.core.state import (MODE_HISTORY, MODE_RECENCY, ARMSConfig,
                              MigrationPlan, TieringState, init_state)

__all__ = [
    "ARMSConfig", "TieringState", "MigrationPlan", "init_state", "arms_step",
    "arms_step_impl", "sampling_period", "policy_every",
]

# §5: PEBS sampling period 10k default, 5k in recency mode.
SAMPLING_PERIOD_HISTORY = 10_000
SAMPLING_PERIOD_RECENCY = 5_000
# §5: policy thread every 500ms steady, 100ms after a hot-set change.
POLICY_EVERY_HISTORY = 5
POLICY_EVERY_RECENCY = 1


def sampling_period(mode):
    return jnp.where(mode == MODE_RECENCY, SAMPLING_PERIOD_RECENCY,
                     SAMPLING_PERIOD_HISTORY)


def policy_every(mode):
    return jnp.where(mode == MODE_RECENCY, POLICY_EVERY_RECENCY,
                     POLICY_EVERY_HISTORY)


def arms_step_impl(state: TieringState, access_counts, slow_bw_frac,
                   app_bw_frac, *, cfg: ARMSConfig, k: int, tier_util=None):
    """One ARMS policy interval (untraced body — see ``arms_step``).

    This un-jitted entry point exists for callers that inline the controller
    into a larger compiled program (the lax.scan simulation engine, vmapped
    tuning sweeps).  There ``cfg``'s *float* fields may be traced arrays —
    e.g. a batch of (alpha_s, noise_z, ...) knob settings swept under vmap —
    while the shape-determining int fields (``bs_max``) stay static.

    Args:
      state: TieringState over n_pages.
      access_counts: [n_pages] accesses observed this interval.
      slow_bw_frac: scalar in [0,1] — slow-tier bandwidth / its max (PHT
        input; §4.2 "increase in slow tier bandwidth" signals hot-set change).
      app_bw_frac: scalar in [0,1] — application bandwidth / BW_max (BS
        throttle input; §4.4).
      cfg: ARMSConfig (static).
      k: fast-tier capacity in pages (static).
      tier_util: optional f32 [R] per-tier bandwidth utilization (N-tier
        machines); throttles the migration batch by the top adjacent
        pair's budget (scheduler.pair_budgets).  None = classic two-tier
        BS formula.

    Returns:
      (new_state, MigrationPlan)
    """
    # 1. change-point detection -> mode.  The TTL counts down only while the
    # slow-tier signal has stabilized (short EWMA not above long EWMA by more
    # than eps); while it keeps rising the system stays in recency mode
    # (§4.2: "until the bandwidth utilization has stabilized").
    x = jnp.asarray(slow_bw_frac, jnp.float32)
    sig_s = cfg.alpha_s * x + (1 - cfg.alpha_s) * state.sig_ewma_s
    sig_l = cfg.alpha_l * x + (1 - cfg.alpha_l) * state.sig_ewma_l
    stabilized = sig_s <= sig_l + cfg.stabilize_eps
    pht, alarm, _ = pht_update(state.pht, x, cfg)
    ttl = jnp.where(
        alarm, cfg.recency_ttl,
        jnp.where(stabilized, jnp.maximum(state.mode_ttl - 1, 0),
                  jnp.maximum(state.mode_ttl, 0)))
    mode = jnp.where(ttl > 0, MODE_RECENCY, MODE_HISTORY).astype(jnp.int32)
    state = state.replace(pht=pht, mode=mode, mode_ttl=ttl,
                          interval=state.interval + 1,
                          sig_ewma_s=sig_s, sig_ewma_l=sig_l)

    # 2. score update (Alg. 1).
    state = classifier.update_scores(state, access_counts, cfg, mode)

    # 3. top-k hot set + hot age.
    hot_mask, _ = classifier.topk_hot_mask(state.score, k)
    state = classifier.update_hot_age(state, hot_mask)

    # 4. candidates, victims, cost/benefit gate (Alg. 2).
    bs_max = min(cfg.bs_max, access_counts.shape[0])
    cand_idx, cand_valid = costbenefit.promotion_candidates(
        state, hot_mask, cfg, bs_max)
    victim_idx, victim_valid = costbenefit.demotion_victims(
        state, hot_mask, bs_max)
    free_slots = k - state.in_fast.sum().astype(jnp.int32)
    ok, demote_idx = costbenefit.cost_benefit_gate(
        state, cand_idx, cand_valid, victim_idx, victim_valid, free_slots,
        cfg, mode=mode)

    # 5. bandwidth-aware batch + priority order; apply residency update.
    plan = scheduler.build_plan(cand_idx, ok, demote_idx, app_bw_frac, 1.0,
                                cfg, tier_util=tier_util)
    state = scheduler.apply_plan(state, plan)
    return state, plan


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def arms_step(state: TieringState, access_counts, slow_bw_frac, app_bw_frac,
              *, cfg: ARMSConfig, k: int):
    """Jitted ``arms_step_impl`` (cfg/k static) — the standalone entry point
    used by the numpy simulator policy and the tiering integrations."""
    return arms_step_impl(state, access_counts, slow_bw_frac, app_bw_frac,
                          cfg=cfg, k=k)
