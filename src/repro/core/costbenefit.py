"""Wasteful-migration elimination (paper §4.3, Algorithm 2).

Multi-round promotion filtering: a page entering the top-k is only a
*candidate* once its score is non-decreasing and its hot age >= 2 — one-hit
wonders never reach the migration queue.

Cost/benefit gate: the i-th hottest candidate p is paired with the i-th
coldest fast-tier victim q (or with a free fast-tier slot), and promoted only
if

    B = (p_score - q_score) * p_hotage * dLatency  >  C = L_promo + L_demo

where L_promo / L_demo are EWMAs of observed migration latencies (fed back by
the migration engine), making the gate self-calibrating — no threshold.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.state import ARMSConfig, TieringState
from repro.utils.topk import top_k

_NEG = jnp.float32(-3.4e38)
_POS = jnp.float32(3.4e38)


def promotion_candidates(state: TieringState, hot_mask, cfg: ARMSConfig,
                         bs_max: int):
    """Top `bs_max` promotion candidates, hottest first (Alg. 2 lines 1-4).

    Returns (idx[bs_max], valid[bs_max]).
    """
    is_cand = (hot_mask
               & (~state.in_fast)
               & (state.score >= state.prev_score)
               & (state.hot_age >= cfg.hot_age_min))
    keyed = jnp.where(is_cand, state.score, _NEG)
    val, idx = top_k(keyed, bs_max)
    return idx, val > _NEG


def demotion_victims(state: TieringState, hot_mask, bs_max: int):
    """Coldest fast-tier pages outside the top-k, coldest first."""
    is_victim = state.in_fast & (~hot_mask)
    keyed = jnp.where(is_victim, -state.score, _NEG)
    val, idx = top_k(keyed, bs_max)
    return idx, val > _NEG


def cost_benefit_gate(state: TieringState, cand_idx, cand_valid, victim_idx,
                      victim_valid, free_slots, cfg: ARMSConfig, mode=None):
    """Alg. 2 lines 5-10, vectorized over the candidate batch.

    The first ``free_slots`` candidates consume free fast-tier capacity
    (no demotion, q_score = 0, C = L_promo only); the rest pair with victims.

    Returns (promote_ok[bs], demote_idx[bs]) where demote_idx == -1 marks a
    free-slot promotion.
    """
    bs = cand_idx.shape[0]
    j = jnp.arange(bs)
    uses_free = j < free_slots
    vpos = jnp.clip(j - free_slots, 0, bs - 1)
    victim = victim_idx[vpos]
    victim_ok = victim_valid[vpos] & (~uses_free)

    q_score = jnp.where(uses_free, 0.0, state.score[victim])
    p_score = state.score[cand_idx]
    p_age = state.hot_age[cand_idx].astype(jnp.float32)

    # §4.3 "PEBS sampling inaccuracies ... cost-benefit provides immunity":
    # sampled counts are ~Poisson, so a score difference below (a fraction
    # of) the noise floor sqrt(p+q) carries no real benefit.  Self-scaling
    # with the count magnitude — noise_z is a fixed internal constant
    # (sensitivity is flat; see EXPERIMENTS.md), not a per-workload knob.
    del mode
    noise = cfg.noise_z * jnp.sqrt(jnp.maximum(p_score + q_score, 0.0))
    gain = jnp.maximum(p_score - q_score - noise, 0.0)
    benefit = gain * p_age * cfg.delta_latency * cfg.access_scale
    cost = jnp.where(uses_free, state.promo_cost,
                     state.promo_cost + state.demo_cost)
    ok = cand_valid & (uses_free | victim_ok) & (benefit > cost)
    demote = jnp.where(uses_free, -1, victim)
    return ok, demote
