"""ARMS core: the paper's primary contribution as a composable JAX module.

Threshold-free dual-EWMA hot/cold classification (Alg. 1), Page-Hinkley
change-point adaptation (§4.2), cost/benefit-gated promotions (Alg. 2) and the
bandwidth-aware batched migration scheduler (§4.4).
"""
from repro.core.controller import (ARMSConfig, MigrationPlan, TieringState,
                                   arms_step, arms_step_impl, init_state,
                                   policy_every, sampling_period)
from repro.core.pht import pht_update
from repro.core.state import MODE_HISTORY, MODE_RECENCY

__all__ = [
    "ARMSConfig", "MigrationPlan", "TieringState", "arms_step",
    "arms_step_impl", "init_state", "pht_update", "MODE_HISTORY",
    "MODE_RECENCY", "sampling_period", "policy_every",
]
