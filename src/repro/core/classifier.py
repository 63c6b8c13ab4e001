"""Threshold-free hot/cold page classification (paper §4.1, Algorithm 1).

Score update
------------
Two EWMAs per page.  NOTE on faithfulness: Algorithm 1 as printed updates
``EWMA = alpha*EWMA + (1-alpha)*accesses`` which, with alpha_s=0.7 and
alpha_l=0.1, would make the *long-term* average the more reactive one —
contradicting the paper's prose ("short-term, fast-moving EWMA_s (alpha_s =
0.7)", 1s vs 10s horizons).  We implement the prose semantics

    EWMA <- alpha * accesses + (1 - alpha) * EWMA

so alpha_s=0.7 reacts fast and alpha_l=0.1 tracks the long horizon.  See
DESIGN.md §1 "Formula note".

Classification
--------------
Pages are *ranked* by score and the top-k (k = fast-tier capacity in pages)
form the hot set — no hotness threshold, no cooling (EWMA decay subsumes it).
``hot_age`` counts consecutive intervals a page stayed in the top-k.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.state import (MODE_RECENCY, ARMSConfig, TieringState)
from repro.utils.topk import top_k


def score_weights(cfg: ARMSConfig, mode):
    """(w_s, w_l) given mode; recency mode prioritizes the short-term EWMA."""
    recency = (mode == MODE_RECENCY)
    w_s = jnp.where(recency, cfg.w_s_recency, cfg.w_s_history)
    w_l = jnp.where(recency, cfg.w_l_recency, cfg.w_l_history)
    return w_s, w_l


def update_scores(state: TieringState, access_counts, cfg: ARMSConfig,
                  mode) -> TieringState:
    """Algorithm 1 lines 1-6: EWMA + hotness score update (vectorized).

    Routed through the fused interval-step EWMA op
    (kernels/interval_step.ops.ewma_score_update: Pallas kernel on TPU,
    fused jnp on other backends) unless ``cfg.use_score_kernel`` is False,
    which pins the jnp reference; every route computes the identical f32
    formula.  The op is lane-batched, so the [n] arrays ride a width-1
    batch axis (an outer ``vmap`` — the scan engine's lane batching —
    turns it into the real lane axis).
    """
    from repro.kernels.interval_step.ops import ewma_score_update

    x = jnp.asarray(access_counts, jnp.float32)
    w_s, w_l = score_weights(cfg, mode)
    ewma_s, ewma_l, score = ewma_score_update(
        state.ewma_s[None], state.ewma_l[None], x[None],
        alpha_s=cfg.alpha_s, alpha_l=cfg.alpha_l, w_s=w_s, w_l=w_l,
        use_kernel=bool(getattr(cfg, "use_score_kernel", True)))
    return state.replace(ewma_s=ewma_s[0], ewma_l=ewma_l[0],
                         prev_score=state.score, score=score[0])


def topk_hot_mask(score: jnp.ndarray, k: int):
    """Boolean mask of the top-k pages by score (Algorithm 1 lines 7-9).

    Ties are broken by page index (``utils.topk.top_k``).
    """
    n = score.shape[0]
    k = min(int(k), n)
    _, idx = top_k(score, k)
    mask = jnp.zeros((n,), bool).at[idx].set(True)
    return mask, idx


def update_hot_age(state: TieringState, hot_mask) -> TieringState:
    """Algorithm 1 lines 10-12."""
    hot_age = jnp.where(hot_mask, state.hot_age + 1, 0)
    return state.replace(hot_age=hot_age)
