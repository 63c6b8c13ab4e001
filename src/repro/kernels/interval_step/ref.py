"""Pure-jnp oracles for the fused per-interval fast path.

Each reference is the BITWISE contract for its Pallas kernel *and* for the
unfused scan-engine path it replaces (``scan_engine._simulate`` with
``interval_kernel=False``, i.e. ``experiment.sweep(...,
use_interval_kernel=False)``):

  * ``topk_mask_ref`` computes the exact top-k mask by threshold bisection
    over the order-preserving uint32 transform of f32 — no ``lax.top_k``
    partial sort, no scatter — with ``lax.top_k``'s tie rule (strictly
    greater first, then ascending index among threshold-equal values), so
    the mask is identical to ``zeros.at[top_k(x, k)[1]].set(True)``.
  * ``tier_migrate_ref`` / ``interval_account_ref`` are the vmapped forms
    of the simjax per-lane functions — literally the same jnp ops the
    unfused path traces, so CPU lanes routed here stay bit-identical.
  * ``ewma_score_update_ref`` is the lane-batched form of
    kernels/score_update's elementwise formula.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.simulator import simjax

_SIGN = jnp.uint32(0x80000000)


def _order_key(x):
    """Order-preserving uint32 key of f32: key(a) > key(b) iff a sorts
    above b under ``lax.top_k``'s TOTAL order on non-NaN inputs.  That
    order is sign-magnitude on bits, so +0.0 ranks strictly above -0.0 —
    branch on the sign BIT (``u & 0x80000000``), not on ``x < 0`` (which
    is False for -0.0 and would tie the two zeros)."""
    u = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)
    return jnp.where((u & _SIGN) != 0, ~u, u | _SIGN)


def topk_mask_ref(x, k: int):
    """Exact top-k bool mask along the last axis, any leading batch dims.

    Threshold bisection: 32 count-passes find the k-th largest key t; the
    mask is ``key > t`` plus the first ``k - count(key > t)`` ties by
    ascending index — exactly the ``lax.top_k`` + scatter mask.
    """
    n = x.shape[-1]
    assert 0 < k <= n
    key = _order_key(x)
    t = jnp.zeros(x.shape[:-1], jnp.uint32)
    for b in range(31, -1, -1):
        cand = t | jnp.uint32(1 << b)
        cnt = jnp.sum((key >= cand[..., None]).astype(jnp.int32), axis=-1)
        t = jnp.where(cnt >= k, cand, t)
    greater = key > t[..., None]
    eq = key == t[..., None]
    need = k - jnp.sum(greater.astype(jnp.int32), axis=-1)
    tie = eq & (jnp.cumsum(eq.astype(jnp.int32), axis=-1) <= need[..., None])
    return greater | tie


def tier_migrate_ref(tier, promote, demote, caps):
    """Lane-batched ``simjax.apply_tier_migrations``: tier [B, n] i32,
    promote [B, P] / demote [B, D] padded-index plans, caps [B, R] i32.
    Returns (tier, pexec, dexec, mig_up, mig_down) with a leading B axis.
    """
    return jax.vmap(simjax.apply_tier_migrations, in_axes=(0, 0, 0, 0))(
        tier, promote, demote, caps)


def interval_account_ref(mach, true, tier, mig_up, mig_down, oracle, k: int):
    """Lane-batched interval accounting + oracle recall in one call.

    ``mach`` is a lane-batched TieredMachineSpec ([B, R] tier leaves);
    ``true`` f32 [B, n]; ``tier`` i32 [B, n]; ``mig_up``/``mig_down`` f32
    [B, R-1]; ``oracle`` bool [B, n].  Returns (acc_fast, acc_slow, wall,
    slow_share, app_raw, recall), each [B] f32 — the first five bitwise
    those of ``simjax.interval_accounting_impl`` mapped lane by lane (the
    scan engine's ``_per_lane``), recall the scan engine's
    ``((tier == 0) & oracle).sum / k``.
    """
    acc_fast, acc_slow, wall, slow_share, app_raw = jax.lax.map(
        lambda a: simjax.interval_accounting_impl(*a),
        (mach, true, tier, mig_up, mig_down))
    recall = ((tier == 0) & oracle).sum(axis=1).astype(jnp.float32) / k
    return acc_fast, acc_slow, wall, slow_share, app_raw, recall


def ewma_score_update_ref(ewma_s, ewma_l, counts, *, alpha_s, alpha_l,
                          w_s, w_l):
    """Lane-batched dual-EWMA + score: arrays [B, n] f32, smoothing/weight
    params scalars or [B] (broadcast over pages)."""
    def col(v):
        v = jnp.asarray(v, jnp.float32)
        return v[:, None] if v.ndim == 1 else v

    a_s, a_l, ws, wl = col(alpha_s), col(alpha_l), col(w_s), col(w_l)
    s = a_s * counts + (1 - a_s) * ewma_s
    l = a_l * counts + (1 - a_l) * ewma_l
    return s, l, ws * s + wl * l
