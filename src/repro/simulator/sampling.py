"""PEBS-style access sampling emulation (paper §2, §4.1).

Hardware event sampling observes roughly 1 in ``period`` accesses; over an
interval the per-page sample count is well modeled as Poisson(true/period).
This reproduces the sampling inaccuracies the paper identifies (§3.2): two
pages with identical true rates receive different counts over short windows,
and sparse-but-hot pages may briefly receive zero samples.
"""
from __future__ import annotations

import numpy as np


def pebs_sample(true_counts: np.ndarray, period: float,
                rng: np.random.Generator) -> np.ndarray:
    """Observed per-page sample counts for one interval."""
    lam = np.maximum(true_counts, 0.0) / float(period)
    return rng.poisson(lam).astype(np.float64)


# --------------------------------------------------------------------------
# Common-random-number (CRN) sampling path, shared by the numpy reference
# engine and the compiled lax.scan engine (simulator/scan_engine.py).
#
# The two engines cannot share numpy's bit-level Poisson sampler, so for
# engine-equivalence (and for paired comparisons across configs in tuning
# sweeps) the noise is expressed as a precomputed uniform field u[t, page]
# and both engines apply the SAME jitted inverse-CDF transform
# ``pebs_sample_from_uniform`` to it.  Identical u + identical transform =>
# bitwise-identical observed counts on both paths.
# --------------------------------------------------------------------------

_POISSON_TERMS = 24      # exact inverse-CDF terms; P(N >= 24 | lam < 12) ~ 1e-3
_NORMAL_SWITCH = 12.0    # above this rate use the normal approximation


def pebs_sample_from_uniform(u, true_counts, period, *,
                             need_normal: bool = True):
    """Jittable Poisson-from-uniform PEBS sample (CRN path).

    ``u`` in [0,1) per page; small rates use the exact inverse CDF (pmf by
    the recurrence p_j = p_{j-1} * lam / j — one ``exp`` per element, the
    rest cheap multiply/adds), large rates the rounded normal approximation.
    The noise *model* only needs to be Poisson-like; what matters is that
    both engines apply this exact transform.

    ``need_normal=False`` statically drops the ndtri branch; callers may set
    it when ``max(lam) < _NORMAL_SWITCH`` (the selected values are identical
    either way — the normal branch would be dead).
    """
    import jax
    import jax.numpy as jnp

    u = jnp.asarray(u, jnp.float32)
    lam = jnp.maximum(jnp.asarray(true_counts, jnp.float32), 0.0) \
        / jnp.asarray(period, jnp.float32)
    # Unrolled recurrence (NOT cumsum/cumprod: XLA lowers those to a
    # quadratic reduce-window on CPU, ~30x slower than this elementwise
    # chain at simulator scale).
    pmf = jnp.exp(-lam)
    cdf = pmf
    out = (cdf < u).astype(jnp.float32)
    for j in range(1, _POISSON_TERMS):
        pmf = pmf * lam / j
        cdf = cdf + pmf
        out = out + (cdf < u)
    if need_normal:
        z = jax.scipy.special.ndtri(jnp.clip(u, 1e-7, 1.0 - 1e-7))
        large = jnp.maximum(jnp.floor(lam + z * jnp.sqrt(lam) + 0.5), 0.0)
        out = jnp.where(lam < _NORMAL_SWITCH, out, large)
    return jnp.where(lam <= 0.0, 0.0, out)


def uniform_field(T: int, n: int, seed: int = 0) -> np.ndarray:
    """Host-side CRN uniform noise field for a whole trace replay."""
    return np.random.default_rng(seed).random((T, n)).astype(np.float32)


# --------------------------------------------------------------------------
# Device-resident CRN rows for the trace-synthesis path.
#
# Workload-lane sweeps (experiment.sweep(workloads=...)) never build a
# [T, n] array anywhere: each interval draws ONE uniform row from a
# counter-based key (fold_in by t — no consumed key chain), shared by
# every sweep lane, so config comparisons stay paired while per-lane storage
# stays O(n).  ``synth_noise_field`` reconstructs the same rows host-side so
# the numpy reference engine can replay a synth run bitwise (tests only —
# it IS the O(T*n) array the synth path avoids).
# --------------------------------------------------------------------------

def synth_uniform_row(key, t, n: int):
    """Jittable [n] uniform row for interval ``t`` (shared across lanes)."""
    import jax
    import jax.numpy as jnp

    return jax.random.uniform(jax.random.fold_in(key, t), (n,),
                              dtype=jnp.float32)


def synth_noise_field(T: int, n: int, seed: int = 0) -> np.ndarray:
    """Host [T, n] replica of the rows a synth run draws in-scan."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    rows = jax.vmap(lambda t: synth_uniform_row(key, t, n))(jnp.arange(T))
    return np.asarray(rows)
