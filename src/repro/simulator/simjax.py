"""Jittable engine-side bookkeeping shared by both simulation engines.

The numpy reference engine (simulator/engine.py) and the compiled
``lax.scan`` engine (simulator/scan_engine.py) must stay *semantically
aligned*: capacity/validity enforcement, wasteful-migration accounting and
the interval cost model are defined once here, as pure jax functions, and
used by both.  The numpy engine calls them per interval in CRN mode (where
bitwise agreement with the scan engine matters); the scan engine inlines
them into its scan body.

Since the N-tier machine protocol (simulator/machine_spec.py), placement
is an i32 per-page **tier index** (0 = fastest) and migrations are
adjacent-tier-pair hop chains; the two-tier boolean form survives as
``apply_padded_migrations`` (run by the serving ``TieredPool``), whose
decisions the tier forms reproduce bitwise at N=2.

Numerical layout notes (N=2 bitwise equivalence with the pre-N-tier
engines): tier 0 charges app + migration bytes against one symmetric
bandwidth in a single division; every lower tier charges reads and writes
separately; the bottom tier's access count is computed by subtraction
(total - upper tiers), matching the legacy ``acc_slow`` expression; the
utilization ratios are returned RAW (a tier demanding more bandwidth-time
than the rest of the interval reports > 1) and are clamped only at the
signal consumer (the engines clamp the policy-facing ``app_bw`` signal;
core/scheduler.batch_size clips its input) — ``min(1, raw)`` equals the
old at-source clamp bitwise, while the raw value keeps the
oversaturation magnitude visible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.simulator.engine import WASTE_WINDOW
from repro.simulator.machine import CACHELINE, PAGE_BYTES

#: destination sentinel for tier-targeted moves: "the first tier below the
#: page's source with room" — the hop-chain demotion cascade.  The binary
#: shim (protocol.PolicySpec.tier_policy) emits its demotions with this
#: destination, which is what makes the shim bitwise-equal to
#: ``apply_tier_migrations``.
DST_BELOW = -2


def tier_access_split(true, tier, R: int):
    """Per-tier f32 access counts [R] + the f32 total.

    Tiers 0..R-2 are masked sums; the bottom tier is the sequential
    remainder ``total - sum(upper)`` — at R=2 exactly the legacy
    ``acc_slow = sum(true) - acc_fast``.
    """
    total = jnp.sum(true)
    accs = []
    rest = total
    for r in range(R - 1):
        a = jnp.sum(true * (tier == r))
        accs.append(a)
        rest = rest - a
    accs.append(rest)
    return accs, total


def _tier_times(mach, acc, mig_up, mig_down):
    """Per-tier latency + bandwidth times (the shared inner arithmetic of
    ``tier_interval_outcome`` and ``tier_utilization_impl`` — op-for-op the
    historical expressions, so factoring it out is bitwise-neutral).

    Returns (t_lat, [R] list of per-tier bandwidth times).
    """
    R = mach.lat_ns.shape[0]
    lat, br, bw = mach.lat_ns, mach.bw_read, mach.bw_write

    t_lat = acc[0] * lat[0]
    for r in range(1, R):
        t_lat = t_lat + acc[r] * lat[r]
    t_lat = t_lat * 1e-9 / mach.mlp

    # tier 0: one symmetric-bandwidth division (legacy fast-tier form).
    times = [(acc[0] * CACHELINE
              + (mig_up[0] + mig_down[0]) * PAGE_BYTES) / br[0]]
    for r in range(1, R):
        rd = mig_up[r - 1]
        if r < R - 1:
            rd = rd + mig_down[r]
        wr = mig_down[r - 1]
        if r < R - 1:
            wr = wr + mig_up[r]
        times.append((acc[r] * CACHELINE + rd * PAGE_BYTES) / br[r]
                     + wr * PAGE_BYTES / bw[r])
    return t_lat, times


def tier_interval_outcome(mach, acc, mig_up, mig_down):
    """N-tier interval cost (jnp mirror of
    machine_spec.interval_outcome_host, f32).

    ``mach``: TieredMachineSpec leaves [R]; ``acc``: list/array of R f32
    access counts; ``mig_up``/``mig_down``: f32 [R-1] pages crossing each
    adjacent pair.  Returns (wall_s, slow_share, app_bw_frac_raw,
    slow_bw_frac_raw); the *_raw ratios are unclamped (module docstring).
    """
    R = mach.lat_ns.shape[0]
    t_lat, times = _tier_times(mach, acc, mig_up, mig_down)

    rest_max = times[1]
    for r in range(2, R):
        rest_max = jnp.maximum(rest_max, times[r])
    wall = jnp.maximum(jnp.maximum(t_lat, times[0]),
                       jnp.maximum(rest_max, 1e-12))

    rest_acc = acc[1]
    for r in range(2, R):
        rest_acc = rest_acc + acc[r]
    slow_share = rest_acc / jnp.maximum(acc[0] + rest_acc, 1e-9)
    app_raw = times[0] / jnp.maximum(t_lat, jnp.maximum(rest_max, 1e-12))
    slow_raw = rest_max / jnp.maximum(t_lat, jnp.maximum(times[0], 1e-12))
    return wall, slow_share, app_raw, slow_raw


def interval_accounting_impl(mach, true_counts, tier, mig_up, mig_down):
    """Full per-interval cost/accounting step, shared with the numpy engine.

    Returns (acc_fast, acc_slow, wall_s, slow_share, app_bw_frac_raw) as
    f32 scalars; acc_fast/acc_slow aggregate tier 0 vs everything below.
    In CRN mode the numpy engine calls the jitted ``interval_accounting``
    so its arithmetic is bit-identical to the scan engine's.
    """
    R = mach.lat_ns.shape[0]
    true = jnp.asarray(true_counts, jnp.float32)
    accs, _ = tier_access_split(true, tier, R)
    wall, slow_share, app_raw, _ = tier_interval_outcome(
        mach, accs, jnp.asarray(mig_up, jnp.float32),
        jnp.asarray(mig_down, jnp.float32))
    acc_slow = accs[1]
    for r in range(2, R):
        acc_slow = acc_slow + accs[r]
    return accs[0], acc_slow, wall, slow_share, app_raw


interval_accounting = jax.jit(interval_accounting_impl)


def tier_utilization_impl(mach, true_counts, tier, mig_up, mig_down):
    """Per-tier bandwidth utilization f32 [R]: each tier's bandwidth time
    as a fraction of the interval wall time.

    The tier-native policy signal (protocol.PolicySpec.tier_policy):
    ``scheduler.pair_budgets`` runs the BS formula against a pair's
    more-saturated endpoint, so policies back migrations off whichever
    tier of a hop is the bottleneck.  Only tier-native programs compute
    it (statically gated in both engines), so existing compiled paths are
    untouched.  Neutral padded tiers (bw inf) report 0.
    """
    R = mach.lat_ns.shape[0]
    true = jnp.asarray(true_counts, jnp.float32)
    accs, _ = tier_access_split(true, tier, R)
    t_lat, times = _tier_times(mach, accs, jnp.asarray(mig_up, jnp.float32),
                               jnp.asarray(mig_down, jnp.float32))
    stack = jnp.stack(times)
    wall = jnp.maximum(jnp.maximum(t_lat, stack.max()), 1e-12)
    return stack / wall


tier_utilization = jax.jit(tier_utilization_impl)


# ------------------------------------------------------------- migrations
def apply_tier_migrations(tier, promote, demote, caps):
    """Adjacent-pair hop migrations over an i32 tier index, fixed shape.

    ``promote``/``demote`` follow the padded-index contract
    (baselines/protocol.py).  Demotions apply first, in priority order:
    each valid entry (page not already in the bottom tier) cascades down
    to the first tier below its source with free capacity — the bottom
    (``caps[-1] == n``) always has room, so demotions never fail and no
    tier exceeds its capacity.  Promotions then move pages to tier 0,
    capped by tier-0 room after demotions; excess requests are dropped.
    At N=2 the executed sets are bitwise those of the legacy boolean
    ``apply_padded_migrations``.

    Returns (tier, pexec, dexec, mig_up, mig_down): the new placement,
    boolean executed masks aligned with the padded arrays, and i32 [R-1]
    counts of pages crossing each adjacent pair (for per-tier bandwidth
    charging).
    """
    R = caps.shape[0]
    n = tier.shape[0]
    i32 = jnp.int32

    d_safe = jnp.where(demote >= 0, demote, 0)
    src = tier[d_safe]
    dexec = (demote >= 0) & (src < R - 1)
    dest = jnp.full(demote.shape, R - 1, i32)
    landed = jnp.zeros(demote.shape, bool)
    for r in range(1, R - 1):
        # occupancy after departures: every demoted page leaves its source
        # tier (it always lands somewhere below), freeing that slot.
        occ_r = (tier == r).sum() - (dexec & (src == r)).sum()
        cand = dexec & (~landed) & (src < r)
        rank = jnp.cumsum(cand.astype(i32)) - 1
        land = cand & (rank < caps[r] - occ_r)
        dest = jnp.where(land, r, dest)
        landed = landed | land
    tier = tier.at[jnp.where(dexec, demote, n)].set(dest, mode="drop")

    p_safe = jnp.where(promote >= 0, promote, 0)
    p_src = tier[p_safe]
    p_ok = (promote >= 0) & (p_src > 0)
    room = caps[0] - (tier == 0).sum().astype(i32)
    rank = jnp.cumsum(p_ok.astype(i32)) - 1
    pexec = p_ok & (rank < room)
    tier = tier.at[jnp.where(pexec, promote, n)].set(0, mode="drop")

    mig_up = jnp.stack([(pexec & (p_src > j)).sum().astype(i32)
                        for j in range(R - 1)])
    mig_down = jnp.stack([(dexec & (src <= j) & (dest > j)).sum().astype(i32)
                          for j in range(R - 1)])
    return tier, pexec, dexec, mig_up, mig_down


def apply_targeted_migrations(tier, pages, dst, caps):
    """Tier-TARGETED migrations: each valid entry of ``pages`` (sentinel
    -1 padded, priority order, unique per direction) requests a move to
    ``dst[i]``; ``DST_BELOW`` resolves to "first tier below the source
    with room" (the hop-chain demotion cascade).

    Execution order mirrors ``apply_tier_migrations`` exactly:

      * DOWN moves (resolved dst > src) run first, in priority order.
        A down-mover lands at the shallowest tier r >= its requested dst
        with free capacity (cascading deeper when full; the bottom always
        has room), so every down-mover leaves its source — which is what
        keeps the occupancy-after-departures precomputation valid.
      * UP moves (dst < src) then run per destination tier, shallowest
        first, each reading occupancy AFTER the downs and any earlier
        ups; requests that don't fit their exact destination are DROPPED
        (never cascaded), like hop-chain promotions.

    With the binary shim's inputs — demotions first with dst=DST_BELOW,
    then promotions with dst=0 — every expression reduces to the
    corresponding one in ``apply_tier_migrations``, and all arithmetic is
    integer/boolean, so the executed sets (and everything downstream) are
    bitwise identical.  Returns (tier, up_exec, down_exec, mig_up,
    mig_down) with the executed masks aligned to ``pages``.

    TRAILING-SENTINEL INVARIANT (load-bearing for the union fabric,
    simulator/fabric.py): appending sentinel (-1) entries AFTER a plan's
    real moves is a bitwise no-op — invalid entries join neither phase,
    and the cumsum admission ranks only count candidates, so earlier
    entries' prefix sums are untouched.  This is what lets ``UnionSpec``
    widen every member family's move list to one shared ``pad_mv``.
    """
    R = caps.shape[0]
    n = tier.shape[0]
    i32 = jnp.int32

    safe = jnp.where(pages >= 0, pages, 0)
    valid = pages >= 0
    src = tier[safe]
    dst = jnp.where(dst == DST_BELOW, src + 1, dst)
    dst = jnp.clip(dst, 0, R - 1)
    down = valid & (dst > src)           # src == R-1 can never move down

    dest = jnp.full(pages.shape, R - 1, i32)
    landed = jnp.zeros(pages.shape, bool)
    for r in range(1, R - 1):
        # occupancy after departures: every down-mover leaves its source
        # tier (it always lands somewhere below), freeing that slot.
        occ_r = (tier == r).sum() - (down & (src == r)).sum()
        cand = down & (~landed) & (dst <= r)
        rank = jnp.cumsum(cand.astype(i32)) - 1
        land = cand & (rank < caps[r] - occ_r)
        dest = jnp.where(land, r, dest)
        landed = landed | land
    tier = tier.at[jnp.where(down, pages, n)].set(dest, mode="drop")
    mig_down = jnp.stack([(down & (src <= j) & (dest > j)).sum().astype(i32)
                          for j in range(R - 1)])

    # up phase: destination tiers shallowest-first; sources re-read from
    # the updated placement (post-downs, post-earlier-ups), so room freed
    # by ups OUT of a tier is visible to ups INTO it.
    up_exec = jnp.zeros(pages.shape, bool)
    up_from = jnp.zeros(pages.shape, i32)
    for r in range(R - 1):
        u_src = tier[safe]
        cand = valid & (~down) & (dst == r) & (u_src > r)
        room = caps[r] - (tier == r).sum().astype(i32)
        rank = jnp.cumsum(cand.astype(i32)) - 1
        take = cand & (rank < room)
        up_from = jnp.where(take, u_src, up_from)
        tier = tier.at[jnp.where(take, pages, n)].set(r, mode="drop")
        up_exec = up_exec | take
    mig_up = jnp.stack([(up_exec & (up_from > j) & (dst <= j)).sum()
                        .astype(i32) for j in range(R - 1)])
    # every down executes: the cascade bottoms out at R-1, which has room.
    return tier, up_exec, down, mig_up, mig_down


def apply_padded_migrations(in_fast, promote, demote, k: int):
    """Two-tier boolean form, kept for the policy-protocol property tests
    and any binary-placement caller.

    ``promote``/``demote`` follow the padded-index contract
    (baselines/protocol.py): i32 arrays of independent widths whose ``-1``
    entries are padding; valid entries are page indices in priority order.
    Semantics identical to the numpy engine's variable-length version:
    demotions of pages actually in the fast tier are applied first; then
    promotions of pages not (any longer) in the fast tier, in plan order,
    capped by the free capacity after demotions.

    Returns (in_fast, pexec, dexec): the new residency plus boolean masks
    (aligned with the padded arrays) of the executed migrations.
    """
    n = in_fast.shape[0]
    d_safe = jnp.where(demote >= 0, demote, 0)
    dexec = (demote >= 0) & in_fast[d_safe]
    in_fast = in_fast.at[jnp.where(dexec, demote, n)].set(False, mode="drop")

    p_safe = jnp.where(promote >= 0, promote, 0)
    p_ok = (promote >= 0) & (~in_fast[p_safe])
    room = k - in_fast.sum().astype(jnp.int32)
    rank = jnp.cumsum(p_ok.astype(jnp.int32)) - 1
    pexec = p_ok & (rank < room)
    in_fast = in_fast.at[jnp.where(pexec, promote, n)].set(True, mode="drop")
    return in_fast, pexec, dexec


def wasteful_update(t, promoted_at, demoted_at, promote, demote, pexec,
                    dexec):
    """WASTE_WINDOW accounting for one interval (t = 0-based engine index).

    Returns (wasteful_this_interval, promoted_at, demoted_at).
    """
    n = promoted_at.shape[0]
    p_safe = jnp.where(pexec, promote, 0)
    d_safe = jnp.where(dexec, demote, 0)
    waste = (pexec & (t - demoted_at[p_safe] <= WASTE_WINDOW)).sum() \
        + (dexec & (t - promoted_at[d_safe] <= WASTE_WINDOW)).sum()
    promoted_at = promoted_at.at[jnp.where(pexec, promote, n)].set(
        t, mode="drop")
    demoted_at = demoted_at.at[jnp.where(dexec, demote, n)].set(
        t, mode="drop")
    return waste.astype(jnp.int32), promoted_at, demoted_at
