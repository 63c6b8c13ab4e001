"""Functional policy protocol: pure ``init``/``step`` over pytree state.

Every tiering policy — ARMS and all baselines — is expressed as a
``PolicySpec``: a pytree dataclass whose *leaves* are the policy's tunable
knobs (f32/i32 scalars, batchable into sweep lanes) and whose *meta* fields
are static shape/identity data (name, pad widths, flags).  The behaviour is
a set of pure, jittable functions over an immutable ``PolicyState`` pytree:

    state = spec.init(n_pages, k, machine)
    state = spec.observe(state, observed)        # cheap, every interval
    fire  = spec.fires(state)                    # is the policy pass due?
    state, promote, demote = spec.policy(state, slow_bw, app_bw, k)
    state, promote, demote = spec.step(state, observed, slow_bw, app_bw, k)

``step`` is the composed reference semantics (observe + cond(fires) around
policy).  The split exists so the compiled scan engine can hoist the
cadence gate to a *scalar* ``lax.cond`` across sweep lanes (see
scan_engine.py) while the numpy reference engine uses ``step`` as-is.

Padded-index contract
---------------------
``promote``/``demote`` are fixed-shape i32 arrays of widths
``spec.pad_promote(n, k)`` / ``spec.pad_demote(n, k)``.  Entries equal to
the sentinel ``-1`` are padding and are skipped; the remaining entries are
page indices in priority order (hottest/most-urgent first).  The engines
execute demotions first, then promotions capped by free capacity — see
``simjax.apply_padded_migrations`` (the two-tier form, which the serving
pools run) and the variable-length equivalent in ``engine.run`` (numpy
engine); both agree exactly (property-tested in
tests/test_policy_protocol.py).  The scan engine runs the N-tier form,
``simjax.apply_tier_migrations``.

Tier-native contract
--------------------
Binary promote/demote only speaks about tier 0; middle tiers of an N-tier
chain are reachable solely through the engine's hop-chain cascade.  Specs
that set ``tier_native = True`` implement ``tier_policy`` instead and see
the whole chain:

    state, pages, dst = spec.tier_policy(
        state, tier_util, slow_bw, app_bw, k, caps)

``tier_util`` is the f32 [R] per-tier bandwidth utilization of the last
interval (simjax.tier_utilization); ``caps`` the i32 [R] resolved per-tier
capacities.  ``pages``/``dst`` are ``pad_moves(n, k)``-wide tier-TARGETED
moves: sentinel-padded page indices in priority order (down-moves first,
then up-moves) with explicit destination tiers (``simjax.DST_BELOW``
requests the hop-chain demotion cascade).  The engines execute them with
``simjax.apply_targeted_migrations``.  Per-pair migration budgets come
from ``scheduler.pair_budgets(tier_util, bs_max)`` and are enforced
policy-side by ``tier_plan``/``pair_limit`` below, so both engines see
identical plans and a policy's residency belief stays exact.

Binary specs need no changes: the base ``tier_policy`` is a shim that
concatenates ``policy``'s demotions (dst=DST_BELOW) and promotions
(dst=0), which ``apply_targeted_migrations`` executes bitwise-identically
to the hop-chain path.  A union sweep (``experiment.sweep(...,
dispatch="union")``, simulator/fabric.py) runs every binary member this
way; tests/test_tier_native.py compares it with the grouped dispatch for
all six binary families.

``LegacyPolicyAdapter`` wraps a spec back into the stateful ``Policy``
interface so the numpy reference engine keeps replaying every policy with
bitwise-identical decisions — that cross-engine agreement is the
correctness oracle for the compiled scan engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.base import Policy
from repro.simulator.simjax import DST_BELOW
from repro.utils.topk import top_k

SENTINEL = -1


# --------------------------------------------------------------- helpers
def ranked_take(key, mask, pad: int, limit=None):
    """First ``limit`` indices of ``mask`` ordered by ``key`` ascending.

    Ties break by ascending page index (jnp.argsort is stable), matching a
    stable numpy argsort applied over ``np.flatnonzero(mask)``.  Returns a
    ``pad``-wide sentinel-padded i32 index array (valid entries form a
    prefix) plus the valid count.  ``limit`` may be a traced scalar or
    static int; ``None`` keeps every masked index (up to ``pad``).
    """
    n = key.shape[0]
    pad = max(1, min(pad, n))
    # top_k, not argsort: XLA's generic sort is ~50x slower on CPU at
    # simulator scale, and top_k's tie rule (lower index first) matches a
    # stable ascending argsort exactly (utils.topk keeps the rule on TPU).
    _, order = top_k(jnp.where(mask, -key.astype(jnp.float32), -jnp.inf),
                     pad)
    order = order.astype(jnp.int32)
    count = mask.sum().astype(jnp.int32)
    if limit is not None:
        count = jnp.minimum(count, jnp.asarray(limit, jnp.int32))
    count = jnp.minimum(count, pad)
    keep = jnp.arange(pad, dtype=jnp.int32) < count
    return jnp.where(keep, order, SENTINEL), count


def truncate_ranked(idx, count):
    """Keep the first ``count`` valid (prefix) entries of a ranked list."""
    keep = jnp.arange(idx.shape[0], dtype=jnp.int32) < count
    return jnp.where(keep, idx, SENTINEL)


def scatter_set(dst, idx, value: bool):
    """Set ``dst[idx] = value`` for non-sentinel entries of ``idx``."""
    n = dst.shape[0]
    safe = jnp.where(idx >= 0, idx, n)
    return dst.at[safe].set(value, mode="drop")


# ---------------------------------------------------------------- protocol
class PolicySpec:
    """Base of the functional policy protocol (subclass + pytree_dataclass).

    Class attributes are static protocol metadata; dataclass fields are the
    knob leaves.  All methods must be pure and traceable; ``self``'s leaves
    may be traced arrays (batched sweep lanes under vmap).
    """

    name: str = "base"
    #: pages migrated per policy pass; models serial (kernel-thread) vs
    #: batched (Nimble/ARMS) migration mechanisms.  Specs that sweep shape-
    #: relevant knobs keep this a static meta field instead.
    migration_limit: int = 10 ** 9
    #: observed counts are TRUE counts (oracle upper bound), not PEBS samples
    wants_true_counts: bool = False
    #: per-slow-access application overhead of the policy mechanism (TPP
    #: NUMA hint faults); charged by both engines.
    slow_access_extra_ns: float = 0.0
    #: whether sampling_period/mode depend on runtime state (ARMS) or are
    #: constant per spec (every baseline).
    dynamic_sampling_period: bool = False
    has_mode: bool = False
    #: specs that see and target the tier vector directly implement
    #: ``tier_policy`` and set this; binary specs reach the targeted
    #: executor through the base shim (module docstring).
    tier_native: bool = False
    #: specs whose LANES mix observation kinds (simulator/fabric.py union
    #: specs: some lanes want true counts, some sampled; some carry a
    #: per-lane mechanism overhead).  The scan engine then consults the
    #: per-lane hooks below instead of the class-level flags.
    mixed_observation: bool = False

    DEFAULT_SAMPLE_PERIOD = 10_000.0

    # --- static shape contract -------------------------------------------
    def pad_promote(self, n: int, k: int) -> int:
        return max(1, min(n, self.migration_limit))

    def pad_demote(self, n: int, k: int) -> int:
        return max(1, min(n, self.migration_limit))

    def pad_moves(self, n: int, k: int) -> int:
        """Width of the tier-native ``pages``/``dst`` arrays (down-moves
        first, then up-moves — the shim's concatenation layout)."""
        return self.pad_demote(n, k) + self.pad_promote(n, k)

    # --- pure functions over pytree state --------------------------------
    def init(self, n_pages: int, k: int, machine):
        raise NotImplementedError

    def observe(self, state, observed):
        """Cheap per-interval accumulation (counts, faults, buffers)."""
        return state

    def fires(self, state):
        """Scalar bool: does the (expensive) policy pass run this interval?"""
        return jnp.asarray(True)

    def sampling_period(self, state):
        return jnp.float32(self.DEFAULT_SAMPLE_PERIOD)

    def min_sampling_period(self) -> float:
        """Host-side lower bound on the sampling period (static shapes)."""
        return float(self.DEFAULT_SAMPLE_PERIOD)

    def mode_of(self, state):
        """Controller mode for the SimResult timeline (ARMS; 0 elsewhere)."""
        return jnp.zeros((), jnp.int32)

    # --- per-lane hooks (``mixed_observation`` specs only) ----------------
    def wants_true_lane(self):
        """Scalar bool: does THIS lane observe true counts (oracle lanes
        of a union spec)?  Only consulted when ``mixed_observation``."""
        return jnp.asarray(type(self).wants_true_counts)

    def slow_extra_lane(self):
        """Scalar f32: this lane's per-slow-access overhead in ns (TPP
        lanes of a union spec).  Only consulted when ``mixed_observation``;
        0.0 lanes add a bitwise no-op (+0.0) to the wall term."""
        return jnp.float32(type(self).slow_access_extra_ns)

    def policy(self, state, slow_bw, app_bw, k: int):
        """-> (state, promote, demote): the full policy pass.

        ``promote``/``demote`` follow the padded-index contract (module
        docstring).  Only called on intervals where ``fires(state)``.
        """
        raise NotImplementedError

    def step(self, state, observed, slow_bw, app_bw, k: int):
        """Reference composition: observe, then cond(fires) around policy."""
        n = observed.shape[0]
        state = self.observe(state, observed)
        pad_p, pad_d = self.pad_promote(n, k), self.pad_demote(n, k)

        def fire(s):
            return self.policy(s, slow_bw, app_bw, k)

        def skip(s):
            return (s, jnp.full((pad_p,), SENTINEL, jnp.int32),
                    jnp.full((pad_d,), SENTINEL, jnp.int32))

        return jax.lax.cond(self.fires(state), fire, skip, state)

    # --- tier-native contract --------------------------------------------
    def tier_policy(self, state, tier_util, slow_bw, app_bw, k: int, caps):
        """-> (state, pages, dst): tier-targeted moves (module docstring).

        Base implementation is the BINARY SHIM: run the classic
        promote/demote pass and emit demotions (dst=DST_BELOW, the
        hop-chain cascade) followed by promotions (dst=0).  Executed
        through ``simjax.apply_targeted_migrations`` this is bitwise the
        hop-chain path, for every binary policy.
        """
        state, promote, demote = self.policy(state, slow_bw, app_bw, k)
        pages = jnp.concatenate([demote, promote])
        dst = jnp.concatenate(
            [jnp.full(demote.shape, DST_BELOW, jnp.int32),
             jnp.zeros(promote.shape, jnp.int32)])
        return state, pages, dst

    def step_tiers(self, state, observed, tier_util, slow_bw, app_bw,
                   k: int, caps):
        """Reference composition of the tier-native contract: observe,
        then cond(fires) around ``tier_policy`` (numpy-engine path)."""
        n = observed.shape[0]
        state = self.observe(state, observed)
        pm = self.pad_moves(n, k)

        def fire(s):
            return self.tier_policy(s, tier_util, slow_bw, app_bw, k, caps)

        def skip(s):
            return (s, jnp.full((pm,), SENTINEL, jnp.int32),
                    jnp.zeros((pm,), jnp.int32))

        return jax.lax.cond(self.fires(state), fire, skip, state)


def capacity_victims(in_fast, cold_key, cold_mask, n_want, k: int, pad_d: int,
                     extra_need=0):
    """Shared victim selection: free slots, then coldest-first demotions.

    Returns (victims, n_victims, n_take) where ``n_take`` caps the
    promotion list at ``free + n_victims`` (the engines never exceed
    capacity, so a policy that respects this bound sees every request
    executed and its internal residency belief stays exact).
    """
    free = (k - in_fast.sum()).astype(jnp.int32)
    need = jnp.maximum(jnp.maximum(n_want - free, extra_need), 0)
    victims, n_vict = ranked_take(cold_key, cold_mask, pad_d, need)
    n_take = jnp.minimum(n_want, free + n_vict)
    return victims, n_vict, n_take


# ------------------------------------------------ tier-native plan helpers
def rank_desc(score):
    """Dense 0-based rank of each page under DESCENDING score (rank 0 =
    hottest; ties break by ascending page index — argsort is stable)."""
    n = score.shape[0]
    order = jnp.argsort(-score.astype(jnp.float32))
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


def rank_partition(rank, caps):
    """Per-tier scores -> target placement: fill tiers shallowest-first by
    rank against the capacity ladder (page with rank < caps[0] targets
    tier 0, the next caps[1] ranks tier 1, ...).  Zero-capacity padded
    tiers are skipped automatically.  Returns i32 [n] target tiers."""
    cum = jnp.cumsum(caps)
    return jnp.sum(rank[:, None] >= cum[None, :-1], axis=1).astype(jnp.int32)


def pair_limit(lo, hi, valid, budgets):
    """Per-pair budget filter over a priority-ordered move list.

    Entry i crosses adjacent pairs ``lo[i] <= j < hi[i]``; it survives iff
    for EVERY crossed pair fewer than ``budgets[j]`` earlier valid entries
    cross that pair.  Counting earlier candidates (not earlier survivors)
    keeps the filter one vectorized pass per pair; it is conservative —
    never over budget, occasionally under when an earlier move was itself
    dropped by a different pair.  Returns the surviving-entry mask.
    """
    ok = valid
    for j in range(budgets.shape[0]):
        crosses = valid & (lo <= j) & (j < hi)
        rank = jnp.cumsum(crosses.astype(jnp.int32)) - 1
        ok = ok & (~crosses | (rank < budgets[j]))
    return ok


def tier_plan(score, cur, target, caps, budgets, pad_down: int, pad_up: int):
    """Feasible tier-targeted moves from a desired placement.

    ``score`` f32 [n] per-page hotness, ``cur`` i32 [n] the policy's
    residency belief, ``target`` i32 [n] the desired placement (e.g. from
    ``rank_partition``), ``caps`` i32 [R], ``budgets`` i32 [R-1] per-pair
    migration budgets (scheduler.pair_budgets).  Returns (pages, dst,
    new_cur): a ``pad_down + pad_up``-wide sentinel-padded move list —
    down-moves first (coldest-first), then up-moves (hottest-first) —
    that ``simjax.apply_targeted_migrations`` is GUARANTEED to execute
    verbatim (down-moves land exactly at their target, up-moves are all
    admitted), because admission here mirrors the executor's order:
    budgets first, then capacity bottom-up for downs / shallowest-first
    for ups with departures freeing slots.  ``new_cur`` therefore stays
    an exact belief of the engine-side placement.
    """
    i32 = jnp.int32
    R = caps.shape[0]
    n = score.shape[0]
    target = jnp.clip(target, 0, R - 1)
    occ = jnp.stack([(cur == r).sum() for r in range(R)]).astype(i32)

    # down-moves: coldest-first, budget-filtered, then capacity-admitted
    # bottom-up (deeper targets admit first; their departures free slots
    # for shallower targets — the executor sees the same order).
    d_pages, _ = ranked_take(score, target > cur, pad_down)
    d_safe = jnp.where(d_pages >= 0, d_pages, 0)
    d_valid = d_pages >= 0
    d_cur = jnp.where(d_valid, cur[d_safe], 0)
    d_tgt = jnp.where(d_valid, target[d_safe], R - 1)
    d_ok = pair_limit(d_cur, d_tgt, d_valid, budgets)
    adm_d = jnp.zeros(d_pages.shape, bool)
    for r in range(R - 1, 0, -1):
        dep = (adm_d & (d_cur == r)).sum().astype(i32)
        room = caps[r] - occ[r] + dep
        cand = d_ok & (d_tgt == r) & (~adm_d)
        rank = jnp.cumsum(cand.astype(i32)) - 1
        adm_d = adm_d | (cand & (rank < room))
    d_pages = jnp.where(adm_d, d_pages, SENTINEL)
    rem = jnp.stack([
        budgets[j] - (adm_d & (d_cur <= j) & (j < d_tgt)).sum().astype(i32)
        for j in range(R - 1)])
    rem = jnp.maximum(rem, 0)
    occ2 = occ + jnp.stack([
        (adm_d & (d_tgt == r)).sum() - (adm_d & (d_cur == r)).sum()
        for r in range(R)]).astype(i32)

    # up-moves: hottest-first, remaining budgets, capacity-admitted
    # shallowest-destination-first against the post-down occupancy.
    u_pages, _ = ranked_take(-score, target < cur, pad_up)
    u_safe = jnp.where(u_pages >= 0, u_pages, 0)
    u_valid = u_pages >= 0
    u_cur = jnp.where(u_valid, cur[u_safe], 0)
    u_tgt = jnp.where(u_valid, target[u_safe], 0)
    u_ok = pair_limit(u_tgt, u_cur, u_valid, rem)
    adm_u = jnp.zeros(u_pages.shape, bool)
    for r in range(R - 1):
        dep = (adm_u & (u_cur == r)).sum().astype(i32)
        room = caps[r] - occ2[r] + dep
        cand = u_ok & (u_tgt == r) & (~adm_u)
        rank = jnp.cumsum(cand.astype(i32)) - 1
        adm_u = adm_u | (cand & (rank < room))
    u_pages = jnp.where(adm_u, u_pages, SENTINEL)

    new_cur = cur.at[jnp.where(adm_d, d_pages, n)].set(
        d_tgt, mode="drop")
    new_cur = new_cur.at[jnp.where(adm_u, u_pages, n)].set(
        u_tgt, mode="drop")
    pages = jnp.concatenate([d_pages, u_pages])
    dst = jnp.concatenate([d_tgt, u_tgt])
    return pages, dst, new_cur


# ----------------------------------------------------------- legacy bridge
@functools.partial(jax.jit, static_argnames=("k",))
def _protocol_step(spec, state, observed, slow_bw, app_bw, k: int):
    return spec.step(state, observed, slow_bw, app_bw, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _protocol_step_tiers(spec, state, observed, tier_util, slow_bw, app_bw,
                         k: int, caps):
    return spec.step_tiers(state, observed, tier_util, slow_bw, app_bw,
                           k, caps)


class LegacyPolicyAdapter(Policy):
    """A functional ``PolicySpec`` exposed as a stateful numpy-engine Policy.

    The adapter holds the pytree state between intervals and calls the
    spec's jitted ``step`` once per interval; padded outputs are converted
    to the engine's variable-length index lists by dropping sentinels (order
    preserved).  Decisions are therefore bitwise-identical to the compiled
    scan engine's — the basis of the cross-engine equivalence tests.
    """

    def __init__(self, spec: PolicySpec):
        self.spec = spec
        self.name = spec.name
        self.slow_access_extra_ns = spec.slow_access_extra_ns

    def reset(self, n_pages, k, machine):
        self.n, self.k = n_pages, k
        self.state = self.spec.init(n_pages, k, machine)
        self._period = float(self.spec.sampling_period(self.state))

    def sampling_period(self):
        return self._period

    def wants_true_counts(self):
        return self.spec.wants_true_counts

    @property
    def mode(self) -> int:
        if not type(self.spec).has_mode:
            return 0
        return int(self.spec.mode_of(self.state))

    @property
    def tier_native(self) -> bool:
        return type(self.spec).tier_native

    def step(self, observed, slow_bw_frac, app_bw_frac):
        self.state, promote, demote = _protocol_step(
            self.spec, self.state, jnp.asarray(observed, jnp.float32),
            jnp.float32(slow_bw_frac), jnp.float32(app_bw_frac), self.k)
        if type(self.spec).dynamic_sampling_period:
            self._period = float(self.spec.sampling_period(self.state))
        promote = np.asarray(promote, np.int64)
        demote = np.asarray(demote, np.int64)
        return promote[promote >= 0], demote[demote >= 0]

    def step_tiers(self, observed, slow_bw_frac, app_bw_frac, tier_util,
                   caps):
        """Tier-native interval: -> (pages, dst) aligned i64 arrays with
        sentinels dropped (priority order preserved)."""
        self.state, pages, dst = _protocol_step_tiers(
            self.spec, self.state, jnp.asarray(observed, jnp.float32),
            jnp.asarray(tier_util, jnp.float32),
            jnp.float32(slow_bw_frac), jnp.float32(app_bw_frac), self.k,
            jnp.asarray(caps, jnp.int32))
        if type(self.spec).dynamic_sampling_period:
            self._period = float(self.spec.sampling_period(self.state))
        pages = np.asarray(pages, np.int64)
        dst = np.asarray(dst, np.int64)
        keep = pages >= 0
        return pages[keep], dst[keep]
