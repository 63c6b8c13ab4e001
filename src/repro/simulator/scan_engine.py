"""Compiled ``lax.scan`` simulation engine + lane-batched sweeps, for EVERY
policy speaking the functional protocol (baselines/protocol.py).

The numpy engine (engine.py) replays a trace with a Python loop and one
policy call per interval — fine as a *reference*, but host<->device
round-trips and per-call dispatch dominate, and tuning studies replay
dozens of full simulations sequentially.  Here the entire replay — PEBS
sampling, the policy (via its pure ``observe``/``fires``/``policy``
functions), engine-side capacity/validity enforcement, the interval cost
model, and wasteful/recall accounting — is one ``jax.lax.scan`` over
intervals, compiled once and executed with zero per-interval host syncs.
On top of it:

  * ``simulate``             — single run of ANY spec, SimResult output;
  * ``sweep_seeds``          — batched over PRNG keys (sampling-noise
    study: per-lane noise drawn from keys threaded through the carry);
  * ``sweep_policy_configs`` — batched over a policy family's knobs: one
    spec per lane, all lanes sharing one CRN noise field (paired
    comparisons — config differences are never confounded with noise);
  * ``arms_sim`` / ``sweep_arms_configs`` — the ARMS-specialized wrappers
    (the latter precomputes both mode-dependent observation grids once and
    broadcasts them, so ARMS config lanes pay zero sampling cost);
  * ``simulate_workload`` / ``sweep_workloads`` / ``sweep_workload_configs``
    — the trace-SYNTHESIS path: the scan carries ``WorkloadSpec`` state
    (simulator/workload_spec.py) and synthesizes ``true = work * probs``
    plus the oracle top-k mask on device each interval; per-lane storage
    is O(n), nothing ``[T, n]`` exists on host or device.

MACHINES are sweep lanes too: every entry point accepts a registry name
(``machines.get``), a legacy two-tier ``MachineSpec``, or an N-tier
``TieredMachineSpec`` (simulator/machine_spec.py), and the machine's
f32 per-tier leaves ride the same lane axis as policy and workload
knobs — ``experiment.sweep`` flattens a P×W×M×S axis product into ONE
dispatch of this engine.  The scan carry holds an i32 per-page tier
index; migrations are adjacent-pair hop chains
(``simjax.apply_tier_migrations``) and the interval cost charges each
tier's bandwidth separately.  N=2 replays are bitwise-identical to the
historical boolean two-tier engine (tests/test_machine_spec.py).

Batching layout: sweep lanes live in an explicit leading axis of the scan
carry rather than under an outer ``vmap`` of the whole simulation.  This
matters: the policy-pass gate is a ``lax.cond`` on the *scalar*
``any(lane fires)``, so on intervals where no lane's policy is due the
expensive pass (top-k / sort ranking dominates the profile) is genuinely
skipped — an outer vmap would turn that cond into a select and pay the
policy every interval.  Inside the fire branch the policy IS ``jax.vmap``-ed
over lanes, with per-lane knobs read from the spec's batched leaves.

Engine-side bookkeeping is shared with the numpy engine via
``simulator/simjax.py``; with a common-random-number uniform field
(``sample_u``) the two engines agree bitwise on sampling and interval
arithmetic, so promotions/demotions/wasteful counts match exactly for every
policy (see tests/test_scan_engine.py).

NOTE on the module boundary: ``simulator/experiment.py`` (the axis-product
orchestrator) assembles lanes directly on this module's underscore helpers
(``_sim_jit``/``_sim_synth_jit``, ``_stack_specs``/``_stack_workloads``/
``_take_lanes``, ``_need_normal``/``_synth_need_normal``, ``_to_result``/
``_timelines_lane_major``/``_record_dispatch``).  They are a load-bearing
internal contract shared by exactly those two modules — change their
signatures in lockstep.
"""
from __future__ import annotations

import contextlib
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.arms_policy import SWEEPABLE, ARMSSpec
from repro.core.state import ARMSConfig
from repro.kernels.interval_step import ops as interval_ops
from repro.simulator import machine_spec, machines, simjax, workload_spec
from repro.simulator.engine import SimResult, oracle_topk_masks
from repro.simulator.sampling import (_NORMAL_SWITCH, pebs_sample_from_uniform,
                                      synth_uniform_row, uniform_field)
from repro.utils.topk import top_k

__all__ = [
    "SWEEPABLE", "simulate", "sweep_seeds", "sweep_policy_configs",
    "arms_sim", "sweep_arms_configs", "simulate_workload",
    "sweep_workloads", "sweep_workload_configs", "last_dispatch",
    "count_dispatches", "DispatchCounter",
]

#: Info about the most recent compiled dispatch (lanes, sampling mode).
#: The CI quick gates read this to assert tuning and machine sweeps stay
#: lane-batched instead of silently regressing to a sequential loop.
last_dispatch: dict = {}


class DispatchCounter:
    """Live tally handed out by ``count_dispatches``: ``count`` dispatches
    so far, ``records`` their ``_record_dispatch`` info dicts in order."""

    def __init__(self):
        self.count = 0
        self.records: list = []

    @property
    def last(self) -> dict:
        return self.records[-1] if self.records else {}


#: counters currently open via ``count_dispatches`` (nesting is fine: every
#: open counter sees every dispatch issued inside its region).
_active_counters: list = []


@contextlib.contextmanager
def count_dispatches():
    """Context-managed dispatch counter for gates and the search engine.

        with scan_engine.count_dispatches() as ctr:
            experiment.sweep(...)
        assert ctr.count == 1 and ctr.last["lanes"] == L

    Concurrent/nested measured regions cannot race: each region owns its
    counter and only dispatches issued within the region are tallied.
    """
    ctr = DispatchCounter()
    _active_counters.append(ctr)
    try:
        yield ctr
    finally:
        _active_counters.remove(ctr)


def _need_normal(trace, min_period: float) -> bool:
    """Static: can any page's sampling rate reach the normal-approx regime?

    When False the ndtri branch of the sampler is dead code and statically
    dropped; selected values are identical either way, so this never
    affects cross-engine equivalence.
    """
    return bool(np.max(trace) / float(min_period) >= _NORMAL_SWITCH)


def _bwhere(pred, a, b):
    """Per-lane select: pred [B], leaves [B] or [B, ...]."""
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred.reshape((-1,) + (1,) * (x.ndim - 1)),
                               x, y), a, b)


def _per_lane(fn, *args):
    """``fn`` over the leading lane axis of ``args``, one lane per loop
    step (``lax.map``) instead of batched (``vmap``).

    For the interval accounting's f32 page-row sums, which feed both the
    statistics and tier-native policy budgets: batched over [B, n] on a
    TPU v5e, a lane's row sum came out differently at different lane
    counts B, so a lane's results depended on how many lanes shared its
    program — its mesh shard (fabric.py).  Each loop step reduces
    one [n] row, the same computation at any B, and the same one the
    numpy engine's jitted single-lane ``simjax.interval_accounting``
    runs.
    """
    return jax.lax.map(lambda a: fn(*a), args)


def _lane_specs(spec, B: int):
    """Broadcast one spec's leaves to B identical sweep lanes."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.asarray(x),
                                   (B,) + jnp.shape(jnp.asarray(x))), spec)


def _stack_specs(specs):
    """Stack same-family specs leaf-wise into one lane-batched spec."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *specs)


def _take_lanes(pytree, idx):
    """Gather lanes of a lane-batched pytree along axis 0."""
    return jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0), pytree)


def _stack_workloads(wl_specs):
    """Stack WorkloadSpecs into one [W]-lane spec (component-count padded)."""
    S = max(sp.n_components for sp in wl_specs)
    return _stack_specs([workload_spec.pad_components(sp, S)
                         for sp in wl_specs])


def _mach_lanes(machine, B: int, n: int, k: int):
    """One machine broadcast to B lanes -> (mach [B,...], caps i32 [B, R])."""
    mach, caps = machine_spec.lane_stack([machines.get(machine)], n, k)
    idx = jnp.zeros((B,), jnp.int32)
    return _take_lanes(mach, idx), jnp.take(caps, idx, axis=0)


def _topk_mask(x, k: int):
    """Device oracle mask: exact top-k of ``x``, tie rule identical to the
    host ``oracle_topk_masks`` (strictly-greater first, then ascending
    index among threshold-equal values — ``lax.top_k``'s rule)."""
    _, idx = top_k(x, k)
    return jnp.zeros(x.shape, bool).at[idx].set(True)


def _init_carry(spec, B: int, n: int, k: int, mach, keys):
    f32 = jnp.float32
    cls = type(spec)
    R = mach.lat_ns.shape[-1]
    state = jax.vmap(lambda sp, mc: cls.init(sp, n, k, mc),
                     axis_size=B)(spec, mach)
    return dict(
        state=state,
        tier=jnp.full((B, n), R - 1, jnp.int32),   # start at the bottom
        promoted_at=jnp.full((B, n), -(10 ** 9), jnp.int32),
        demoted_at=jnp.full((B, n), -(10 ** 9), jnp.int32),
        t=jnp.zeros((), jnp.int32),
        key=keys,
        slow_bw=jnp.ones((B,), f32),      # everything starts slow
        app_bw=jnp.zeros((B,), f32),
        exec_time=jnp.zeros((B,), f32),
        promotions=jnp.zeros((B,), jnp.int32),
        demotions=jnp.zeros((B,), jnp.int32),
        wasteful=jnp.zeros((B,), jnp.int32),
        acc_fast_total=jnp.zeros((B,), f32),
        acc_total=jnp.zeros((B,), f32),
        recall_sum=jnp.zeros((B,), f32),
    )


def _simulate(spec, trace, oracle_mask, k: int, mach, caps, keys, sample,
              sampling: str, need_normal: bool, wl=None, wl_keys=None,
              noise_key=None, wl_rep: int = 1, n: int | None = None,
              wl_boost: bool = True, wl_drift: bool = True,
              interval_kernel: bool = True, reduce: str = "stack",
              tier_shim: bool = False, widx=None):
    """Traceable batched replay; returns a dict of [B] scalars + timelines.

    Lanes (= sweep entries) form the leading axis of every carried array,
    of every leaf of ``spec``, and of every leaf of ``mach`` (a
    ``TieredMachineSpec`` with [B, R]-shaped tier leaves; ``caps`` is the
    resolved i32 [B, R] per-tier capacity).  True counts come from one of
    two sources:
      * trace mode (``wl is None``): ``trace`` is a host-materialized
        [T, n] array scanned as xs, with the host-computed ``oracle_mask``;
      * synth mode: ``wl`` is a [W]-lane-batched ``WorkloadSpec`` whose
        state lives in the scan carry — each interval synthesizes
        ``true = work * probs`` on device (each workload lane feeding
        ``wl_rep`` consecutive policy lanes) and the oracle top-k mask is
        computed on device from the synthesized counts.  No [T, n] array
        exists anywhere; per-lane storage is O(n).  Workload
        re-randomization events are gated behind a scalar any-lane
        ``lax.cond`` exactly like the policy pass.

    ``sampling`` (static) selects the PEBS noise source:
      * "prng": per-lane keys threaded through the carry; per-interval
        uniforms transformed by the shared Poisson inverse-CDF;
      * "crn":  ``sample`` is a [T, n] uniform field, transformed per
        interval with each lane's sampling period — the path the numpy
        engine mirrors bitwise;
      * "crn_prng": one uniform row per interval drawn on device from
        ``noise_key`` (counter-based fold_in by t), shared across lanes —
        CRN pairing without any [T, n] field (synth-mode default);
      * "pre":  ``sample`` is a [T, P, n] stack of precomputed observation
        grids (one per period in the family's ``PRE_PERIODS``); lanes only
        select by ``spec.obs_index(state)``.

    ``interval_kernel`` (static) routes the interval hot path through the
    fused ``kernels/interval_step`` ops — threshold-select oracle masks
    instead of full ``lax.top_k`` + scatter, migrations + wasteful
    accounting hoisted inside the any-lane fire cond (bitwise a no-op on
    non-fire intervals, so the hop-chain gather/scatter work is genuinely
    skipped), and single-call fused accounting + recall.  Every route is
    bitwise-equal to the unfused path under CRN (tests/test_interval_step).

    ``reduce`` (static) selects the per-interval output layout:
      * "stack":  timelines stacked into [T, B] ys (historical layout);
      * "stream": timelines folded into running sums/extrema inside the
        scan carry — the scan emits NO ys, so per-lane output memory is
        O(n), not O(T).  The result dict then carries ``mean_*`` /
        ``max_promotions_interval`` summaries and no ``timeline_*`` keys.

    Specs with ``tier_native`` take the TIER-TARGETED route: the carry
    additionally holds the last interval's per-tier utilization (f32
    [B, R], ``simjax.tier_utilization``), the policy emits aligned
    ``(pages, dst)`` moves via ``tier_policy``, and the engine executes
    them with ``simjax.apply_targeted_migrations`` — up-moves count as
    promotions, down-moves as demotions, sharing the binary path's
    wasteful accounting.  ``tier_shim`` (static) forces BINARY specs
    through that same route via the base-class shim; it is bitwise-equal
    to the default hop-chain path (tests/test_tier_native.py), and exists
    so tests can assert exactly that.
    """
    assert reduce in ("stack", "stream")
    if wl is None:
        T, n = trace.shape
    else:
        T = sample.shape[0]
        wl_cls = type(wl)
    B = keys.shape[0]
    cls = type(spec)
    pad_p, pad_d = spec.pad_promote(n, k), spec.pad_demote(n, k)
    f32 = jnp.float32

    tn = cls.tier_native or tier_shim
    vobserve = jax.vmap(cls.observe)
    vfires = jax.vmap(cls.fires)
    vpolicy = jax.vmap(cls.policy, in_axes=(0, 0, 0, 0, None))
    vtier_policy = jax.vmap(cls.tier_policy,
                            in_axes=(0, 0, 0, 0, 0, None, 0))
    vperiod = jax.vmap(cls.sampling_period)
    vmode = jax.vmap(cls.mode_of)

    def observed_for(xs_sample, true_b, state, subs, t0):
        if cls.wants_true_counts:
            return true_b
        if sampling == "pre":
            idx = jax.vmap(cls.obs_index)(spec, state)          # [B]
            return xs_sample[idx]                               # [B, n]
        period = vperiod(spec, state)[:, None]                  # [B, 1]
        if sampling == "prng":
            u = jax.vmap(lambda s: jax.random.uniform(s, (n,), dtype=f32)
                         )(subs)
            sampled = pebs_sample_from_uniform(u, true_b, period,
                                               need_normal=need_normal)
        elif sampling == "crn_prng":
            u = synth_uniform_row(noise_key, t0, n)
            sampled = pebs_sample_from_uniform(u[None], true_b, period,
                                               need_normal=need_normal)
        else:
            sampled = pebs_sample_from_uniform(xs_sample[None], true_b,
                                               period,
                                               need_normal=need_normal)
        if cls.mixed_observation:
            # union lanes mixing observation kinds (fabric.py): oracle
            # lanes read true counts, the rest keep the sampled row the
            # whole batch shares — bitwise what each family's own
            # dispatch would observe.
            wt = jax.vmap(cls.wants_true_lane)(spec)            # [B]
            sampled = jnp.where(wt[:, None], true_b, sampled)
        return sampled

    # The interval body is partitioned into five named scopes — synth,
    # sample, policy, migrate, account — which reach the compiled
    # program's op_name metadata and so the device trace (PERF.md,
    # "Layers").  Scopes are metadata only: the computation is unchanged.
    def step(c, xs):
        with jax.named_scope("synth"):
            if wl is None:
                true, orc, xs_sample = xs
                true_b = jnp.broadcast_to(true[None], (B, n))    # [B, n]
                orc_b = jnp.broadcast_to(orc[None], (B, n))
                wst = None
            else:
                xs_sample = xs
                wst, tw = c["wl_state"], c["t"]
                due = jax.vmap(wl_cls.event_due, in_axes=(0, 0, None))(
                    wl, wst, tw)
                # scalar any-lane gate: permutation redraws (sorts) only
                # run on intervals where some workload lane has an event
                # due.
                wst = jax.lax.cond(
                    jnp.any(due),
                    lambda s: jax.vmap(
                        lambda w, st_: wl_cls.event(w, st_, tw, wl_boost))(
                        wl, s),
                    lambda s: s, wst)
                probs = jax.vmap(
                    lambda w, st_: wl_cls.probs_of(w, st_, tw, wl_drift))(
                    wl, wst)                                     # [W, n]
                workt = jax.vmap(wl_cls.work_of, in_axes=(0, 0, None))(
                    wl, wst, tw)                                 # [W]
                true_w = workt[:, None] * probs
                orc_w = (interval_ops.topk_mask(true_w, k)
                         if interval_kernel
                         else jax.vmap(lambda x: _topk_mask(x, k))(true_w))
                if widx is None:
                    true_b = jnp.repeat(true_w, wl_rep, axis=0)  # [B, n]
                    orc_b = jnp.repeat(orc_w, wl_rep, axis=0)
                else:
                    # sharded lanes (fabric.py): every shard synthesizes
                    # the full replicated [W] workload stack and gathers
                    # its own lanes' rows by GLOBAL workload index — a row
                    # gather is value-wise exactly the ``repeat`` above,
                    # so shard results are bitwise the unsharded path's.
                    true_b = jnp.take(true_w, widx, axis=0)      # [B, n]
                    orc_b = jnp.take(orc_w, widx, axis=0)
        state = c["state"]
        with jax.named_scope("sample"):
            split = jax.vmap(jax.random.split, out_axes=1)(c["key"])
            key, subs = split[0], split[1]
            observed = observed_for(xs_sample, true_b, state, subs, c["t"])
        t = c["t"] + 1
        with jax.named_scope("policy"):
            state = vobserve(spec, state, observed)
            do = vfires(spec, state)                            # [B]
            any_do = jnp.any(do)

        R = caps.shape[-1]

        def plan(st):
            new_state, promote, demote = vpolicy(
                spec, st, c["slow_bw"], c["app_bw"], k)
            # lanes whose policy is not due keep their state; their padded
            # outputs are blanked so no migrations execute.
            st = _bwhere(do, new_state, st)
            promote = jnp.where(do[:, None], promote, -1)
            demote = jnp.where(do[:, None], demote, -1)
            return st, promote, demote

        def skip_moves(op):
            st, tier0, p_at0, d_at0 = op
            z = jnp.zeros((B,), jnp.int32)
            zp = jnp.zeros((B, R - 1), jnp.int32)
            return st, tier0, p_at0, d_at0, z, z, z, zp, zp

        if tn:
            # Tier-targeted route: the policy sees the per-tier utilization
            # and emits (pages, dst) moves; migrations + wasteful
            # accounting ride inside the any-lane fire cond (bitwise a
            # no-op on skip intervals — all-(-1) pages execute nothing).
            def fire(op):
                st, tier0, p_at0, d_at0 = op
                with jax.named_scope("policy"):
                    st2, pages, dst = vtier_policy(
                        spec, st, c["tier_util"], c["slow_bw"],
                        c["app_bw"], k, caps)
                    st = _bwhere(do, st2, st)
                    pages = jnp.where(do[:, None], pages, -1)
                with jax.named_scope("migrate"):
                    tier, up_exec, down_exec, mig_up, mig_down = jax.vmap(
                        simjax.apply_targeted_migrations)(tier0, pages, dst,
                                                          caps)
                    waste, p_at, d_at = jax.vmap(
                        simjax.wasteful_update,
                        in_axes=(None, 0, 0, 0, 0, 0, 0))(
                        t - 1, p_at0, d_at0, pages, pages, up_exec,
                        down_exec)
                    return (st, tier, p_at, d_at,
                            up_exec.sum(axis=1).astype(jnp.int32),
                            down_exec.sum(axis=1).astype(jnp.int32), waste,
                            mig_up, mig_down)

            (state, tier, promoted_at, demoted_at, n_promo, n_demo, waste,
             mig_up, mig_down) = jax.lax.cond(
                any_do, fire, skip_moves,
                (state, c["tier"], c["promoted_at"], c["demoted_at"]))
            with jax.named_scope("account"):
                if interval_kernel:
                    acc_fast, acc_slow, wall, slow_share, app_raw, recall = \
                        interval_ops.interval_account(
                            mach, true_b, tier, mig_up.astype(f32),
                            mig_down.astype(f32), orc_b, k)
                else:
                    acc_fast, acc_slow, wall, slow_share, app_raw = \
                        _per_lane(simjax.interval_accounting_impl,
                                  mach, true_b, tier, mig_up.astype(f32),
                                  mig_down.astype(f32))
                    recall = ((tier == 0) & orc_b).sum(axis=1).astype(
                        f32) / k
        elif interval_kernel:
            # Fused route: migrations + wasteful accounting ride INSIDE the
            # any-lane fire cond.  On non-fire intervals the unfused path
            # executes them against all-(-1) plans — a bitwise no-op — so
            # skipping them entirely preserves CRN equivalence while
            # dropping the hop-chain gather/scatter from most intervals.
            def fire(op):
                st, tier0, p_at0, d_at0 = op
                with jax.named_scope("policy"):
                    st, promote, demote = plan(st)
                with jax.named_scope("migrate"):
                    tier, pexec, dexec, mig_up, mig_down = \
                        interval_ops.tier_migrate(tier0, promote, demote,
                                                  caps)
                    waste, p_at, d_at = jax.vmap(
                        simjax.wasteful_update,
                        in_axes=(None, 0, 0, 0, 0, 0, 0))(
                        t - 1, p_at0, d_at0, promote, demote, pexec, dexec)
                    return (st, tier, p_at, d_at,
                            pexec.sum(axis=1).astype(jnp.int32),
                            dexec.sum(axis=1).astype(jnp.int32), waste,
                            mig_up, mig_down)

            (state, tier, promoted_at, demoted_at, n_promo, n_demo, waste,
             mig_up, mig_down) = jax.lax.cond(
                any_do, fire, skip_moves,
                (state, c["tier"], c["promoted_at"], c["demoted_at"]))
            with jax.named_scope("account"):
                acc_fast, acc_slow, wall, slow_share, app_raw, recall = \
                    interval_ops.interval_account(
                        mach, true_b, tier, mig_up.astype(f32),
                        mig_down.astype(f32), orc_b, k)
        else:
            def fire(st):
                with jax.named_scope("policy"):
                    return plan(st)

            def skip(st):
                return (st, jnp.full((B, pad_p), -1, jnp.int32),
                        jnp.full((B, pad_d), -1, jnp.int32))

            # Scalar predicate: the policy pass (top-k / sort ranking
            # dominates its cost) only runs on intervals where at least one
            # lane's cadence is due — unlike an outer vmap-of-cond, which
            # would select-execute it every interval.
            state, promote, demote = jax.lax.cond(any_do, fire, skip, state)

            with jax.named_scope("migrate"):
                tier, pexec, dexec, mig_up, mig_down = jax.vmap(
                    simjax.apply_tier_migrations, in_axes=(0, 0, 0, 0))(
                    c["tier"], promote, demote, caps)
                n_promo = pexec.sum(axis=1).astype(jnp.int32)   # [B]
                n_demo = dexec.sum(axis=1).astype(jnp.int32)
                waste, promoted_at, demoted_at = jax.vmap(
                    simjax.wasteful_update,
                    in_axes=(None, 0, 0, 0, 0, 0, 0))(
                    t - 1, c["promoted_at"], c["demoted_at"], promote,
                    demote, pexec, dexec)
            with jax.named_scope("account"):
                acc_fast, acc_slow, wall, slow_share, app_raw = _per_lane(
                    simjax.interval_accounting_impl,
                    mach, true_b, tier, mig_up.astype(f32),
                    mig_down.astype(f32))
                recall = ((tier == 0) & orc_b).sum(axis=1).astype(f32) / k
        with jax.named_scope("policy"):
            mode = vmode(spec, state)
        with jax.named_scope("account"):
            if cls.mixed_observation:
                # per-lane mechanism overhead (union lanes): non-TPP lanes
                # carry 0.0, and ``wall + acc_slow * 0.0 * 1e-9 / mlp``
                # adds +0.0 to a nonnegative finite wall — a bitwise no-op.
                extra = jax.vmap(cls.slow_extra_lane)(spec)      # [B]
                wall = wall + acc_slow * extra * f32(1e-9) / mach.mlp
            elif cls.slow_access_extra_ns:
                # policy-mechanism overhead charged to the application
                # (TPP's NUMA hint faults are taken on slow-tier accesses).
                wall = wall + acc_slow * f32(cls.slow_access_extra_ns) \
                    * f32(1e-9) / mach.mlp

            new_c = dict(
                state=state, tier=tier,
                promoted_at=promoted_at, demoted_at=demoted_at, t=t,
                key=key, slow_bw=slow_share,
                # consumer-side clamp of the RAW tier-0 utilization: the
                # policy-facing signal stays in [0,1] (bitwise the
                # historical at-source clamp; the raw ratio keeps
                # oversaturation visible to accounting consumers).
                app_bw=jnp.minimum(1.0, app_raw),
                exec_time=c["exec_time"] + wall,
                promotions=c["promotions"] + n_promo,
                demotions=c["demotions"] + n_demo,
                wasteful=c["wasteful"] + waste,
                acc_fast_total=c["acc_fast_total"] + acc_fast,
                acc_total=c["acc_total"] + acc_fast + acc_slow,
                recall_sum=c["recall_sum"] + recall)
            if tn:
                new_c["tier_util"] = _per_lane(
                    simjax.tier_utilization_impl,
                    mach, true_b, tier, mig_up.astype(f32),
                    mig_down.astype(f32))
            if wl is not None:
                new_c["wl_state"] = wst
            hits_val = acc_fast / jnp.maximum(acc_fast + acc_slow, 1e-9)
            if reduce == "stream":
                # per-interval outputs folded into the carry: the scan
                # emits no ys, so nothing [T, ...]-shaped is ever
                # allocated.
                new_c["slow_sum"] = c["slow_sum"] + slow_share
                new_c["hits_sum"] = c["hits_sum"] + hits_val
                new_c["mode_sum"] = c["mode_sum"] + mode
                new_c["promos_max"] = jnp.maximum(c["promos_max"], n_promo)
                ys = {}
            else:
                ys = dict(slow=slow_share, hits=hits_val, mode=mode,
                          promos=n_promo)
        return new_c, ys

    carry = _init_carry(spec, B, n, k, mach, keys)
    if tn:
        carry["tier_util"] = jnp.zeros((B, caps.shape[-1]), f32)
    if reduce == "stream":
        carry["slow_sum"] = jnp.zeros((B,), f32)
        carry["hits_sum"] = jnp.zeros((B,), f32)
        carry["mode_sum"] = jnp.zeros((B,), jnp.int32)
        carry["promos_max"] = jnp.zeros((B,), jnp.int32)
    if wl is None:
        trace = jnp.asarray(trace, f32)
        xs = (trace, jnp.asarray(oracle_mask, bool), sample)
    else:
        carry["wl_state"] = jax.vmap(wl_cls.init, in_axes=(0, None, 0))(
            wl, n, wl_keys)
        xs = sample
    carry, ys = jax.lax.scan(step, carry, xs)
    out = dict(
        exec_time=carry["exec_time"], promotions=carry["promotions"],
        demotions=carry["demotions"], wasteful=carry["wasteful"],
        hot_recall=carry["recall_sum"] / T,
        fast_hit_frac=carry["acc_fast_total"]
        / jnp.maximum(carry["acc_total"], 1e-9))
    if reduce == "stream":
        out.update(
            mean_slow_bw=carry["slow_sum"] / T,
            mean_fast_hits=carry["hits_sum"] / T,
            mean_mode=carry["mode_sum"].astype(f32) / T,
            max_promotions_interval=carry["promos_max"])
    else:
        out.update(
            timeline_slow_bw=ys["slow"], timeline_fast_hits=ys["hits"],
            timeline_mode=ys["mode"], timeline_promotions=ys["promos"])
    return out


#: Donation lists: every donated position is (re)built fresh at each call
#: site — spec / mach / caps lane stacks and PRNG key stacks — so XLA can
#: reuse their buffers for outputs.  trace / oracle / sample are NEVER
#: donated: callers hold and reuse them across dispatches (CRN pairing).
#: Donation is best-effort by shape: [B]-shaped spec leaves alias the [B]
#: result scalars; the machine's small [B, R] rows have no same-shaped
#: output, which XLA reports per dispatch — silence just that notice.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")
@functools.partial(
    jax.jit, static_argnames=("k", "sampling", "need_normal",
                              "interval_kernel", "reduce", "tier_shim"),
    donate_argnums=(0, 4, 5, 6))
def _sim_jit(spec, trace, oracle_mask, k, mach, caps, keys, sample,
             sampling, need_normal, interval_kernel=True, reduce="stack",
             tier_shim=False):
    return _simulate(spec, trace, oracle_mask, k, mach, caps, keys, sample,
                     sampling, need_normal, interval_kernel=interval_kernel,
                     reduce=reduce, tier_shim=tier_shim)


def _precompute_observations(trace, u, periods: tuple, need_normal: bool):
    """[T, P, n] observation grids for a shared CRN field, one per period.

    Row-by-row scan keeps the transform's intermediates small while
    producing the full grids every sweep lane shares.
    """
    def row(_, xs):
        u_t, tr_t = xs
        return None, jnp.stack([
            pebs_sample_from_uniform(u_t, tr_t, jnp.float32(p),
                                     need_normal=need_normal)
            for p in periods])
    return jax.lax.scan(row, None, (u, trace))[1]


@functools.partial(
    jax.jit, static_argnames=("k", "periods", "need_normal",
                              "interval_kernel", "reduce"),
    donate_argnums=(0, 4, 5, 6))
def _sim_pre_jit(spec, trace, oracle_mask, k, mach, caps, keys, u, periods,
                 need_normal, interval_kernel=True, reduce="stack"):
    obs = _precompute_observations(trace, u, periods, need_normal)
    return _simulate(spec, trace, oracle_mask, k, mach, caps, keys, obs,
                     "pre", need_normal, interval_kernel=interval_kernel,
                     reduce=reduce)


@functools.partial(
    jax.jit, static_argnames=("k", "sampling", "need_normal",
                              "wl_rep", "n", "wl_boost", "wl_drift",
                              "interval_kernel", "reduce", "tier_shim"),
    donate_argnums=(0, 3, 4, 5, 7, 8))
def _sim_synth_jit(spec, wl, k, mach, caps, keys, sample, noise_key,
                   wl_keys, sampling, need_normal, wl_rep, n,
                   wl_boost=True, wl_drift=True, interval_kernel=True,
                   reduce="stack", tier_shim=False):
    # NB: ``wl`` (position 1) and ``sample`` (6) are NOT donated —
    # experiment.sweep shares one workload stack / CRN field across every
    # per-family dispatch of a single axis-product call.
    return _simulate(spec, None, None, k, mach, caps, keys, sample,
                     sampling, need_normal, wl=wl, wl_keys=wl_keys,
                     noise_key=noise_key, wl_rep=wl_rep, n=n,
                     wl_boost=wl_boost, wl_drift=wl_drift,
                     interval_kernel=interval_kernel, reduce=reduce,
                     tier_shim=tier_shim)


def _synth_need_normal(wl_specs, min_period: float) -> bool:
    """Static host bound for synth mode: can any page's sampling rate reach
    the normal-approx regime?  Uses the specs' work bound (probs <= 1), so
    it may be conservatively True — the sampler's selected values are
    identical either way (see pebs_sample_from_uniform)."""
    return max(sp.max_rate() for sp in wl_specs) / float(min_period) \
        >= _NORMAL_SWITCH


def _to_result(out, lane: int, name: str) -> SimResult:
    lane_out = jax.tree_util.tree_map(lambda x: x[lane], out)
    res = SimResult(
        name=name,
        exec_time_s=float(lane_out["exec_time"]),
        promotions=int(lane_out["promotions"]),
        demotions=int(lane_out["demotions"]),
        wasteful=int(lane_out["wasteful"]),
        hot_recall=float(lane_out["hot_recall"]),
        fast_hit_frac=float(lane_out["fast_hit_frac"]))
    if "timeline_slow_bw" in lane_out:       # reduce="stack"
        ts = {k: np.asarray(v) for k, v in lane_out.items()
              if k.startswith("timeline_")}
        res.timeline_slow_bw = ts["timeline_slow_bw"].astype(np.float64)
        res.timeline_fast_hits = ts["timeline_fast_hits"].astype(np.float64)
        res.timeline_mode = ts["timeline_mode"].astype(np.int32)
        res.timeline_promotions = ts["timeline_promotions"].astype(np.int32)
    else:                                    # reduce="stream" summaries
        res.mean_slow_bw = float(lane_out["mean_slow_bw"])
        res.mean_fast_hits = float(lane_out["mean_fast_hits"])
        res.mean_mode = float(lane_out["mean_mode"])
        res.max_promotions_interval = int(
            lane_out["max_promotions_interval"])
    return res


def _timelines_lane_major(out):
    """scan stacks timelines as [T, B]; give callers [B, T]."""
    for key in list(out):
        if key.startswith("timeline_"):
            out[key] = jnp.swapaxes(out[key], 0, 1)
    return out


def _record_dispatch(**info):
    if "T" in info and "lanes" in info:
        # lanes x intervals: the dispatch's compute spend in the unit the
        # search engine compares strategies on (SearchResult.lane_intervals).
        # ``lanes`` is always the LOGICAL lane count — mesh padding reports
        # its widened count separately (``padded_lanes``, fabric.py) so
        # search compute curves stay comparable across mesh sizes.
        info["lane_intervals"] = int(info["lanes"]) * int(info["T"])
    last_dispatch.clear()
    last_dispatch.update(info)
    for ctr in _active_counters:
        ctr.count += 1
        ctr.records.append(dict(info))


# ------------------------------------------------------------- public API
def simulate(spec, trace, machine, k: int, seed: int = 0, sample_u=None,
             name: str | None = None,
             use_interval_kernel: bool = True,
             tier_shim: bool = False) -> SimResult:
    """Device-resident replay of ``trace`` under any policy spec.

    ``machine``: registry name / MachineSpec / TieredMachineSpec.
    ``sample_u``: optional [T, n] uniform field selecting the CRN sampling
    path (pass the same field to ``engine.run(..., sample_u=...)`` for an
    exactly-comparable reference run).  Default: PEBS noise drawn with
    ``jax.random`` from a key threaded through the scan carry.
    ``use_interval_kernel=False`` pins the historical unfused interval
    path — the fused route is bitwise-equal, so this only matters for
    equivalence tests and the kernel benchmark.  ``tier_shim=True`` forces
    a binary spec through the tier-targeted executor via the protocol's
    shim — also bitwise-equal (tests/test_tier_native.py).
    """
    trace = np.asarray(trace)
    assert 0 < k <= trace.shape[1]
    oracle = oracle_topk_masks(trace, k)
    crn = sample_u is not None
    sample = (jnp.asarray(sample_u, jnp.float32) if crn
              else jnp.zeros((trace.shape[0], 1), jnp.float32))
    keys = jax.random.PRNGKey(seed)[None]
    mach, caps = _mach_lanes(machine, 1, trace.shape[1], k)
    out = _sim_jit(_lane_specs(spec, 1), jnp.asarray(trace, jnp.float32),
                   jnp.asarray(oracle), k, mach, caps, keys, sample,
                   "crn" if crn else "prng",
                   _need_normal(trace, spec.min_sampling_period()),
                   interval_kernel=use_interval_kernel,
                   tier_shim=tier_shim)
    _record_dispatch(lanes=1, sampling="crn" if crn else "prng",
                     policy=spec.name, machines=1, T=trace.shape[0],
                     interval_kernel=use_interval_kernel, reduce="stack")
    return _to_result(_timelines_lane_major(out), 0, name or spec.name)


def sweep_seeds(trace, machine, k: int, seeds, cfg: ARMSConfig | None = None,
                spec=None) -> list[SimResult]:
    """Batched runs over PRNG seeds: one compile, one device dispatch.

    Every seed's full replay runs in lockstep in the lane axis — the
    sampling-noise study (and any seed-averaged comparison) no longer pays
    one sequential simulation per seed.  Defaults to ARMS (``cfg``); pass
    any ``spec`` for a baseline.
    """
    if spec is None:
        spec = ARMSSpec.make(base_cfg=cfg)
    elif cfg is not None:
        raise ValueError("pass either cfg (ARMS) or spec, not both")
    seeds = list(seeds)
    if not seeds:
        raise ValueError("sweep_seeds needs at least one seed")
    trace = np.asarray(trace)
    oracle = oracle_topk_masks(trace, k)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    mach, caps = _mach_lanes(machine, len(seeds), trace.shape[1], k)
    out = _sim_jit(_lane_specs(spec, len(seeds)),
                   jnp.asarray(trace, jnp.float32), jnp.asarray(oracle), k,
                   mach, caps, keys,
                   jnp.zeros((trace.shape[0], 1), jnp.float32), "prng",
                   _need_normal(trace, spec.min_sampling_period()))
    _record_dispatch(lanes=len(seeds), sampling="prng", policy=spec.name,
                     machines=1, T=trace.shape[0], interval_kernel=True,
                     reduce="stack")
    out = _timelines_lane_major(out)
    return [_to_result(out, i, f"{spec.name}[seed={s}]")
            for i, s in enumerate(seeds)]


def sweep_policy_configs(spec_family, trace, machine, k: int, configs,
                         sim_seed: int = 0, sample_u=None
                         ) -> list[SimResult]:
    """Lane-batched sweep over one policy family's knob grid.

    ``spec_family`` is a callable mapping a config dict to a spec (e.g.
    ``HeMemSpec.make``); ``configs`` a list of config dicts, one lane each.
    All lanes share ONE common-random-number uniform noise field
    (``sample_u`` or ``sampling.uniform_field(T, n, seed=sim_seed)``), so
    config comparisons are paired — never confounded with sampling noise —
    and the whole sweep is one compiled ``scan``+``vmap`` program.  The
    numpy engine replaying any one config with the same field produces
    identical migrations (the tuning-equivalence tests assert this).
    """
    configs = list(configs)
    if not configs:
        raise ValueError("sweep_policy_configs needs at least one config")
    specs = [spec_family(**cfg) for cfg in configs]
    spec = _stack_specs(specs)
    trace = np.asarray(trace)
    T, n = trace.shape
    oracle = oracle_topk_masks(trace, k)
    if sample_u is None:
        sample_u = uniform_field(T, n, seed=sim_seed)
    assert sample_u.shape == (T, n)
    min_period = min(s.min_sampling_period() for s in specs)
    keys = jnp.stack([jax.random.PRNGKey(0)] * len(configs))
    mach, caps = _mach_lanes(machine, len(configs), n, k)
    out = _sim_jit(spec, jnp.asarray(trace, jnp.float32),
                   jnp.asarray(oracle), k, mach, caps, keys,
                   jnp.asarray(sample_u, jnp.float32), "crn",
                   _need_normal(trace, min_period))
    _record_dispatch(lanes=len(configs), sampling="crn",
                     policy=specs[0].name, machines=1, T=T,
                     interval_kernel=True, reduce="stack")
    out = _timelines_lane_major(out)
    labels = [",".join(f"{nm}={v:.6g}" for nm, v in sorted(cfg.items()))
              for cfg in configs]
    return [_to_result(out, i, f"{specs[0].name}[{lbl}]")
            for i, lbl in enumerate(labels)]


def arms_sim(trace, machine, k: int, cfg: ARMSConfig | None = None,
             seed: int = 0, sample_u=None, name: str = "arms") -> SimResult:
    """ARMS replay of ``trace`` — scan-engine counterpart of
    ``engine.run(ARMSPolicy(cfg), ...)``."""
    return simulate(ARMSSpec.make(base_cfg=cfg), trace, machine, k,
                    seed=seed, sample_u=sample_u, name=name)


def sweep_arms_configs(trace, machine, k: int, overrides: dict,
                       base_cfg: ARMSConfig | None = None, seed: int = 0,
                       sample_u=None, reduce: str = "stack"
                       ) -> list[SimResult]:
    """Batched ARMS runs over a grid of float knob settings.

    ``overrides`` maps ARMSConfig float field names to equal-length value
    lists; row b of every list forms config b.  All configs share one CRN
    uniform noise field, which lets the per-mode observation grids
    (``ARMSSpec.PRE_PERIODS``) be computed once and broadcast across
    lanes: config lanes pay zero sampling cost, and the whole sweep is one
    compiled ``scan``+``vmap`` program.  ``reduce="stream"`` drops the
    ``timeline_*`` stacks for O(lanes) output (scalars are identical) —
    the search engine's eliminate-and-redraw loops use it.
    """
    names = tuple(sorted(overrides))
    if not names:
        raise ValueError("overrides must name at least one ARMSConfig knob")
    B = len(overrides[names[0]])
    if B == 0 or any(len(overrides[nm]) != B for nm in names):
        raise ValueError(
            "override value lists must be non-empty and of equal length; "
            f"got {({nm: len(overrides[nm]) for nm in names})}")
    specs = [ARMSSpec.make({nm: overrides[nm][b] for nm in names},
                           base_cfg=base_cfg) for b in range(B)]
    spec = _stack_specs(specs)
    trace = np.asarray(trace)
    T, n = trace.shape
    oracle = oracle_topk_masks(trace, k)
    if sample_u is None:
        sample_u = uniform_field(T, n, seed=seed)
    need_normal = _need_normal(trace, specs[0].min_sampling_period())
    keys = jnp.stack([jax.random.PRNGKey(0)] * B)
    mach, caps = _mach_lanes(machine, B, n, k)
    out = _sim_pre_jit(spec, jnp.asarray(trace, jnp.float32),
                       jnp.asarray(oracle), k, mach, caps, keys,
                       jnp.asarray(sample_u, jnp.float32),
                       ARMSSpec.PRE_PERIODS, need_normal, reduce=reduce)
    _record_dispatch(lanes=B, sampling="pre", policy="arms", machines=1,
                     T=T, interval_kernel=True, reduce=reduce)
    out = _timelines_lane_major(out)
    labels = [",".join(f"{nm}={float(overrides[nm][b]):.4g}" for nm in names)
              for b in range(B)]
    return [_to_result(out, i, f"arms[{lbl}]")
            for i, lbl in enumerate(labels)]


# --------------------------------------------- trace synthesis (workloads)
def simulate_workload(spec, workload, machine, k: int, T: int, n: int,
                      sim_seed: int = 0, wl_seed: int = 0, sample_u=None,
                      name: str | None = None,
                      use_interval_kernel: bool = True) -> SimResult:
    """Device-synthesized replay of a ``WorkloadSpec`` under any policy.

    The scan engine synthesizes ``true = work * probs`` per interval from
    the spec's pure ``step`` and computes the oracle mask on device — no
    [T, n] trace is materialized anywhere (per-lane storage O(n)).  Under
    the same seeds the run is bitwise-identical to replaying
    ``workload.materialize(T, n, wl_seed)`` with the
    ``sampling.synth_noise_field(T, n, sim_seed)`` CRN field (or with
    ``sample_u`` if given).
    """
    assert 0 < k <= n
    crn = sample_u is not None
    if crn:
        sample = jnp.asarray(sample_u, jnp.float32)
        assert sample.shape == (T, n)
    else:
        sample = jnp.zeros((T, 1), jnp.float32)
    wl = _stack_workloads([workload])
    mach, caps = _mach_lanes(machine, 1, n, k)
    out = _sim_synth_jit(
        _lane_specs(spec, 1), wl, k, mach, caps,
        jax.random.PRNGKey(0)[None], sample, jax.random.PRNGKey(sim_seed),
        jax.random.PRNGKey(wl_seed)[None], "crn" if crn else "crn_prng",
        _synth_need_normal([workload], spec.min_sampling_period()), 1, n,
        wl_boost=workload.has_boost(), wl_drift=workload.has_drift(),
        interval_kernel=use_interval_kernel)
    _record_dispatch(lanes=1, sampling="crn" if crn else "crn_prng",
                     policy=spec.name, synth=True, workloads=1, configs=1,
                     machines=1, T=T, interval_kernel=use_interval_kernel,
                     reduce="stack")
    label = name or f"{spec.name}@{workload_spec.label_of(workload)}"
    return _to_result(_timelines_lane_major(out), 0, label)


def sweep_workloads(workloads, machine, k: int, T: int, n: int,
                    cfg: ARMSConfig | None = None, spec=None,
                    sim_seed: int = 0, wl_seed: int = 0,
                    names=None) -> list[SimResult]:
    """One policy across W workload lanes: ONE compiled dispatch.

    ``workloads`` is a list of ``WorkloadSpec``s (combinator outputs
    welcome; component counts are padded to stack).  Every lane
    synthesizes its own trace on device and all lanes share the
    counter-based CRN noise rows, so workload comparisons are paired.
    Defaults to ARMS (``cfg``); pass any policy ``spec`` for a baseline.
    """
    if spec is None:
        spec = ARMSSpec.make(base_cfg=cfg)
    elif cfg is not None:
        raise ValueError("pass either cfg (ARMS) or spec, not both")
    workloads = list(workloads)
    if not workloads:
        raise ValueError("sweep_workloads needs at least one workload")
    W = len(workloads)
    names = list(names) if names is not None else [
        workload_spec.label_of(w, f"wl{i}") for i, w in enumerate(workloads)]
    mach, caps = _mach_lanes(machine, W, n, k)
    out = _sim_synth_jit(
        _lane_specs(spec, W), _stack_workloads(workloads), k, mach, caps,
        jnp.stack([jax.random.PRNGKey(0)] * W),
        jnp.zeros((T, 1), jnp.float32), jax.random.PRNGKey(sim_seed),
        jnp.stack([jax.random.PRNGKey(wl_seed)] * W), "crn_prng",
        _synth_need_normal(workloads, spec.min_sampling_period()), 1, n,
        wl_boost=any(w.has_boost() for w in workloads),
        wl_drift=any(w.has_drift() for w in workloads))
    _record_dispatch(lanes=W, sampling="crn_prng", policy=spec.name,
                     synth=True, workloads=W, configs=1, machines=1, T=T,
                     interval_kernel=True, reduce="stack")
    out = _timelines_lane_major(out)
    return [_to_result(out, i, f"{spec.name}@{nm}")
            for i, nm in enumerate(names)]


def sweep_workload_configs(spec_family, configs, workloads, machine, k: int,
                           T: int, n: int, sim_seed: int = 0,
                           wl_seed: int = 0, sample_u=None, names=None
                           ) -> list[list[SimResult]]:
    """W workloads x B configs as ONE compiled dispatch of W*B lanes.

    Lane ``w * B + b`` scores config ``b`` on workload ``w``; each
    workload's state is synthesized once per interval and feeds its B
    config lanes.  All lanes share the CRN noise rows (device
    counter-based by default; pass ``sample_u`` for an explicit field),
    so config comparisons stay paired within and across workloads.
    Returns results grouped per workload: ``out[w][b]``.
    """
    configs = list(configs)
    workloads = list(workloads)
    if not configs or not workloads:
        raise ValueError("sweep_workload_configs needs >=1 config and "
                         ">=1 workload")
    W, B = len(workloads), len(configs)
    names = list(names) if names is not None else [
        workload_spec.label_of(w, f"wl{i}") for i, w in enumerate(workloads)]
    pol_specs = [spec_family(**cfg) for cfg in configs]
    lane_spec = _stack_specs([pol_specs[b]
                              for _ in range(W) for b in range(B)])
    crn = sample_u is not None
    if crn:
        sample = jnp.asarray(sample_u, jnp.float32)
        assert sample.shape == (T, n)
    else:
        sample = jnp.zeros((T, 1), jnp.float32)
    min_period = min(s.min_sampling_period() for s in pol_specs)
    mach, caps = _mach_lanes(machine, W * B, n, k)
    out = _sim_synth_jit(
        lane_spec, _stack_workloads(workloads), k, mach, caps,
        jnp.stack([jax.random.PRNGKey(0)] * (W * B)), sample,
        jax.random.PRNGKey(sim_seed),
        jnp.stack([jax.random.PRNGKey(wl_seed)] * W),
        "crn" if crn else "crn_prng",
        _synth_need_normal(workloads, min_period), B, n,
        wl_boost=any(w.has_boost() for w in workloads),
        wl_drift=any(w.has_drift() for w in workloads))
    _record_dispatch(lanes=W * B, sampling="crn" if crn else "crn_prng",
                     policy=pol_specs[0].name, synth=True, workloads=W,
                     configs=B, machines=1, T=T, interval_kernel=True,
                     reduce="stack")
    out = _timelines_lane_major(out)
    labels = [",".join(f"{nm}={v:.6g}" for nm, v in sorted(cfg.items()))
              for cfg in configs]
    return [[_to_result(out, w * B + b,
                        f"{pol_specs[b].name}@{names[w]}[{labels[b]}]")
             for b in range(B)] for w in range(W)]
