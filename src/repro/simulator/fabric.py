"""Mesh sweep fabric: shard the lane axis over devices and fuse a
mixed-family policy panel into ONE compiled program.

The scan engine (scan_engine.py) batches sweep lanes in the leading axis
of every carried array, and ``experiment.sweep`` flattens the P×W×M×S
axis product into those lanes — but with two ceilings this module
removes:

* **Lane sharding** (``sim_trace`` / ``sim_synth``): the per-lane
  ``[B, n]`` state bounds sweep width by one device's memory.  The
  fabric pads the flat lane axis to a multiple of the mesh size
  (replicating lane 0 — padded lanes are DROPPED from results before
  labeling), then runs the unchanged ``scan_engine._simulate`` under
  ``shard_map`` over a 1-D ``jax.sharding.Mesh``: spec / machine / caps
  / PRNG-key lanes are sharded with ``PartitionSpec("lanes")``, the
  trace / CRN field / workload stack are replicated, and carries are
  donated.  Results are bitwise-identical to the unsharded path at any
  mesh size (including a forced mesh of 1) because nothing a lane
  computes ever depends on which shard it landed on:

    - per-lane PRNG keys are data, derived HOST-side from the global
      lane id (seed), and the in-scan ``split`` is a per-lane vmap;
    - the any-lane fire / workload-event ``lax.cond`` gates become
      per-SHARD conds, but both branches are bitwise no-ops for lanes
      that don't fire (the engine's load-bearing skip invariant), so a
      shard skipping an interval another shard fires on changes nothing;
    - synth lanes gather their workload row by GLOBAL workload index
      (``widx``) from the replicated [W] synthesis — value-wise exactly
      the unsharded ``repeat``;
    - no lane's numerics depend on how many lanes share its program.
      On a TPU v5e both a batched f32 row sum and ``lax.top_k``'s tie
      order changed with the lane count (PERF.md), so the interval
      accounting sums one lane per loop step (``scan_engine._per_lane``)
      and rankings use ``utils.topk.top_k``.  Until a run on several
      chips confirms it, ``resolve_mesh`` refuses mesh sizes above 1 on
      a TPU backend.

  Streaming aggregation (``reduce="stream"``) already makes outputs
  O(lanes); the fabric's only cross-device traffic is the final
  per-lane result gather.

* **Union dispatch** (``build_union`` / ``UnionSpec``): policies of
  different families have different state pytrees, so the sweep
  historically issued one compiled dispatch per family.  ``UnionSpec``
  is a single PolicySpec whose state is a tuple of neutral-padded SLOT
  arrays — the leaf union over the member families, bucketed by
  (shape, dtype) with per-bucket multiplicity the max over members (so
  union state memory is the max family's, not the sum) — and whose
  per-lane ``fam`` index selects the active member via ``lax.switch``.
  Every lane runs the tier-targeted route; binary members go through
  the protocol's base shim, bitwise-equal to the hop-chain path under
  CRN (tests/test_tier_native.py compares ``dispatch="union"`` with
  ``dispatch="grouped"`` for each binary family).  Mixed observation
  kinds (oracle lanes see true counts; TPP lanes carry a
  per-slow-access overhead) ride per-lane leaves consulted by the
  engine's ``mixed_observation`` hooks.  A full mixed-family
  robustness board therefore compiles to literally ONE program,
  bitwise-equal to the per-family grouped path.

``experiment.sweep(dispatch=..., mesh=...)`` is the public face and the
only caller of ``sim_trace`` / ``sim_synth``, which hand the scan
engine's jits (``scan_engine._sim_jit`` / ``_sim_synth_jit``) their
arguments unchanged on the plain path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.baselines.protocol import SENTINEL, PolicySpec
from repro.simulator import scan_engine
from repro.utils.pytree import pytree_dataclass, static_dataclass

__all__ = ["UnionSpec", "UnionMember", "build_union", "resolve_mesh",
           "sim_trace", "sim_synth"]

#: the 1-D mesh axis every fabric dispatch shards lanes over
LANE_AXIS = "lanes"


# ------------------------------------------------------------ union spec
@static_dataclass
class UnionMember:
    """Static identity of one member family inside a ``UnionSpec``.

    Keyed by the member's spec TREEDEF (class + meta), not just its
    class: two HeMemSpecs with different ``migration_limit`` meta have
    different pad widths / behaviour and get separate branches.
    """

    name: str
    spec_treedef: object      #: treedef of the member spec pytree
    state_treedef: object     #: treedef of the member state pytree
    slot_ids: tuple           #: state leaf i lives in union slot slot_ids[i]
    pad_mv: int               #: the member's own pad_moves(n, k)


@pytree_dataclass(meta=("members", "slot_defs", "pad_mv", "min_period"))
class UnionSpec(PolicySpec):
    """One spec whose lanes may each be a DIFFERENT policy family.

    Data leaves (lane-batched under the engine's vmap):
      * ``fam``        — i32 member index selecting the active branch;
      * ``knobs[f]``   — member f's spec LEAVES (inactive lanes carry the
        member's panel-representative values; their branch output is
        discarded by the switch);
      * ``wants_true`` — bool, this lane observes true counts (oracle);
      * ``slow_extra`` — f32 ns per slow access (TPP; 0.0 elsewhere is a
        bitwise no-op in the engine's wall term).

    State is a tuple of slot arrays (``slot_defs``); member states pack
    into / unpack out of their ``slot_ids``, untouched slots pass
    through.  All behaviour methods are a ``lax.switch`` over members —
    under the engine's lane vmap that is ONE program executing every
    branch and selecting per lane.
    """

    fam: jnp.ndarray
    knobs: tuple
    wants_true: jnp.ndarray
    slow_extra: jnp.ndarray
    members: tuple = ()
    slot_defs: tuple = ()     #: ((shape, dtype-name), ...) per union slot
    pad_mv: int = 1
    min_period: float = PolicySpec.DEFAULT_SAMPLE_PERIOD

    name = "union"
    tier_native = True        # every lane takes the tier-targeted route
    mixed_observation = True  # per-lane wants_true / slow_extra hooks

    # --- member plumbing -------------------------------------------------
    def _member_spec(self, f: int):
        m = self.members[f]
        return jax.tree_util.tree_unflatten(m.spec_treedef,
                                            list(self.knobs[f]))

    def _unpack(self, f: int, slots):
        m = self.members[f]
        return jax.tree_util.tree_unflatten(
            m.state_treedef, [slots[i] for i in m.slot_ids])

    def _pack(self, f: int, slots, state):
        out = list(slots)
        for i, leaf in zip(self.members[f].slot_ids,
                           jax.tree_util.tree_leaves(state)):
            # same-dtype cast: a no-op on values that normalizes weak
            # types so every switch branch returns identical avals.
            out[i] = jnp.asarray(leaf).astype(self.slot_defs[i][1])
        return tuple(out)

    def _switch(self, make_branch, *operands):
        def scoped(f):
            # the member's ops read ``<scope>/<member name>`` in the
            # compiled program's op_name metadata (scan_engine scopes)
            branch = make_branch(f)

            def run(*ops):
                with jax.named_scope(self.members[f].name):
                    return branch(*ops)

            return run

        branches = [scoped(f) for f in range(len(self.members))]
        return jax.lax.switch(self.fam, branches, *operands)

    # --- shape contract --------------------------------------------------
    def pad_promote(self, n: int, k: int) -> int:
        return self.pad_mv

    pad_demote = pad_promote

    def pad_moves(self, n: int, k: int) -> int:
        return self.pad_mv

    def min_sampling_period(self) -> float:
        return float(self.min_period)

    # --- per-lane hooks (scan_engine ``mixed_observation`` route) --------
    def wants_true_lane(self):
        return self.wants_true

    def slow_extra_lane(self):
        return self.slow_extra

    # --- behaviour: lax.switch over members ------------------------------
    def init(self, n_pages, k, machine):
        zeros = tuple(jnp.zeros(shape, dtype)
                      for shape, dtype in self.slot_defs)

        def branch(f):
            return lambda mach: self._pack(
                f, zeros, self._member_spec(f).init(n_pages, k, mach))

        return self._switch(branch, machine)

    def observe(self, state, observed):
        def branch(f):
            return lambda st, obs: self._pack(
                f, st, self._member_spec(f).observe(self._unpack(f, st),
                                                    obs))

        return self._switch(branch, state, observed)

    def fires(self, state):
        def branch(f):
            return lambda st: jnp.asarray(
                self._member_spec(f).fires(self._unpack(f, st)))

        return self._switch(branch, state)

    def sampling_period(self, state):
        def branch(f):
            return lambda st: jnp.asarray(
                self._member_spec(f).sampling_period(self._unpack(f, st)),
                jnp.float32)

        return self._switch(branch, state)

    def mode_of(self, state):
        def branch(f):
            return lambda st: jnp.asarray(
                self._member_spec(f).mode_of(self._unpack(f, st)),
                jnp.int32)

        return self._switch(branch, state)

    def tier_policy(self, state, tier_util, slow_bw, app_bw, k: int, caps):
        def branch(f):
            def run(st, tu, sb, ab, cp):
                sp = self._member_spec(f)
                st2, pages, dst = sp.tier_policy(
                    self._unpack(f, st), tu, sb, ab, k, cp)
                # widen to the union's pad_mv by APPENDING sentinels —
                # trailing skipped entries after the member's own moves,
                # a bitwise no-op in apply_targeted_migrations.
                pad = self.pad_mv - pages.shape[0]
                pages = jnp.concatenate(
                    [pages.astype(jnp.int32),
                     jnp.full((pad,), SENTINEL, jnp.int32)])
                dst = jnp.concatenate(
                    [dst.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
                return self._pack(f, st, st2), pages, dst

            return run

        return self._switch(branch, state, tier_util, slow_bw, app_bw,
                            caps)


def build_union(pol_specs, n: int, k: int, mach_all):
    """Union-ize a mixed-family policy panel.

    ``pol_specs`` are the panel's (unstacked) PolicySpecs; ``mach_all``
    a lane-stacked machine pytree ([M, ...] leaves) whose single-lane
    shape templates the state layouts (all lanes share one padded tier
    depth, machine_spec.lane_stack).  Returns one ``UnionSpec`` per
    policy (stackable: identical meta), ready for
    ``scan_engine._stack_specs`` + ``_take_lanes``.

    Slot layout: member state leaves are bucketed by (shape, dtype);
    the union carries max-over-members slots per bucket, so the union
    state is as big as the LARGEST member's, not the sum.  Layouts are
    computed by ``jax.eval_shape`` of each member's ``init`` — no
    device computation happens here.
    """
    mach1 = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), mach_all)
    # member identity = spec treedef (class + meta): specs that cannot
    # stack leaf-wise get their own branch.
    fam_of, reps, keys = [], [], {}
    for sp in pol_specs:
        key = jax.tree_util.tree_structure(sp)
        if key not in keys:
            keys[key] = len(reps)
            reps.append(sp)
        fam_of.append(keys[key])

    slot_req: dict = {}
    fam_layouts = []
    for rep in reps:
        st = jax.eval_shape(lambda m, sp=rep: sp.init(n, k, m), mach1)
        leaves, state_treedef = jax.tree_util.tree_flatten(st)
        buckets: dict = {}
        fam_slots = []
        for leaf in leaves:
            bk = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
            i = buckets.get(bk, 0)
            buckets[bk] = i + 1
            fam_slots.append((bk, i))
        for bk, cnt in buckets.items():
            slot_req[bk] = max(slot_req.get(bk, 0), cnt)
        fam_layouts.append((state_treedef, fam_slots))

    # deterministic global slot order: sort buckets by (dtype, shape)
    slot_defs, base = [], {}
    for bk in sorted(slot_req, key=lambda b: (b[1], b[0])):
        base[bk] = len(slot_defs)
        slot_defs.extend([bk] * slot_req[bk])
    slot_defs = tuple(slot_defs)

    members = tuple(
        UnionMember(
            name=rep.name,
            spec_treedef=jax.tree_util.tree_structure(rep),
            state_treedef=treedef,
            slot_ids=tuple(base[bk] + i for bk, i in fam_slots),
            pad_mv=int(rep.pad_moves(n, k)))
        for rep, (treedef, fam_slots) in zip(reps, fam_layouts))
    pad_mv = max(m.pad_mv for m in members)
    min_period = min(sp.min_sampling_period() for sp in pol_specs)
    rep_knobs = tuple(
        tuple(jnp.asarray(lf) for lf in jax.tree_util.tree_leaves(rep))
        for rep in reps)

    out = []
    for sp, f in zip(pol_specs, fam_of):
        knobs = tuple(
            tuple(jnp.asarray(lf)
                  for lf in jax.tree_util.tree_leaves(sp))
            if g == f else rep_knobs[g]
            for g in range(len(reps)))
        out.append(UnionSpec(
            fam=jnp.asarray(f, jnp.int32), knobs=knobs,
            wants_true=jnp.asarray(type(sp).wants_true_counts),
            slow_extra=jnp.float32(type(sp).slow_access_extra_ns),
            members=members, slot_defs=slot_defs, pad_mv=int(pad_mv),
            min_period=float(min_period)))
    return out


# --------------------------------------------------------- lane sharding
def resolve_mesh(mesh) -> int | None:
    """``mesh`` param -> shard count D, or None for the plain path.

    ``None`` never shards; ``"auto"`` shards over every local device
    (plain path on a single-device host); an int forces that many
    devices (1 is allowed — the forced-shard_map equivalence tests).

    On a TPU backend D > 1 raises: the lane-count fix is shown bitwise on
    one chip (the shards' programs run one after another), but no run on
    several chips has yet compared mesh D with mesh 1 (PERF.md, open
    questions).  Lift this once ``chip_smoke.py --chips 4`` passes.
    """
    if mesh is None:
        return None
    if mesh == "auto":
        d = jax.device_count()
        d = d if d > 1 else None
    else:
        d = int(mesh)
        if not 1 <= d <= jax.device_count():
            raise ValueError(f"mesh={d} but only {jax.device_count()} "
                             "device(s) are available")
    if d is not None and d > 1 and jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"mesh={mesh}: a lane-sharded sweep over {d} TPU chips is not "
            "yet shown bitwise equal to mesh=1 on real chips (PERF.md, "
            "open questions); use mesh=None or mesh=1")
    return d


def _lane_mesh(D: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:D]), (LANE_AXIS,))


def _pad_lanes(tree, B: int, Lp: int):
    """Widen lane-batched leaves [B, ...] -> [Lp, ...] replicating lane 0
    (cheap, and keeps every padded lane a valid simulation)."""
    idx = jnp.concatenate([jnp.arange(B, dtype=jnp.int32),
                           jnp.zeros((Lp - B,), jnp.int32)])
    return scan_engine._take_lanes(tree, idx)


def _unpad_out(out: dict, B: int) -> dict:
    """Drop padded lanes from a raw engine output dict ([B]-leading
    scalars; ``timeline_*`` are [T, B], made lane-major on the host by
    ``scan_engine._to_results``)."""
    return {key: (v[:, :B] if key.startswith("timeline_") else v[:B])
            for key, v in out.items()}


def _out_specs(reduce: str) -> dict:
    names = ["exec_time", "promotions", "demotions", "wasteful",
             "hot_recall", "fast_hit_frac"]
    if reduce == "stream":
        return {nm: P(LANE_AXIS) for nm in names + [
            "mean_slow_bw", "mean_fast_hits", "mean_mode",
            "max_promotions_interval"]}
    specs = {nm: P(LANE_AXIS) for nm in names}
    specs.update({nm: P(None, LANE_AXIS) for nm in (
        "timeline_slow_bw", "timeline_fast_hits", "timeline_mode",
        "timeline_promotions")})
    return specs


@functools.partial(
    jax.jit, static_argnames=("k", "sampling", "need_normal",
                              "interval_kernel", "reduce", "mesh"),
    donate_argnums=(0, 4, 5, 6))
def _fab_trace_jit(spec, trace, oracle_mask, k, mach, caps, keys, sample,
                   sampling, need_normal, interval_kernel, reduce, mesh):
    lane, rep = P(LANE_AXIS), P()
    f = jax.shard_map(
        lambda sp, tr, om, mc, cp, ky, sm: scan_engine._simulate(
            sp, tr, om, k, mc, cp, ky, sm, sampling, need_normal,
            interval_kernel=interval_kernel, reduce=reduce),
        mesh=mesh,
        in_specs=(lane, rep, rep, lane, lane, lane, rep),
        out_specs=_out_specs(reduce), check_vma=False)
    return f(spec, trace, oracle_mask, mach, caps, keys, sample)


@functools.partial(
    jax.jit, static_argnames=("k", "sampling", "need_normal", "n",
                              "wl_boost", "wl_drift", "interval_kernel",
                              "reduce", "mesh"),
    donate_argnums=(0, 3, 4, 5, 9))
def _fab_synth_jit(spec, wl, k, mach, caps, keys, sample, noise_key,
                   wl_keys, widx, sampling, need_normal, n, wl_boost,
                   wl_drift, interval_kernel, reduce, mesh):
    # NB mirrors _sim_synth_jit's donation: wl / sample are shared across
    # dispatches (CRN pairing) and never donated; widx (9) is rebuilt per
    # call and is.
    lane, rep = P(LANE_AXIS), P()
    f = jax.shard_map(
        lambda sp, w, mc, cp, ky, sm, nk, wk, wi: scan_engine._simulate(
            sp, None, None, k, mc, cp, ky, sm, sampling, need_normal,
            wl=w, wl_keys=wk, noise_key=nk, n=n, wl_boost=wl_boost,
            wl_drift=wl_drift, interval_kernel=interval_kernel, reduce=reduce,
            widx=wi),
        mesh=mesh,
        in_specs=(lane, rep, lane, lane, lane, rep, rep, rep, lane),
        out_specs=_out_specs(reduce), check_vma=False)
    return f(spec, wl, mach, caps, keys, sample, noise_key, wl_keys, widx)


def _plan_padding(B: int, D: int, pad_multiple) -> int:
    mult = D * int(pad_multiple or 1)
    return ((B + mult - 1) // mult) * mult


def sim_trace(spec, trace, oracle_mask, k, mach, caps, keys, sample,
              sampling, need_normal, interval_kernel=True, reduce="stack",
              mesh=None, pad_multiple=None):
    """Trace-mode dispatch, optionally sharded.  Returns ``(out, info)``:
    the raw engine output dict with padded lanes already dropped, and
    the fabric's dispatch info ({} on the plain path)."""
    D = resolve_mesh(mesh)
    if D is None and not pad_multiple:
        out = scan_engine._sim_jit(
            spec, trace, oracle_mask, k, mach, caps, keys, sample,
            sampling, need_normal, interval_kernel=interval_kernel,
            reduce=reduce)
        return out, {}
    D = D or 1
    B = keys.shape[0]
    Lp = _plan_padding(B, D, pad_multiple)
    spec, mach, caps, keys = (
        _pad_lanes(x, B, Lp) for x in (spec, mach, caps, keys))
    out = _fab_trace_jit(spec, trace, oracle_mask, k, mach, caps, keys,
                         sample, sampling, need_normal, interval_kernel,
                         reduce, _lane_mesh(D))
    return _unpad_out(out, B), dict(mesh=D, padded_lanes=Lp)


def sim_synth(spec, wl, k, mach, caps, keys, sample, noise_key, wl_keys,
              sampling, need_normal, wl_rep, n, wl_boost=True,
              wl_drift=True, interval_kernel=True, reduce="stack",
              mesh=None, pad_multiple=None):
    """Synth-mode dispatch, optionally sharded (see ``sim_trace``).

    ``wl_rep`` maps lane -> workload exactly as in ``_sim_synth_jit``
    (each workload feeds ``wl_rep`` consecutive lanes); the sharded path
    turns it into an explicit global ``widx`` gather so shard-local
    lanes read the right replicated synthesis row.
    """
    D = resolve_mesh(mesh)
    if D is None and not pad_multiple:
        out = scan_engine._sim_synth_jit(
            spec, wl, k, mach, caps, keys, sample, noise_key, wl_keys,
            sampling, need_normal, wl_rep, n, wl_boost=wl_boost,
            wl_drift=wl_drift, interval_kernel=interval_kernel, reduce=reduce)
        return out, {}
    D = D or 1
    B = keys.shape[0]
    Lp = _plan_padding(B, D, pad_multiple)
    widx = jnp.concatenate([
        jnp.arange(B, dtype=jnp.int32) // jnp.int32(wl_rep),
        jnp.zeros((Lp - B,), jnp.int32)])
    spec, mach, caps, keys = (
        _pad_lanes(x, B, Lp) for x in (spec, mach, caps, keys))
    out = _fab_synth_jit(spec, wl, k, mach, caps, keys, sample, noise_key,
                         wl_keys, widx, sampling, need_normal, n, wl_boost,
                         wl_drift, interval_kernel, reduce, _lane_mesh(D))
    return _unpad_out(out, B), dict(mesh=D, padded_lanes=Lp)
