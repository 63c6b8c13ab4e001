"""Axis-product experiment API: policies × workloads × machines × seeds.

The spec trilogy — policies (baselines/protocol.py), workloads
(simulator/workload_spec.py) and machines (simulator/machine_spec.py) —
makes every experiment axis a batchable pytree, so the full paper
question ("which policy is robust across workloads AND machines without
tuning?") flattens into lanes of ONE compiled scan-engine dispatch:

    res = experiment.sweep(
        policies=["arms", HeMemSpec.make(hot_threshold=4)],
        workloads=["gups", "silo-tpcc"],       # synth mode (needs T, n)
        machines=["pmem-large", "dram-cxl-pmem"],
        seeds=[0], k=256, T=300, n=2048)
    res.at(policy="arms", workload="gups", machine="dram-cxl-pmem")

Lane layout per dispatch: ``((w*P + p)*M + m)*S + s`` — workloads
outermost (each workload's device-synthesized state feeds its P*M*S
policy/machine/seed lanes), machines of different tier depth unified by
neutral padding (machine_spec.pad_tiers), seeds innermost.  Policies of
*different families* (different state pytrees) cannot share a lane axis;
they are grouped by family, one dispatch per family, each still covering
the full W×M×S product — a single-family sweep (e.g. a tuning grid
across machines) is exactly one dispatch, which the CI machine-sweep
gate asserts.

Noise pairing: with a single seed, lanes share common random numbers
(trace mode: one uniform field from ``sim_seed``; synth mode: the
counter-based ``crn_prng`` rows) so policy/workload/machine comparisons
are paired.  With multiple seeds the sampling switches to per-lane
``prng`` keys — each seed lane draws its own noise.

``tuning.tune``, ``benchmarks/paper_tables.py`` and
``examples/simulate_tiering.py`` route their sweeps through here.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.baselines.arms_policy import ARMSSpec
from repro.baselines.hemem import HeMemSpec
from repro.baselines.hybridtier import HybridTierSpec
from repro.baselines.jenga import JengaSpec
from repro.baselines.memtis import MemtisSpec
from repro.baselines.static import AllSlowSpec, OracleSpec
from repro.baselines.tierbpf import TierBPFSpec
from repro.baselines.tpp import TPPSpec
from repro.simulator import fabric, machine_spec, scan_engine, workload_spec
from repro.simulator import machines as machines_mod
from repro.simulator.engine import SimResult, oracle_topk_masks
from repro.simulator.sampling import uniform_field

__all__ = ["sweep", "SweepResult", "policy_spec", "POLICY_REGISTRY"]

POLICY_REGISTRY = {
    "arms": lambda: ARMSSpec.make(),
    "hemem": lambda: HeMemSpec.make(),
    "memtis": lambda: MemtisSpec.make(),
    "tpp": lambda: TPPSpec.make(),
    "all-slow": AllSlowSpec,
    "oracle": OracleSpec,
    # tier-native families (see baselines/protocol.py, tier-native contract)
    "hybridtier": lambda: HybridTierSpec.make(),
    "jenga": lambda: JengaSpec.make(),
    "tierbpf": lambda: TierBPFSpec.make(),
}

AXES = ("policy", "workload", "machine", "seed")


def policy_spec(p):
    """Resolve a policy name to its default-knob spec; specs pass through."""
    if isinstance(p, str):
        if p not in POLICY_REGISTRY:
            raise ValueError(f"unknown policy {p!r}; "
                             f"known: {sorted(POLICY_REGISTRY)}")
        return POLICY_REGISTRY[p]()
    return p


@dataclasses.dataclass
class SweepResult:
    """Structured P×W×M×S result grid.

    ``axes`` maps axis name -> labels (in order policy, workload, machine,
    seed); ``grid`` is the flat SimResult list in C order over those axes.
    """

    axes: dict
    grid: list

    @property
    def shape(self) -> tuple:
        return tuple(len(self.axes[a]) for a in AXES)

    def _index(self, axis: str, key) -> int:
        if isinstance(key, str):
            labels = [lb.lower() for lb in self.axes[axis]]
            try:
                return labels.index(key.lower())
            except ValueError:
                raise KeyError(
                    f"{key!r} not on {axis} axis {self.axes[axis]}")
        key = int(key)
        # flat C-order indexing would silently alias a negative or
        # out-of-range index into a neighbouring axis block.
        if not 0 <= key < len(self.axes[axis]):
            raise IndexError(f"{axis} index {key} out of range "
                             f"[0, {len(self.axes[axis])})")
        return key

    def at(self, policy=0, workload=0, machine=0, seed=0) -> SimResult:
        """One cell, addressed by axis label or integer index."""
        p, w, m, s = (self._index(a, v) for a, v in
                      zip(AXES, (policy, workload, machine, seed)))
        P, W, M, S = self.shape
        return self.grid[((p * W + w) * M + m) * S + s]

    def items(self):
        """Yield (coords dict, SimResult) over the full grid."""
        P, W, M, S = self.shape
        for i, res in enumerate(self.grid):
            s = i % S
            m = (i // S) % M
            w = (i // (S * M)) % W
            p = i // (S * M * W)
            yield {a: self.axes[a][j]
                   for a, j in zip(AXES, (p, w, m, s))}, res


def _dedup_labels(labels):
    """Disambiguate duplicate axis labels (``name#i``) — shared with the
    search engine, whose grouped modes key results by these labels."""
    import collections
    counts = collections.Counter(labels)
    return [f"{nm}#{i}" if counts[nm] > 1 else nm
            for i, nm in enumerate(labels)]


#: lane_stack / TieredMachineSpec placeholder names that carry no identity;
#: hand-built specs keep their given ``name``, these fall back to ``m{i}``.
_ANON_MACHINE_NAMES = ("", "machine", "lanes")


def _machine_labels(machines_in, mach_specs):
    """Axis labels for the machine axis: the preset STRING the caller
    passed, else the spec's own name, else a positional ``m{i}``."""
    labels = []
    for i, (m_in, sp) in enumerate(zip(machines_in, mach_specs)):
        if isinstance(m_in, str):
            labels.append(m_in)
            continue
        nm = getattr(sp, "name", "") or ""
        labels.append(f"m{i}" if nm in _ANON_MACHINE_NAMES else nm)
    return labels


def _resolve_workloads(workloads, T):
    specs, names = [], []
    for i, w in enumerate(workloads):
        if isinstance(w, str):
            specs.append(workload_spec.named(w, T=T))
            names.append(w)
        else:
            specs.append(w)
            names.append(workload_spec.label_of(w, f"wl{i}"))
    return specs, names


@functools.partial(jax.profiler.annotate_function, name="experiment.sweep")
def sweep(policies, *, workloads=None, trace=None, machines="pmem-large",
          seeds=(0,), k: int, T: int | None = None, n: int | None = None,
          sim_seed: int = 0, wl_seed: int = 0, sample_u=None,
          timelines: bool = False, use_interval_kernel: bool = True,
          dispatch: str = "auto", mesh=None,
          _pad_multiple=None) -> SweepResult:
    """Axis-product sweep; ONE lane-batched dispatch for the whole panel.

    ``policies``: policy names and/or PolicySpec instances (a tuning grid
    is a list of same-family specs).  ``workloads``: workload names /
    WorkloadSpecs (device-synthesis mode; requires ``T``/``n``) — or pass
    a materialized ``trace`` instead (trace-replay mode, workload axis
    collapses to the single trace).  ``machines``: registry names /
    MachineSpecs / TieredMachineSpecs; tier depths may differ (neutral
    padding unifies them in one dispatch).  ``seeds``: one entry keeps
    all lanes CRN-paired (noise from ``sim_seed``); several entries give
    each seed lane its own PRNG noise stream.

    Per-interval outputs STREAM by default: timelines fold into running
    sums/extrema inside the scan carry (``SimResult.mean_*`` /
    ``max_promotions_interval``), so a wide sweep's output memory is
    O(lanes), independent of T.  Pass ``timelines=True`` to opt back into
    stacked [T] ``timeline_*`` series.  Scalar results are identical
    either way.  ``use_interval_kernel=False`` pins the historical
    unfused interval path (equivalence tests / kernel benchmark only).

    ``dispatch`` selects how mixed-family panels compile: ``"auto"``
    (default) fuses >1 distinct family into ONE program via the union
    fabric (simulator/fabric.py) and leaves single-family panels on the
    plain stacked path; ``"union"`` / ``"grouped"`` force either side
    (grouped = historical one-dispatch-per-family, the union path's
    bitwise reference).  ``mesh`` shards the lane axis over devices:
    ``None`` (no sharding), ``"auto"`` (all local devices), or an int
    device count — results are bitwise-identical at any mesh size
    (on a TPU backend, sizes above 1 are refused until shown on several
    chips: ``fabric.resolve_mesh``);
    padded lanes are dropped before labeling.  ``_pad_multiple`` is
    test-only: it forces lane padding even on a 1-device mesh so the
    padding/labeling honesty is regression-testable anywhere.

    While a profiler trace records, the call marks its phases as host
    spans on the trace's clock: ``experiment.sweep`` around it all,
    ``experiment.build`` (once for the shared specs, then per group for
    its lane layout and keys), and per group ``experiment.dispatch``
    (the enqueue), ``experiment.wait`` (until the results exist) and
    ``experiment.readback`` (``_to_result`` over the group's lanes).
    """
    reduce = "stack" if timelines else "stream"
    with TraceAnnotation("experiment.build"):
        policies = [policies] if not isinstance(policies, (list, tuple)) \
            else list(policies)
        pol_specs = [policy_spec(p) for p in policies]
        machines_in = [machines] if not isinstance(machines, (list, tuple)) \
            else list(machines)
        mach_specs = [machines_mod.get(m) for m in machines_in]
        mach_labels = _machine_labels(machines_in, mach_specs)
        seeds = list(seeds)
        P, M, S = len(pol_specs), len(mach_specs), len(seeds)
        if not (P and M and S):
            raise ValueError("every axis needs at least one entry")

        synth = workloads is not None
        if synth:
            if trace is not None:
                raise ValueError("pass either trace or workloads, not both")
            if T is None or n is None:
                raise ValueError("workload-synthesis mode needs T and n")
            if not list(workloads):
                raise ValueError("every axis needs at least one entry")
            wl_specs, wl_names = _resolve_workloads(list(workloads), T)
            W = len(wl_specs)
            wl = scan_engine._stack_workloads(wl_specs)
            wl_boost = any(w.has_boost() for w in wl_specs)
            wl_drift = any(w.has_drift() for w in wl_specs)
        else:
            if trace is None:
                raise ValueError("need a trace or a workloads list")
            trace = np.asarray(trace)
            T, n = trace.shape
            W, wl_names = 1, ["trace"]
            oracle = oracle_topk_masks(trace, k)
        assert 0 < k <= n

        if sample_u is not None:
            if S > 1:
                # "crn" never consumes the per-lane keys: the seed lanes would
                # be silent bitwise copies of each other.
                raise ValueError("sample_u fixes the noise for every lane; "
                                 "it cannot be combined with a seeds axis")
            sampling = "crn"
            sample = jnp.asarray(sample_u, jnp.float32)
            assert sample.shape == (T, n)
        elif S == 1:
            # paired comparisons: every lane shares one CRN noise source.
            sampling = "crn" if not synth else "crn_prng"
            sample = (jnp.asarray(uniform_field(T, n, seed=sim_seed))
                      if not synth else jnp.zeros((T, 1), jnp.float32))
        else:
            sampling = "prng"
            sample = jnp.zeros((T, 1), jnp.float32)

        # group same-family policies: different state pytrees cannot stack —
        # unless the union fabric fuses the mixed panel into ONE group (and
        # therefore ONE compiled program).
        if dispatch not in ("auto", "union", "grouped"):
            raise ValueError(f"dispatch={dispatch!r}; "
                             "expected auto | union | grouped")
        mach_all, caps_all = machine_spec.lane_stack(mach_specs, n, k)
        n_families = len({jax.tree_util.tree_structure(sp)
                          for sp in pol_specs})
        use_union = dispatch == "union" or (dispatch == "auto"
                                            and n_families > 1)
        if use_union:
            lane_specs = fabric.build_union(pol_specs, n, k, mach_all)
            groups = {fabric.UnionSpec: list(range(P))}
        else:
            lane_specs = pol_specs
            # key on the TREEDEF (class + meta), not the class: same-family
            # specs with different meta (e.g. migration_limit) have different
            # pad widths and cannot stack leaf-wise.
            groups = {}
            for i, sp in enumerate(pol_specs):
                groups.setdefault(jax.tree_util.tree_structure(sp),
                                  []).append(i)

    grid = [None] * (P * W * M * S)
    for cls, idxs in groups.items():
        Pg = len(idxs)
        L = W * Pg * M * S
        lane = np.arange(L)
        p_local = (lane // (M * S)) % Pg
        m_of = (lane // S) % M
        s_of = lane % S
        with TraceAnnotation("experiment.build"):
            spec_l = scan_engine._take_lanes(
                scan_engine._stack_specs([lane_specs[i] for i in idxs]),
                jnp.asarray(p_local, jnp.int32))
            mach_l = scan_engine._take_lanes(mach_all,
                                             jnp.asarray(m_of, jnp.int32))
            caps_l = jnp.take(caps_all, jnp.asarray(m_of, jnp.int32), axis=0)
            keys = jnp.stack([jax.random.PRNGKey(int(seeds[s]))
                              for s in s_of])
        min_period = min(lane_specs[i].min_sampling_period() for i in idxs)
        with TraceAnnotation("experiment.dispatch"):
            if synth:
                out, finfo = fabric.sim_synth(
                    spec_l, wl, k, mach_l, caps_l, keys, sample,
                    jax.random.PRNGKey(sim_seed),
                    jnp.stack([jax.random.PRNGKey(wl_seed)] * W),
                    sampling,
                    scan_engine._synth_need_normal(wl_specs, min_period),
                    Pg * M * S, n, wl_boost=wl_boost, wl_drift=wl_drift,
                    interval_kernel=use_interval_kernel, reduce=reduce,
                    mesh=mesh, pad_multiple=_pad_multiple)
            else:
                out, finfo = fabric.sim_trace(
                    spec_l, jnp.asarray(trace, jnp.float32),
                    jnp.asarray(oracle), k, mach_l, caps_l, keys, sample,
                    sampling, scan_engine._need_normal(trace, min_period),
                    interval_kernel=use_interval_kernel, reduce=reduce,
                    mesh=mesh, pad_multiple=_pad_multiple)
            out = scan_engine._timelines_lane_major(out)
        scan_engine._record_dispatch(
            lanes=L, sampling=sampling, policy=lane_specs[idxs[0]].name,
            synth=synth, workloads=W, configs=Pg, machines=M, seeds=S, T=T,
            axis_product=True, interval_kernel=use_interval_kernel,
            reduce=reduce, dispatch="union" if use_union else "grouped",
            families=n_families if use_union else 1, **finfo)
        with TraceAnnotation("experiment.wait"):
            jax.block_until_ready(out)
        with TraceAnnotation("experiment.readback"):
            for l in range(L):
                w = l // (Pg * M * S)
                p = idxs[p_local[l]]
                m, s = m_of[l], s_of[l]
                name = f"{pol_specs[p].name}@{wl_names[w]}[{mach_labels[m]}]"
                if S > 1:
                    name += f"[seed={seeds[s]}]"
                grid[((p * W + w) * M + m) * S + s] = scan_engine._to_result(
                    out, l, name)

    axes = dict(policy=_dedup_labels([sp.name for sp in pol_specs]),
                workload=_dedup_labels(wl_names),
                machine=_dedup_labels(mach_labels),
                seed=[str(s) for s in seeds])
    return SweepResult(axes=axes, grid=grid)
