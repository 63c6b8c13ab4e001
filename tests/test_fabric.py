"""Mesh sweep fabric (simulator/fabric.py): union dispatch + lane
sharding, single-device half of the equivalence suite.

Three guarantees anchor the fabric:

  * UNION EQUIVALENCE — a mixed-family panel fused into ONE compiled
    dispatch by the union PolicyState is BITWISE equal (every scalar,
    summary and timeline) to the historical grouped per-family path
    under CRN, on 2- and 3-tier machines, fused and unfused, synth and
    trace modes;
  * SHARDING EQUIVALENCE — running the same panel under ``shard_map``
    (forced mesh of 1 here; mesh > 1 in test_fabric_mesh.py's
    forced-device-count subprocess) is bitwise equal to the plain path,
    with padded lanes dropped before labeling even when the lane count
    is not a multiple of the padding unit;
  * DISPATCH ACCOUNTING — ``scan_engine.count_dispatches`` counters
    nest/overlap without racing, and the whole mixed board records
    exactly one dispatch.
"""
import dataclasses

import numpy as np
import pytest

from repro.baselines.hemem import HeMemSpec
from repro.simulator import (experiment, fabric, machine_spec, machines,
                             scan_engine, search, workloads)
from repro.simulator.engine import SimResult
from repro.simulator.sampling import uniform_field

T, N, K = 48, 192, 24

#: every registry family rides the board (arms/hemem/memtis/tpp binary
#: through the shim, all-slow/oracle static, three tier-native).
ALL_FAMILIES = list(experiment.POLICY_REGISTRY)
MACHS = ["pmem-large", "dram-cxl-pmem"]       # 2-tier and 3-tier

_FIELDS = [f.name for f in dataclasses.fields(SimResult)
           if f.name != "name"]


def _assert_bitwise(ra, rb, tag=""):
    for (coords, a), (_, b) in zip(ra.items(), rb.items()):
        for f in _FIELDS:
            va, vb = getattr(a, f), getattr(b, f)
            if va is None and vb is None:
                continue
            assert np.array_equal(np.asarray(va), np.asarray(vb)), \
                f"{tag} {coords} {f}: {va} != {vb}"
    assert ra.axes == rb.axes


# ------------------------------------------------------- union dispatch
class TestUnionDispatch:
    @pytest.mark.parametrize("interval_kernel", [True, False])
    def test_union_bitwise_equals_grouped_synth(self, interval_kernel):
        """All nine families x 2-/3-tier x workloads, timelines on, fused
        and unfused: the ONE-program union path is bitwise the grouped
        per-family path."""
        kw = dict(workloads=["gups", "btree"], machines=MACHS, k=K, T=T,
                  n=N, timelines=True, use_interval_kernel=interval_kernel)
        with scan_engine.count_dispatches() as cu:
            ru = experiment.sweep(ALL_FAMILIES, dispatch="union", **kw)
        with scan_engine.count_dispatches() as cg:
            rg = experiment.sweep(ALL_FAMILIES, dispatch="grouped", **kw)
        assert cu.count == 1 and cu.last["dispatch"] == "union"
        assert cg.count == len(ALL_FAMILIES)
        _assert_bitwise(ru, rg, f"synth ik={interval_kernel}")

    def test_union_bitwise_equals_grouped_trace(self):
        trace = workloads.make("silo-tpcc", T=T, n=N)
        u = uniform_field(T, N, seed=7)
        kw = dict(trace=trace, machines=MACHS, k=K, sample_u=u,
                  timelines=True)
        ru = experiment.sweep(ALL_FAMILIES, dispatch="union", **kw)
        rg = experiment.sweep(ALL_FAMILIES, dispatch="grouped", **kw)
        _assert_bitwise(ru, rg, "trace")

    def test_auto_unions_mixed_and_groups_single_family(self):
        kw = dict(workloads=["gups"], machines=["pmem-large"], k=K, T=T,
                  n=N)
        with scan_engine.count_dispatches() as ctr:
            experiment.sweep(["hemem", "arms"], **kw)
        assert ctr.last["dispatch"] == "union"
        with scan_engine.count_dispatches() as ctr:
            experiment.sweep([HeMemSpec.make(), HeMemSpec.make(
                hot_threshold=2)], **kw)
        # one family (same treedef): plain stacked path, no union overhead
        assert ctr.count == 1 and ctr.last["dispatch"] == "grouped"

    def test_union_state_is_max_not_sum(self):
        """The slot union buckets by (shape, dtype) with per-bucket max
        multiplicity: far fewer slots than the sum of member leaves."""
        specs = [experiment.policy_spec(p) for p in ALL_FAMILIES]
        mach_all, _ = machine_spec.lane_stack(
            [machines.get(m) for m in MACHS], N, K)
        uspecs = fabric.build_union(specs, N, K, mach_all)
        members = uspecs[0].members
        assert len(members) == len(ALL_FAMILIES)
        total_leaves = sum(len(m.slot_ids) for m in members)
        assert len(uspecs[0].slot_defs) < total_leaves
        # every member's slots fit the union layout, and no member maps
        # two of its leaves onto the same slot
        for m in members:
            assert all(0 <= i < len(uspecs[0].slot_defs)
                       for i in m.slot_ids)
            assert len(set(m.slot_ids)) == len(m.slot_ids)

    def test_same_family_different_meta_get_separate_branches(self):
        """Member identity keys on the spec TREEDEF: two HeMems with
        different migration_limit meta cannot share a switch branch."""
        a, b = HeMemSpec.make(), HeMemSpec.make(migration_limit=4)
        kw = dict(workloads=["gups"], machines=["pmem-large"], k=K, T=T,
                  n=N)
        ru = experiment.sweep([a, b, "jenga"], dispatch="union", **kw)
        rg = experiment.sweep([a, b, "jenga"], dispatch="grouped", **kw)
        _assert_bitwise(ru, rg, "meta-variant")

    def test_bad_dispatch_value_raises(self):
        with pytest.raises(ValueError, match="dispatch"):
            experiment.sweep(["hemem"], workloads=["gups"], k=K, T=T, n=N,
                             dispatch="fused")


# ---------------------------------------------- sharding (single device)
class TestShardingSingleDevice:
    @pytest.mark.parametrize("interval_kernel", [True, False])
    def test_mesh1_bitwise_equals_plain(self, interval_kernel):
        kw = dict(workloads=["gups", "btree"], machines=MACHS, k=K, T=T,
                  n=N, timelines=True, use_interval_kernel=interval_kernel)
        pols = ["arms", "hemem", "tpp", "oracle", "jenga"]
        base = experiment.sweep(pols, **kw)
        m1 = experiment.sweep(pols, mesh=1, **kw)
        _assert_bitwise(base, m1, f"mesh1 ik={interval_kernel}")

    def test_mesh1_trace_mode(self):
        trace = workloads.make("gups", T=T, n=N)
        kw = dict(trace=trace, machines=MACHS, k=K)
        pols = ["hemem", "tierbpf", "memtis"]
        _assert_bitwise(experiment.sweep(pols, **kw),
                        experiment.sweep(pols, mesh=1, **kw), "trace-mesh1")

    def test_padded_lanes_dropped_before_labeling(self):
        """Satellite regression: a lane count that is NOT a multiple of
        the padding unit keeps the same result shape, labels and values
        as the unpadded run — padded lanes never leak into the grid."""
        pols = ["arms", "hemem", "tpp"]                 # 3*2*2 = 12 lanes
        kw = dict(workloads=["gups", "btree"], machines=MACHS, k=K, T=T,
                  n=N)
        base = experiment.sweep(pols, **kw)
        for mult in (5, 8):                             # 12 % mult != 0
            padded = experiment.sweep(pols, mesh=1, _pad_multiple=mult,
                                      **kw)
            assert padded.shape == base.shape
            assert padded.axes == base.axes
            assert len(padded.grid) == len(base.grid)
            _assert_bitwise(base, padded, f"pad_multiple={mult}")

    def test_dispatch_record_reports_logical_and_padded_lanes(self):
        with scan_engine.count_dispatches() as ctr:
            experiment.sweep(["arms", "hemem"], workloads=["gups"],
                             machines=MACHS, k=K, T=T, n=N, mesh=1,
                             _pad_multiple=3)
        assert ctr.last["lanes"] == 4                   # logical
        assert ctr.last["padded_lanes"] == 6            # ceil(4/3)*3
        assert ctr.last["mesh"] == 1

    def test_search_mesh_is_bitwise_and_logical_lane_intervals(self):
        """Satellite: SearchResult.lane_intervals counts LOGICAL lanes, so
        ASHA/CE compute curves are identical at any mesh size."""
        trace = workloads.make("gups", T=T, n=N)
        plain = search.run("hemem", "asha", trace=trace, k=K, budget=6)
        meshy = search.run("hemem", "asha", trace=trace, k=K, budget=6,
                           mesh=1)
        assert plain.best_config == meshy.best_config
        assert plain.lane_intervals == meshy.lane_intervals
        assert [r.lane_intervals for r in plain.rounds] == \
            [r.lane_intervals for r in meshy.rounds]
        assert float(plain.best_result.exec_time_s) == \
            float(meshy.best_result.exec_time_s)

    def test_mesh_too_big_raises(self):
        import jax
        with pytest.raises(ValueError, match="device"):
            fabric.resolve_mesh(jax.device_count() + 1)

    @pytest.mark.parametrize("mesh", [4, "auto"])
    def test_multi_chip_mesh_refused_on_tpu(self, monkeypatch, mesh):
        """Sharding over several TPU chips is refused, loudly, until a
        four-chip run shows it bitwise; one device and the plain path
        stay available."""
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda: 4)
        with pytest.raises(NotImplementedError, match="PERF.md"):
            fabric.resolve_mesh(mesh)
        assert fabric.resolve_mesh(1) == 1
        assert fabric.resolve_mesh(None) is None


# --------------------------------------------------- dispatch accounting
class TestCountDispatches:
    def test_counters_nest_without_racing(self):
        trace = workloads.make("gups", T=T, n=N)
        with scan_engine.count_dispatches() as outer:
            experiment.sweep(["hemem"], trace=trace, k=K)
            with scan_engine.count_dispatches() as inner:
                experiment.sweep(["hemem"], trace=trace, k=K)
            experiment.sweep(["hemem"], trace=trace, k=K)
        assert inner.count == 1
        assert outer.count == 3
        assert len(outer.records) == 3
        assert outer.last["lanes"] == 1

    def test_counter_sees_nothing_outside_its_scope(self):
        trace = workloads.make("gups", T=T, n=N)
        with scan_engine.count_dispatches() as ctr:
            pass
        experiment.sweep(["hemem"], trace=trace, k=K)
        assert ctr.count == 0 and ctr.last == {}
