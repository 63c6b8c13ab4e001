"""Results come to the host in one transfer per dispatch:
``scan_engine._to_results`` reads a dispatch's whole output tree with one
``jax.device_get`` and slices its lanes in numpy.  Every field must equal
what a per-lane ``float()`` / ``int()`` of the device output gives, with
the same Python types, and ``count_readbacks`` must see one transfer per
dispatch group whatever the lane count."""
import dataclasses

import numpy as np
import pytest

from repro.baselines.hemem import HeMemSpec
from repro.simulator import experiment, scan_engine, workload_spec, workloads
from repro.simulator.machine import PMEM_LARGE
from repro.simulator.sampling import uniform_field

T, N, K = 6, 256, 32
MACHS = ["pmem-large", "numa"]
SCALARS = {"exec_time_s": ("exec_time", float), "promotions": (None, int),
           "demotions": (None, int), "wasteful": (None, int),
           "hot_recall": (None, float), "fast_hit_frac": (None, float)}
STREAM = {"mean_slow_bw": float, "mean_fast_hits": float,
          "mean_mode": float, "max_promotions_interval": int}
TIMELINES = {"timeline_slow_bw": np.float64, "timeline_fast_hits": np.float64,
             "timeline_mode": np.int32, "timeline_promotions": np.int32}


@pytest.fixture
def outputs(monkeypatch):
    """The raw engine output trees handed to ``_to_results``, with the
    names of their lanes, in call order."""
    seen, orig = [], scan_engine._to_results

    def record(out, names):
        seen.append((out, list(names)))
        return orig(out, names)
    monkeypatch.setattr(scan_engine, "_to_results", record)
    return seen


def _same_float(got, want):
    return type(got) is float and float.hex(got) == float.hex(want)


def _check_lane(res, out, lane):
    """``res`` against a per-lane device read of ``out`` (the route the
    bulk transfer replaced): one blocking ``float()`` / ``int()`` each."""
    for field, (key, kind) in SCALARS.items():
        want = kind(out[key or field][lane])
        got = getattr(res, field)
        assert type(got) is kind, (field, type(got))
        assert (_same_float(got, want) if kind is float
                else got == want), (field, got, want)
    if "timeline_slow_bw" in out:
        for field, dtype in TIMELINES.items():
            want = np.asarray(out[field][:, lane]).astype(dtype)
            got = getattr(res, field)
            assert got.dtype == dtype and got.shape == (out[field].shape[0],)
            np.testing.assert_array_equal(got, want)
        assert all(getattr(res, f) is None for f in STREAM)
    else:
        for field, kind in STREAM.items():
            want, got = kind(out[field][lane]), getattr(res, field)
            assert type(got) is kind, (field, type(got))
            assert (_same_float(got, want) if kind is float
                    else got == want), (field, got, want)
        assert all(getattr(res, f) is None for f in TIMELINES)


def _check_all(results, outputs):
    by_name = {r.name: r for r in results}
    assert len(by_name) == len(results)
    lanes = 0
    for out, names in outputs:
        assert int(out["exec_time"].shape[0]) == len(names)
        for lane, nm in enumerate(names):
            _check_lane(by_name[nm], out, lane)
        lanes += len(names)
    assert lanes == len(results)


@pytest.mark.parametrize("pad", [None, 5])
@pytest.mark.parametrize("dispatch", ["union", "grouped"])
@pytest.mark.parametrize("timelines", [False, True])
def test_sweep_fields_equal_per_lane_device_reads(outputs, timelines,
                                                  dispatch, pad):
    res = experiment.sweep(["arms", "hemem", "tpp"],
                           workloads=["gups", "btree"], machines=MACHS,
                           k=K, T=T, n=N, timelines=timelines,
                           dispatch=dispatch, _pad_multiple=pad)
    assert len(outputs) == (1 if dispatch == "union" else 3)
    _check_all(res.grid, outputs)


def _trace():
    return workloads.make("gups", T=T, n=N)


def _wls():
    return [workload_spec.named("gups", T=T), workload_spec.named("btree",
                                                                  T=T)]


#: one sweep per mode: trace or synth; CRN from one seed, per-lane PRNG
#: from several seeds, or an explicit ``sample_u`` field; streamed or
#: stacked timelines.  Lanes differ by machine or seed, so names are unique.
MODES = {
    "trace.crn": lambda: experiment.sweep(
        ["hemem"], trace=_trace(), machines=MACHS, k=K),
    "trace.crn.stack": lambda: experiment.sweep(
        ["arms"], trace=_trace(), machines=MACHS, k=K, timelines=True),
    "trace.prng.stack": lambda: experiment.sweep(
        ["hemem"], trace=_trace(), machines=PMEM_LARGE, seeds=[0, 4, 9],
        k=K, timelines=True),
    "trace.sample_u": lambda: experiment.sweep(
        [HeMemSpec.make(hot_threshold=4)], trace=_trace(), machines=MACHS,
        k=K, sample_u=uniform_field(T, N, seed=3)),
    "synth.crn": lambda: experiment.sweep(
        ["hemem"], workloads=_wls(), machines=PMEM_LARGE, k=K, T=T, n=N),
    "synth.crn.stack": lambda: experiment.sweep(
        ["hemem"], workloads=_wls()[:1], machines=MACHS, k=K, T=T, n=N,
        sim_seed=2, timelines=True),
    "synth.prng": lambda: experiment.sweep(
        ["arms"], workloads=_wls(), machines=PMEM_LARGE, seeds=[0, 4],
        k=K, T=T, n=N),
    "synth.sample_u.stack": lambda: experiment.sweep(
        ["hemem"], workloads=_wls(), machines=MACHS, k=K, T=T, n=N,
        sample_u=uniform_field(T, N, seed=5), timelines=True),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_helper_fields_equal_per_lane_device_reads(outputs, mode):
    with scan_engine.count_readbacks() as rb, \
            scan_engine.count_dispatches() as dc:
        results = MODES[mode]().grid
    assert rb.count == dc.count == len(outputs) == 1
    assert rb.last == {"lanes": len(results)}
    _check_all(results, outputs)


def test_readback_slices_no_device_array(outputs, monkeypatch):
    """Past the one ``device_get`` nothing indexes or converts a device
    array: with those operations refused on the array type, the results
    come out the same."""
    kw = dict(workloads=["gups"], machines=MACHS, k=K, T=T, n=N,
              timelines=True)
    want = experiment.sweep(["hemem"], **kw)
    (out, names), = outputs
    array_type = type(out["exec_time"])

    def refuse(*a, **kw):
        raise AssertionError("a device array was read per lane or field")
    for op in ("__getitem__", "__float__", "__int__", "__index__"):
        monkeypatch.setattr(array_type, op, refuse)
    with scan_engine.count_readbacks() as rb:
        got = scan_engine._to_results(out, names)
    assert rb.count == 1
    assert [dataclasses.astuple(r)[:6] for r in got] == \
        [dataclasses.astuple(r)[:6] for r in want.grid]
    for g, w in zip(got, want.grid):
        for field in TIMELINES:
            np.testing.assert_array_equal(getattr(g, field),
                                          getattr(w, field))
    with pytest.raises(AssertionError, match="per lane"):
        float(out["exec_time"][0])


@pytest.mark.parametrize("seeds,lanes", [(9, 18), (72, 144)])
def test_one_readback_per_single_family_sweep(seeds, lanes):
    with scan_engine.count_readbacks() as rb, \
            scan_engine.count_dispatches() as dc:
        res = experiment.sweep(["hemem"], workloads=["gups"],
                               machines=MACHS, seeds=range(seeds), k=K,
                               T=T, n=N)
    assert len(res.grid) == lanes
    assert rb.count == 1 and rb.last == {"lanes": lanes}
    assert dc.count == 1 and dc.last["lanes"] == lanes


@pytest.mark.parametrize("dispatch,groups", [("grouped", 3), ("union", 1)])
def test_one_readback_per_dispatch_group(dispatch, groups):
    with scan_engine.count_readbacks() as rb, \
            scan_engine.count_dispatches() as dc:
        experiment.sweep(["arms", "hemem", "tpp"], workloads=["gups"],
                         machines=MACHS, k=K, T=T, n=N, dispatch=dispatch)
    assert rb.count == dc.count == groups
    assert [r["lanes"] for r in rb.records] == \
        [d["lanes"] for d in dc.records]


def test_readback_counters_nest_and_stay_in_scope():
    kw = dict(workloads=["gups"], machines=MACHS, k=K, T=T, n=N)
    with scan_engine.count_readbacks() as outer:
        experiment.sweep(["hemem"], **kw)
        with scan_engine.count_readbacks() as inner:
            experiment.sweep(["tpp"], **kw)
    experiment.sweep(["tpp"], **kw)
    assert (outer.count, inner.count) == (2, 1)
