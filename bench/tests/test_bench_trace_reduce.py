"""The trace reduction on a small trace recorded on the CPU, and on
hand-made intervals."""
import time

import pytest

from _paths import BENCH  # noqa: F401
import trace_reduce as tr


def test_opcode_parsing():
    assert tr.opcode("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), "
                     "kind=kLoop") == "fusion"
    assert tr.opcode("%while.2 = (s32[], f32[4]{0}) while((s32[], "
                     "f32[4]{0}) %t), condition=%c") == "while"
    assert tr.opcode("%sort.1 = (f32[4]{0}, s32[4]{0}) sort(f32[4]{0} %a)"
                     ) == "sort"
    assert tr.opcode("copy-done.3") == "copy-done.3"


def test_self_times_and_union():
    ops = [tr.Op("outer", 0, 100), tr.Op("a", 10, 20), tr.Op("b", 40, 30),
           tr.Op("after", 150, 10)]
    tr._self_times(ops)
    self_ns = {o.name: o.self_ns for o in ops}
    assert self_ns == {"outer": 50, "a": 20, "b": 30, "after": 10}
    assert tr._union([(o.start, o.end) for o in ops], 0, 200) == \
        [[0, 100], [150, 160]]
    assert tr._union([(0, 100)], 20, 50) == [[20, 50]]


def test_gaps_attributed_to_spans():
    t = tr.Trace(ops={"d": [tr.Op("x", 0, 10), tr.Op("y", 30, 10)]},
                 modules={}, spans=[tr.Op("bench.window", 0, 60),
                                    tr.Op("sweep.results", 10, 20)],
                 window=(0, 60))
    tr._self_times(t.ops["d"])
    assert t.busy_s() == pytest.approx(20e-9)
    assert t.idle_share() == pytest.approx(40 / 60)
    assert t.gaps() == [(10, 30), (40, 60)]
    by = t.gaps_by_span()
    assert by["sweep.results"] == pytest.approx(20e-9)
    assert by["bench.window"] == pytest.approx(20e-9)
    assert t.busy_within_s("sweep.results") == [0.0]


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) * 2.0 + 1.0)
    x = jnp.arange(200_000, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("sweep.dispatch"):
            for _ in range(3):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("sweep.results"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), ("bench.window", "sweep.dispatch",
                                "sweep.results"), cpu_ops=True)
    assert t.window_s() >= 0.05
    busy = t.busy_s()
    assert 0 < busy < t.window_s()
    assert 0 < t.idle_share() < 1
    by_name = t.op_self_s()
    sort_s = t.select_s(lambda nm: "sort" in nm)
    assert sort_s > 0 and sort_s <= sum(by_name.values()) + 1e-12
    assert t.count(lambda nm: "sort" in nm) >= 3
    gaps = t.gaps_by_span()
    # the sleep is a gap of at least 50 ms inside sweep.results
    assert gaps["sweep.results"] >= 0.045
    assert sum(gaps.values()) == pytest.approx(t.window_s() - busy)
    bd = t.breakdown()
    assert bd["idle_gaps"][0][0] == "sweep.results"
    assert len(bd["device_ops"]) <= 10
