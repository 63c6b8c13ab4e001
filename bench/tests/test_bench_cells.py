"""Every cell's files load and build valid specs of the program at a
tiny page count; BENCHMARK.json keeps to its shape.  No timing, no
chip."""
import json
import os
import re

import numpy as np
import pytest

from _paths import BENCH, ROOT
from cell import Cell, benchmark

BENCH_JSON = benchmark()
CELLS = [w["name"] for w in BENCH_JSON["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_shape():
    b = BENCH_JSON
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    cfgs = {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in cfgs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in b["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(ROOT, c["file"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_build_specs(name):
    import jax
    from repro.simulator import machine_spec
    cell = Cell.load(name).scaled(pages=256, fast_pages=64, intervals=4)
    assert cell.lanes == len(cell.policies) * len(cell.workloads) \
        * len(cell.machines)
    assert os.path.exists(os.path.join(BENCH, "checks", name + ".json"))
    wls = cell.workload_specs()
    assert len(wls) == len(cell.traffic["workloads"])
    for w in wls:
        st = w.init(cell.n, jax.random.PRNGKey(0))
        p = np.asarray(w.probs_of(st, 0))
        assert p.shape == (cell.n,) and abs(p.sum() - 1.0) < 1e-4
        assert float(w.work_of(st, 0)) > 0
    for m in cell.machine_specs():
        caps = machine_spec.resolved_caps(m, cell.n, cell.k)
        assert caps[0] == cell.k and caps[-1] == cell.n
    pols = cell.policy_specs()
    assert [p.name for p in pols] == [p["family"] for p in cell.policies]
    for p in pols:
        st = p.init(cell.n, cell.k, cell.machine_specs()[0])
        assert jax.tree_util.tree_leaves(st)


@pytest.mark.parametrize("name", CELLS)
def test_config_states_what_it_assumes(name):
    cfg = Cell.load(name).config
    ent = {c["name"]: c for c in BENCH_JSON["configs"]}[cfg["name"]]
    for key in ent["reduced"]:
        assert key in cfg["source_values"]
    assert 0 < Cell.load(name).T < cfg["source_values"]["intervals"]
    assert cfg["precision"] == "float32"
    assert cfg["assumed"] and cfg["guarantees"]
