"""The sweep's own trace: host spans of ``experiment.sweep``
(profiler ``TraceAnnotation``s) and the named scopes of the scan
engine's interval body, which reach the compiled program's op_name
metadata.  On the CPU, trace events carry no op_name, so the scopes are
checked in the lowered program."""
import collections
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.simulator import experiment, scan_engine

SMALL = dict(workloads=["gups", "btree"], machines=["pmem-large", "numa"],
             k=32, T=4, n=256)
SCOPES = ("synth", "sample", "policy", "migrate", "account")


def _host_spans(logdir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("experiment."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _stats(res):
    return [dataclasses.astuple(r) for r in res.grid]


@pytest.mark.parametrize("policies,dispatch,groups,replay", [
    (["arms", "hemem"], "auto", 1, False),      # the union: one program
    (["arms"], "auto", 1, False),               # one family
    (["arms", "hemem"], "grouped", 2, False),   # one program per family
    (["arms", "hemem"], "grouped", 2, True),    # trace replay, per family
])
def test_sweep_spans(tmp_path, policies, dispatch, groups, replay):
    kw = dict(SMALL, dispatch=dispatch)
    if replay:
        kw = dict(k=32, dispatch=dispatch, machines=SMALL["machines"],
                  trace=np.random.default_rng(0).random((4, 256)))
    want = experiment.sweep(policies, **kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = experiment.sweep(policies, **kw)
    finally:
        jax.profiler.stop_trace()
    assert _stats(res) == _stats(want)
    spans = sorted(_host_spans(str(tmp_path)), key=lambda sp: sp[1])
    count = collections.Counter(nm for nm, *_ in spans)
    assert count == {"experiment.sweep": 1, "experiment.build": 1 + groups,
                     "experiment.dispatch": groups,
                     "experiment.wait": groups,
                     "experiment.readback": groups}
    (_, s0, e0), = [sp for sp in spans if sp[0] == "experiment.sweep"]
    assert all(s0 <= s and e <= e0 for _, s, e in spans)
    # the phases follow one another: shared build, then per group
    # build -> dispatch -> wait -> readback
    phases = [nm for nm, *_ in spans if nm != "experiment.sweep"]
    group = ["experiment.build", "experiment.dispatch", "experiment.wait",
             "experiment.readback"]
    assert phases == ["experiment.build"] + group * groups
    inner = [sp for sp in spans if sp[0] != "experiment.sweep"]
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def _lowered(monkeypatch, policies):
    """Lowered text (with locations) of each ``_sim_synth_jit`` call the
    sweep makes."""
    texts, jitted = [], scan_engine._sim_synth_jit

    def record(*a, **kw):
        texts.append(jitted.lower(*a, **kw).as_text(debug_info=True))
        return jitted(*a, **kw)

    monkeypatch.setattr(scan_engine, "_sim_synth_jit", record)
    experiment.sweep(policies, **SMALL)
    return texts


@pytest.mark.parametrize("policies", [
    ["arms", "hemem", "oracle", "tpp", "jenga"], ["arms"], ["memtis"]])
def test_program_scopes(monkeypatch, policies):
    (text,) = _lowered(monkeypatch, policies)
    for scope in SCOPES:
        assert re.search(rf'"(?:[^"/]*/)*{scope}/', text), scope
    if len(policies) > 1:
        for fam in policies:
            assert re.search(rf"policy/vmap\({re.escape(fam)}\)/", text), \
                fam
