"""The readers of the sweep program's device time by named scope
(metrics/_scopes.py): the op_name paths, the map from the compiled
module's text, hand-made traces, and the scopes of each cell's program
at a tiny size."""
import importlib

import pytest

from _paths import BENCH  # noqa: F401
import trace_reduce as tr
from cell import Cell, benchmark
from metrics import _scopes

SCOPES = ("synth", "sample", "policy", "migrate", "account")
PATH = ("jit(_sim_synth_jit)/while/body/closed_call/cond/branch_1_fun/"
        "policy/vmap(hemem)/jit(top_k)/sort")


def test_scope_paths():
    assert _scopes.parts("a/vmap(vmap(tpp))/vmap()/b") == \
        ["a", "tpp", "", "b"]
    assert _scopes.in_scope(PATH, "policy")
    assert _scopes.in_scope(PATH, "policy/hemem")
    assert not _scopes.in_scope(PATH, "policy/arms")
    assert not _scopes.in_scope(PATH, "migrate")
    assert not _scopes.in_scope(PATH, "hemem/policy")
    assert not _scopes.in_scope("", "synth")
    # a jitted function's name is not a scope
    assert not _scopes.in_scope("jit(f)/jit(policy)/add", "policy")


MODULE_TEXT = """HloModule jit__sim_synth_jit

%fused_computation.11 (param_0.3: s32[8]) -> s32[8] {
  %param_0.3 = s32[8]{0} parameter(0)
  ROOT %scatter.1 = s32[8]{0} add(s32[8]{0} %param_0.3, s32[8]{0} %param_0.3), metadata={op_name="jit(f)/while/body/migrate/scatter"}
}

%fused_computation.9 (param_0.2: s32[8]) -> s32[8] {
  %param_0.2 = s32[8]{0} parameter(0)
  ROOT %fusion.10 = s32[8]{0} fusion(s32[8]{0} %param_0.2), kind=kLoop, calls=%fused_computation.11
}

%fused_computation.7 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %add.3 = s32[8]{0} add(s32[8]{0} %param_0.1, s32[8]{0} %param_0.1)
}

ENTRY %main.9 (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %fusion.21 = s32[8]{0} fusion(s32[8]{0} %p.1), kind=kLoop, calls=%fused_computation.7, metadata={op_type="mul" op_name="jit(f)/synth/mul" source_file="x.py" source_line=3}
  %fusion.8 = s32[8]{0} fusion(s32[8]{0} %fusion.21), kind=kLoop, calls=%fused_computation.9
  %sort.2 = s32[8]{0} sort(s32[8]{0} %fusion.21), dimensions={0}, to_apply=%compare.1
  %fusion.5 = s32[8]{0} fusion(s32[8]{0} %sort.2), kind=kCustom, calls=%fused_computation.7
  %copy.4 = s32[8]{0} copy(s32[8]{0} %fusion.5), metadata={op_name="jit(f)/while/body/closed_call"}
  ROOT %neg.6 = s32[8]{0} negate(s32[8]{0} %copy.4), metadata={op_name="PATH"}
}
""".replace("PATH", PATH)


def test_op_names_from_module_text():
    """An instruction's own op_name; where a compiler pass left none (or
    only the scan body's), first the op_name of the computations it
    calls, then the nearest informative one through its users and
    operands."""
    how = _scopes.resolve(MODULE_TEXT)
    assert _scopes.op_names(MODULE_TEXT) == {k: on for k, (on, _) in
                                             how.items()}
    assert how["fusion.21"] == ("jit(f)/synth/mul", "own")
    assert how["neg.6"] == (PATH, "own")
    # the called computations win over a nearer operand, even through a
    # nested fusion: fusion.8's operand fusion.21 names ``synth``, but
    # the scatter it performs names ``migrate``
    assert how["fusion.8"] == ("jit(f)/while/body/migrate/scatter",
                               "called")
    # nearest first: the sort's operand names a scope, its user does not
    assert how["sort.2"] == ("jit(f)/synth/mul", "operand")
    # neither fusion.5's called computation, nor its user copy.4 (only
    # the scan body's) nor its operand sort.2 names one, so the search
    # reaches neg.6
    assert how["fusion.5"] == (PATH, "user>user")
    assert how["copy.4"] == (PATH, "user")
    assert how["p.1"] == ("jit(f)/synth/mul", "user")
    # a fused computation's parameter reaches nothing informative
    assert how["param_0.1"] == ("", "none")


def _trace(ops, modules):
    t = tr.Trace(ops={"d": ops}, modules={"d": modules}, spans=[],
                 window=(0, 200))
    tr._self_times(t.ops["d"])
    return t


def _ctx(trace, names):
    return dict(trace=trace, op_names=names, cell=None)


NAMES = {"fusion.1": "jit(_sim_synth_jit)/while/body/synth/mul",
         "sort.2": PATH,
         "fusion.3": "jit(_sim_synth_jit)/while/body/cond/branch_1_fun/"
                     "migrate/vmap()/scatter",
         "fusion.4": "jit(_sim_synth_jit)/while/body/account/add",
         "while.5": "jit(_sim_synth_jit)/while"}


def _program_trace():
    """One run of the sweep program (0-100 ns) holding a while (its own
    time 10 ns) around four scoped ops, then another program's op named
    like one of them."""
    ops = [tr.Op("%while.5 = (s32[]) while(...)", 0, 100),
           tr.Op("%fusion.1 = f32[8] fusion(...)", 0, 30),
           tr.Op("%sort.2 = s32[8] sort(...)", 30, 40),
           tr.Op("%fusion.3 = s32[8] fusion(...)", 70, 10),
           tr.Op("%fusion.4 = f32[8] fusion(...)", 80, 10),
           tr.Op("%fusion.1 = f32[8] fusion(...)", 150, 50)]
    mods = [tr.Op("jit__sim_synth_jit(123)", 0, 100),
            tr.Op("jit_slice(7)", 150, 50)]
    return _trace(ops, mods)


@pytest.mark.parametrize("name,share", [
    ("synth", 30 / 150), ("sample", None), ("policy", 40 / 150),
    ("migrate", 10 / 150), ("account", 10 / 150)])
def test_readers_on_a_hand_made_trace(name, share):
    mod = importlib.import_module(f"metrics.{name}_share")
    got = mod.read(_ctx(_program_trace(), NAMES))
    if share is None:           # no op under ``sample``
        assert got is None
    else:
        assert got == pytest.approx(100 * share)


def test_family_split_and_remainder():
    ctx = _ctx(_program_trace(), NAMES)
    assert _scopes.scope_self_s(ctx, "policy/hemem") == pytest.approx(40e-9)
    assert _scopes.scope_self_s(ctx, "policy/arms") == 0
    # the while's own 10 ns lie under no scope
    scoped = sum(_scopes.scope_self_s(ctx, s) for s in SCOPES)
    assert sum(ctx["program_self_ns"].values()) / 1e9 - scoped == \
        pytest.approx(10e-9)


@pytest.mark.parametrize("name", ["synth", "policy", "migrate", "account"])
def test_readers_refuse_an_instruction_missing_from_the_map(name):
    """An op of the window that the compiled text does not hold means
    the window ran another executable: no share is read, though every
    op of the scope itself is mapped."""
    mod = importlib.import_module(f"metrics.{name}_share")
    names = {k: v for k, v in NAMES.items() if k != "while.5"}
    assert mod.read(_ctx(_program_trace(), names)) is None


@pytest.mark.parametrize("name", [f"{s}_share" for s in SCOPES])
def test_readers_find_nothing(name):
    """A program without scopes (names without them), and a trace with no
    device op, give no reading."""
    mod = importlib.import_module(f"metrics.{name}")
    bare = {k: "jit(_sim_synth_jit)/while/body/mul" for k in NAMES}
    assert mod.read(_ctx(_program_trace(), bare)) is None
    assert mod.read(_ctx(_trace([], []), NAMES)) is None


@pytest.mark.parametrize("name", [w["name"] for w in benchmark()["workloads"]])
def test_each_cell_program_has_the_scopes(name):
    cell = Cell.load(name).scaled(pages=256, fast_pages=64, intervals=2)
    names = _scopes.op_names(_scopes.module_text(cell))
    paths = list(names.values())
    for scope in SCOPES:
        assert any(_scopes.in_scope(p, scope) for p in paths), scope
    fams = {p["family"] for p in cell.policies}
    if len(fams) > 1:
        for fam in fams:
            assert any(_scopes.in_scope(p, f"policy/{fam}")
                       for p in paths), fam
