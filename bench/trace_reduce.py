"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``.  Device operations are the events of a
device plane's ``XLA Ops`` line (on a TPU); a CPU trace, which the
tests record, has its operations on host threads, marked by an
``hlo_op`` stat.  Ops nest (a ``while`` holds its body's ops), so busy
time is the union of their intervals, and per-name time is each op's
self time: its duration less its direct children's.  Host spans are the
benchmark's own ``TraceAnnotation`` events, found by name.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s, HBM bytes/s and bytes of one chip of this kind."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns on the trace's clock
    dur: float            # ns
    self_ns: float = 0.0

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def opcode(self) -> str:
        return opcode(self.name)


def opcode(name: str) -> str:
    """The HLO opcode of an op event named by its instruction text
    (``%fusion.3 = f32[8]{0} fusion(...)``); the bare name otherwise."""
    if " = " not in name:
        return name
    rest = name.split(" = ", 1)[1]
    if rest.startswith("("):                 # tuple type: skip the parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else rest
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else rest.split("(", 1)[0].strip()


def _self_times(ops: list) -> None:
    """Self time of nested ops: duration less the direct children's."""
    ops.sort(key=lambda o: (o.start, -o.dur))
    stack: list = []
    for o in ops:
        o.self_ns = o.dur
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.dur
        stack.append(o)


def _union(ivals, lo: float, hi: float) -> list:
    """Merged intervals of ``ivals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in ivals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Trace:
    ops: dict                   # device id -> [Op]
    modules: dict               # device id -> [Op]
    spans: list                 # [Op] of the benchmark's host spans
    window: tuple               # (start, end) ns of the outermost span

    # ------------------------------------------------------------ device
    def busy_intervals(self, dev) -> list:
        lo, hi = self.window
        return _union([(o.start, o.end) for o in self.ops[dev]], lo, hi)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(d))
                  for d in self.ops)
        return tot / len(self.ops) / 1e9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def op_self_s(self) -> collections.Counter:
        """Self seconds by op name, summed over devices."""
        lo, hi = self.window
        c = collections.Counter()
        for ops in self.ops.values():
            for o in ops:
                if lo <= o.start and o.end <= hi:
                    c[o.name] += o.self_ns / 1e9
        return c

    def select_s(self, pred) -> float:
        """Self seconds of the ops whose name satisfies ``pred``."""
        return sum(v for k, v in self.op_self_s().items() if pred(k))

    def count(self, pred) -> int:
        lo, hi = self.window
        return sum(1 for ops in self.ops.values() for o in ops
                   if pred(o.name) and lo <= o.start and o.end <= hi)

    def module_s(self, pred) -> float:
        lo, hi = self.window
        return sum(o.dur for ms in self.modules.values() for o in ms
                   if pred(o.name) and lo <= o.start and o.end <= hi) / 1e9

    # ------------------------------------------------------------- gaps
    def gaps(self, dev=None) -> list:
        """Idle gaps of a device in the window: [(start, end)]."""
        dev = next(iter(self.ops)) if dev is None else dev
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy_intervals(dev):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def span_at(self, t: float) -> str:
        """Innermost benchmark span covering time ``t``."""
        best = None
        for s in self.spans:
            if s.start <= t < s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best is not None else "none"

    def gaps_by_span(self) -> collections.Counter:
        c = collections.Counter()
        for s, e in self.gaps():
            c[self.span_at((s + e) / 2)] += (e - s) / 1e9
        return c

    def span_s(self, name: str) -> list:
        return [s.dur / 1e9 for s in self.spans if s.name == name]

    def busy_within_s(self, name: str) -> list:
        """Device-busy seconds inside each span of this name."""
        dev = next(iter(self.ops))
        busy = self.busy_intervals(dev)
        out = []
        for sp in self.spans:
            if sp.name != name:
                continue
            out.append(sum(max(0.0, min(e, sp.end) - max(s, sp.start))
                           for s, e in busy) / 1e9)
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = self.op_self_s().most_common(top)
        gaps = sorted(((e - s) / 1e9, self.span_at((s + e) / 2))
                      for s, e in self.gaps())[::-1][:top]
        return {"device_ops": [[k[:160], v] for k, v in ops],
                "idle_gaps": [[nm, v] for v, nm in gaps]}


def from_profile(pd, span_names, cpu_ops: bool = False) -> Trace:
    """Build a ``Trace`` from a ``ProfileData``; ``cpu_ops`` takes the
    ops of a CPU trace (host threads, ``hlo_op`` stat) as device 0's."""
    ops, modules, spans = {}, {}, []
    span_names = set(span_names)
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and \
            "CUSTOM" not in plane.name
        for line in plane.lines:
            for ev in line.events:
                if ev.name in span_names and not is_dev:
                    spans.append(Op(ev.name, ev.start_ns, ev.duration_ns))
                    continue
                if is_dev and line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).append(
                        Op(ev.name, ev.start_ns, ev.duration_ns))
                elif is_dev and line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).append(
                        Op(ev.name, ev.start_ns, ev.duration_ns))
                elif cpu_ops and not is_dev and ev.duration_ns > 0:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        ops.setdefault("cpu", []).append(
                            Op(ev.name, ev.start_ns, ev.duration_ns))
                        modules.setdefault("cpu", []).append(
                            Op(str(stats.get("hlo_module", "")),
                               ev.start_ns, ev.duration_ns))
    for v in ops.values():
        _self_times(v)
    outer = [s for s in spans if s.name == next(iter(
        n for n in span_names if n.endswith("window")), "")]
    if outer:
        window = (min(s.start for s in outer), max(s.end for s in outer))
    elif spans:
        window = (min(s.start for s in spans), max(s.end for s in spans))
    else:
        allops = [o for v in ops.values() for o in v]
        window = (min(o.start for o in allops), max(o.end for o in allops))
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def load(logdir: str, span_names, cpu_ops: bool = False) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return from_profile(ProfileData.from_file(paths[-1]), span_names,
                        cpu_ops=cpu_ops)
