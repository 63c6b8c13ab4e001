"""Benchmark of the compiled tiering sweep on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's sweep from its files (``cell.py``), compiles and runs
it once as set-up, then times whole calls of ``experiment.sweep``, each
ending in results on the host, for about ``--seconds``: a further sweep
starts only where, at the length of the last one, it ends inside them.  After
the window a sample of the last sweep's lanes, drawn from the seed, is
replayed by the plain reference (``check.py``).  The last line of
standard output is one JSON object: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the window (``trace_reduce.py``, ``metrics/``).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import contextlib                                           # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import numpy as np                                          # noqa: E402

import check                                                # noqa: E402
import trace_reduce                                         # noqa: E402
from cell import Cell, benchmark                            # noqa: E402

#: the benchmark's host spans, outermost first
SPANS = ("bench.window", "sweep", "sweep.build", "sweep.dispatch",
         "sweep.results")


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def find_devices(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX finds no device: {e}")
    if devs[0].platform == "cpu":
        fail("JAX finds no accelerator; the benchmark never runs on the "
             "CPU")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


class _Spans:
    """The benchmark's host spans around the layers a sweep calls: the
    whole call, its set-up (``sweep.build``), the fabric's dispatch
    (``sweep.dispatch``) and the results' way to the host
    (``sweep.results``).  Recorded only in a traced run."""

    def __init__(self):
        self.open = None

    def switch(self, name):
        import jax
        if self.open is not None:
            self.open.__exit__(None, None, None)
        self.open = jax.profiler.TraceAnnotation(name) if name else None
        if self.open is not None:
            self.open.__enter__()

    @contextlib.contextmanager
    def patched(self):
        from repro.simulator import fabric
        orig = fabric.sim_synth

        def sim_synth(*a, **kw):
            self.switch("sweep.dispatch")
            try:
                return orig(*a, **kw)
            finally:
                self.switch("sweep.results")

        fabric.sim_synth = sim_synth
        try:
            yield
        finally:
            fabric.sim_synth = orig


def window(args, seconds: float, spans=None):
    """Whole sweeps, at least one, the next started only where it ends
    within ``seconds`` at the length of the last -> (last result, each
    sweep's seconds, elapsed s, dispatches, compiles)."""
    import jax
    from repro.simulator import experiment, scan_engine
    from repro.utils.compilation import count_compiles
    times, res = [], None
    with count_compiles() as cc, scan_engine.count_dispatches() as dc:
        t0 = t = time.perf_counter()
        while True:
            if spans is None:
                res = experiment.sweep(**args)
            else:
                with jax.profiler.TraceAnnotation("sweep"):
                    spans.switch("sweep.build")
                    res = experiment.sweep(**args)
                    spans.switch(None)
            now = time.perf_counter()
            times.append(now - t)
            t = now
            if now - t0 + times[-1] > seconds:
                break
        elapsed = time.perf_counter() - t0
    return res, times, elapsed, dc.count, cc.count


def traced_window(args, seconds: float):
    import jax
    spans = _Spans()
    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        with spans.patched(), jax.profiler.TraceAnnotation("bench.window"):
            out = window(args, seconds, spans)
        jax.profiler.stop_trace()
        tr = trace_reduce.load(logdir, SPANS)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return out, tr


def per_layer(cell, bench, ctx) -> dict:
    """Each per-layer metric of the cell, from its reader in metrics/."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell.name not in m["workloads"]:
            continue
        mod = importlib.import_module(f"metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None, devices=None, cell=None) -> int:
    """The benchmark run; tests pass ``devices`` (skipping the look for a
    chip) and a scaled ``cell``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    bench = benchmark()
    cell = cell or Cell.load(a.workload, bench)
    devs = devices or find_devices(cell.chips)
    dev = devs[0]
    from repro.simulator import experiment
    from repro.utils.compilation import setup_compile_cache
    setup_compile_cache()
    peaks = trace_reduce.peaks(dev.device_kind) if a.trace else None

    args = cell.sweep_args(a.seed)
    experiment.sweep(**args)                   # compile + warm up
    setup_s = time.perf_counter() - T_START

    if a.trace:
        (res, times, elapsed, disp, comps), tr = traced_window(
            args, a.seconds)
    else:
        res, times, elapsed, disp, comps = window(args, a.seconds)
    sweeps = len(times)
    lane_intervals = cell.lanes * cell.T * sweeps
    rate = lane_intervals / elapsed
    # the CPU backend (tests) keeps no memory statistics
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    # correctness: the last timed sweep's sampled lanes vs the reference
    lanes = check.sample_lanes(cell, a.seed)
    got = check.program_stats(res, cell, lanes)
    del res, args
    want = check.reference_stats(cell, lanes, a.seed)
    lim = check.limits(cell.name)
    gaps = check.gaps(got, want, cell.k)
    failed = check.lane_failures(got, want, lim, cell.k)
    checks = {k: {"value": gaps[k], "limit": lim[k]} for k in lim}
    correct = (comps == 0 and failed == 0
               and all(gaps[k] <= lim[k] for k in lim))
    checks["compiles_in_window"] = {"value": comps, "limit": 0}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(lanes),
           "failed": int(failed)}
    if a.trace:
        ctx = dict(cell=cell, trace=tr, peaks=peaks, sweeps=sweeps,
                   lane_intervals=lane_intervals, dispatches=disp)
        out["metrics"] = per_layer(cell, bench, ctx)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        out["device"] = device
        out["breakdown"] = tr.breakdown()
    else:
        out["metrics"] = {
            "lane_intervals_per_s": {"value": rate,
                                     "unit": "lane-intervals/s"},
            "peak_hbm_gb": {"value": peak / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        out["device"] = device
    out["checks"] = checks
    print("sweep seconds: " + " ".join(f"{t:.4f}" for t in times),
          file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
