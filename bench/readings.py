"""Readings that the limits of ``checks/<cell>.json`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--out FILE]

For each of ``--seeds``: one sweep of the cell at its timed size, its
sampled lanes replayed by the reference, and the gaps (the lower
readings).  For each of ``--control-seeds``: the reference computed in
bfloat16 put in the program's place, against the float32 reference (the
control, whose gaps give the upper readings).  One JSON line per seed;
all in one process, so the sweep compiles once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import check                                                # noqa: E402
from cell import Cell                                       # noqa: E402


def control_gaps(cell, seed: int) -> dict:
    """The bfloat16 reference against the float32 one on the sampled
    lanes of ``seed``: the gaps over the lanes where bfloat16 gives
    finite statistics (the upper readings), and how many lanes it did
    not (``nonfinite_lanes``, each a failed comparison)."""
    import ml_dtypes
    lanes = check.sample_lanes(cell, seed)
    want = check.reference_stats(cell, lanes, seed)
    got = check.reference_stats(cell, lanes, seed, ft=ml_dtypes.bfloat16)
    ok = [all(np.isfinite(v) for v in g.values()) for g in got]
    out = check.gaps([g for g, o in zip(got, ok) if o],
                     [w for w, o in zip(want, ok) if o], cell.k)
    out["nonfinite_lanes"] = ok.count(False)
    return out


def program_gaps(cell, seed: int) -> dict:
    from repro.simulator import experiment
    res = experiment.sweep(**cell.sweep_args(seed))
    lanes = check.sample_lanes(cell, seed)
    got = check.program_stats(res, cell, lanes)
    del res
    return check.gaps(got, check.reference_stats(cell, lanes, seed),
                      cell.k)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = Cell.load(a.workload)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    cseeds = [int(s) for s in a.control_seeds.split(",") if s]
    out = open(a.out, "a") if a.out else None
    if seeds:
        from repro.utils.compilation import setup_compile_cache
        setup_compile_cache()
    for kind, ss, fn in (("program", seeds, program_gaps),
                         ("control", cseeds, control_gaps)):
        for s in ss:
            t0 = time.perf_counter()
            rec = {"workload": cell.name, "kind": kind, "seed": s,
                   "gaps": fn(cell, s), "s": time.perf_counter() - t0}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
