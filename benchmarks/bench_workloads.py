"""Record the trace-synthesis scale win into BENCH_workloads.json.

Before (materialized path): every workload lane of a sweep needs its dense
``[T, n]`` f32 trace host-materialized, plus the host oracle masks and a
``[T, n]`` CRN uniform field — O(T*n) bytes each, which capped scenario
scale by host memory (n=65536, T=4096 is 1 GiB of trace per workload
before sampling fields).  After (synth path): the same W-workload x
B-config study runs as ONE compiled dispatch straight from the
``WorkloadSpec`` pytrees — true counts and the oracle are synthesized on
device per interval, per-lane storage is O(n), and nothing ``[T, n]``
exists on host or device.

Usage:
  PYTHONPATH=src:. python benchmarks/bench_workloads.py \
      [--n 65536] [--T 4096] [--budget 2] [--workloads gups,silo-tpcc] \
      [--quick] [--out BENCH_workloads.json]
"""
from __future__ import annotations

import argparse
import json
import resource
import time

from benchmarks import common
from repro.baselines.hemem import HeMemSpec
from repro.simulator import experiment, scan_engine, workload_spec, workloads
from repro.simulator.engine import oracle_topk_masks
from repro.simulator.machine import PMEM_LARGE
from repro.simulator.sampling import uniform_field


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_workloads.json")
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--T", type=int, default=4096)
    ap.add_argument("--budget", type=int, default=2)
    ap.add_argument("--workloads", default="gups,silo-tpcc")
    ap.add_argument("--quick", action="store_true",
                    help="tiny scale smoke run (n=2048, T=256)")
    args = ap.parse_args()

    n, T = (2048, 256) if args.quick else (args.n, args.T)
    k = n // 8
    wl_names = args.workloads.split(",")
    W, B = len(wl_names), args.budget
    cfgs = [dict(hot_threshold=float(h)) for h in (4, 8, 16, 32)][:B]
    pol_specs = [HeMemSpec.make(**c) for c in cfgs]
    specs = [workloads.spec(nm, T=T) for nm in wl_names]

    rec = dict(n_pages=n, T=T, k=k, workloads=wl_names, budget=B,
               lanes=W * B)
    rec["trace_bytes_per_workload"] = T * n * 4
    rec["synth_state_bytes_per_workload"] = 2 * n * 4  # rank + rank2 (i32)

    # --- after: device synthesis, one W*B-lane dispatch, nothing [T, n] ---
    print(f"[bench_workloads] synth sweep: {W} workloads x {B} configs, "
          f"n={n} T={T} k={k}", flush=True)
    mat_before = workload_spec.MATERIALIZE_CALLS
    kw = dict(workloads=specs, machines=PMEM_LARGE, k=k, T=T, n=n,
              timelines=True)
    _, cold_s = common.timed(experiment.sweep, pol_specs, **kw)
    rec["synth_sweep_cold_s"] = round(cold_s, 3)
    _, warm_s = common.timed(experiment.sweep, pol_specs, **kw)
    rec["synth_sweep_warm_s"] = round(warm_s, 3)
    rec["synth_lanes"] = scan_engine.last_dispatch["lanes"]
    rec["synth_materialize_calls"] = \
        workload_spec.MATERIALIZE_CALLS - mat_before
    rec["synth_peak_rss_mb"] = round(_rss_mb(), 1)
    print(f"[bench_workloads] synth: cold {rec['synth_sweep_cold_s']}s, "
          f"warm {rec['synth_sweep_warm_s']}s, "
          f"rss {rec['synth_peak_rss_mb']}MB", flush=True)

    # --- before: host-materialized traces + oracle + CRN field, one
    # trace-mode sweep per workload -----------------------------------
    mat_s = orc_s = sweep_s = 0.0
    for nm, sp in zip(wl_names, specs):
        t0 = time.time()
        trace = sp.materialize(T, n)
        mat_s += time.time() - t0
        t0 = time.time()
        oracle_topk_masks(trace, k)     # what a trace-mode sweep pays
        orc_s += time.time() - t0
        t0 = time.time()
        experiment.sweep(pol_specs, trace=trace, machines=PMEM_LARGE, k=k,
                         sample_u=uniform_field(T, n, seed=0),
                         timelines=True)
        sweep_s += time.time() - t0
        print(f"[bench_workloads] materialized {nm}: done "
              f"(cum mat {mat_s:.1f}s orc {orc_s:.1f}s sweep {sweep_s:.1f}s)",
              flush=True)
    rec["materialized_trace_build_s"] = round(mat_s, 3)
    rec["materialized_oracle_s"] = round(orc_s, 3)
    rec["materialized_sweep_s"] = round(sweep_s, 3)
    rec["materialized_total_s"] = round(mat_s + orc_s + sweep_s, 3)
    rec["materialized_host_bytes"] = W * (2 * T * n * 4 + T * n)  # +u field
    rec["materialized_peak_rss_mb"] = round(_rss_mb(), 1)
    rec["scale_win_wall"] = round(
        rec["materialized_total_s"] / max(rec["synth_sweep_warm_s"], 1e-9), 2)
    rec["scale_win_bytes_per_workload"] = round(
        rec["trace_bytes_per_workload"]
        / rec["synth_state_bytes_per_workload"], 1)

    out = dict(
        description="Workload-lane sweep: device trace synthesis "
                    "(WorkloadSpec protocol) vs host-materialized [T, n] "
                    "traces, same W x B tuned-HeMem study",
        machine="pmem-large model; CI container CPU (2 cores)",
        notes=[
            "'synth' runs the whole W x B study as ONE compiled dispatch "
            "synthesizing true counts and the oracle on device; "
            "materialize_calls==0 proves no [T, n] array was built.",
            "'materialized' is the pre-protocol path: per workload, build "
            "the dense f32 trace, host oracle masks, a [T, n] CRN field, "
            "and one trace-mode sweep dispatch.",
            "bytes per workload: O(T*n) trace vs O(n) synth state "
            "(rank permutations).",
        ],
        **rec,
    )
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
