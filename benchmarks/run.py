"""Benchmark harness entry point (deliverable d).

One function per paper table/figure (benchmarks/paper_tables.py) plus
framework-layer benches (kernels, tiered serving, roofline summary).
Prints ``name,us_per_call,derived`` CSV and a paper-claims validation
report; exits non-zero if a reproduced claim fails.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the tuning study (slowest bench)")
    args, _ = ap.parse_known_args()

    from benchmarks import common, framework, paper_tables as pt
    from repro.utils.compilation import setup_compile_cache
    setup_compile_cache()
    common.header()
    if not args.quick:
        pt.bench_tuning_study()
        pt.bench_tuned_baselines()
        pt.bench_arms_sweep()
    # always-on gates: tuning sweeps must stay lane-batched in the compiled
    # scan engine (a silent fallback to a sequential loop fails CI here),
    # workload-lane sweeps must stay on the device-synthesis path (never
    # host-materializing a [T, n] trace), and machine-axis sweeps must
    # compile to ONE P*M-lane dispatch (no per-machine recompiles) —
    # recorded in BENCH_machines.json.  The kernel gate asserts the fused
    # interval path stays bitwise-identical to the unfused scan under CRN
    # and that default sweeps stream (no [T, ...] timeline allocation) —
    # recorded in BENCH_kernels.json.  The search gate asserts every
    # ASHA/CE round stays ONE compiled dispatch per family and that ASHA
    # reaches within 3% of the exhaustive grid best at <= 40% of its
    # lane-intervals; the transfer gate asserts the tuned-on-A/deployed-
    # on-B matrix's exact grid-strategy invariants over >= 3 machine
    # presets — both recorded in BENCH_search.json.  The robustness gate
    # runs the adversarial-scenario leaderboard (all eight policy
    # families x scenarios x machines as ONE dispatch per family, ARMS
    # worst-case slowdown bounded) — recorded in BENCH_robustness.json.
    # The serving gate closes the model-stack loop: decode traffic on the
    # policy-generic tiered paged-KV pool, captured -> fitted -> swept
    # with the trace-replay lane, one dispatch per family — recorded in
    # BENCH_serving.json.  The sharding gate runs the mesh sweep fabric
    # (union dispatch + shard_map lane sharding) in a forced-8-device
    # subprocess: bitwise equality at every mesh size, ONE dispatch for
    # the whole mixed-family board, throughput within noise — recorded
    # in BENCH_sharding.json.
    pt.bench_baseline_sweep_gate()
    pt.bench_workload_sweep_gate()
    pt.bench_machine_sweep_gate()
    pt.bench_kernel_gate()
    pt.bench_search_gate()
    pt.bench_transfer_matrix()
    pt.bench_machine_sensitivity()
    pt.bench_robustness_gate()
    pt.bench_serving_gate()
    pt.bench_sharding_gate()
    pt.bench_main_comparison()
    pt.bench_migrations()
    pt.bench_adaptivity()
    pt.bench_tier_ratios()
    pt.bench_scaling()
    pt.bench_numa_machine()
    pt.bench_overheads()
    framework.bench_kernels()
    framework.bench_tiered_serving()
    framework.bench_sparse_serving()
    framework.bench_roofline_summary()

    print("\n=== paper-claim validation ===")
    failed = 0
    for name, value, target, ok in pt.CLAIMS:
        status = "PASS" if ok else "FAIL"
        if not ok:
            failed += 1
        print(f"[{status}] {name}: measured {value} (target {target})")
    print(f"=== {len(pt.CLAIMS) - failed}/{len(pt.CLAIMS)} claims hold ===")
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
