"""Benchmarks reproducing the paper's tables/figures (deliverable d).

Each function reproduces one figure/table and emits CSV rows; the asserted
claims are collected and reported at the end of run.py.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from benchmarks.common import emit, geomean
from repro.baselines.hemem import HeMemPolicy
from repro.simulator import scan_engine, tuning, workloads
from repro.simulator.engine import run
from repro.simulator.machine import NUMA, PMEM_LARGE
from repro.simulator.sampling import uniform_field

CLAIMS = []


def claim(name, value, target, ok):
    CLAIMS.append((name, value, target, bool(ok)))


def _default_row(rows, defaults):
    return next(res for cfg, res in rows if cfg == dict(defaults))


# ------------------------------------------------------ Fig. 2/3 + Table 2
def bench_tuning_study(budget: int = 24):
    """Tuned vs default HeMem per workload (paper: 1.05-2.09x gains).

    The whole budget is ONE lane-batched scan-engine dispatch per workload;
    tuned and default rows share the CRN noise field (paired comparison).
    """
    gains = []
    for wl in common.WORKLOAD_SET:
        trace = common.trace_for(wl)
        t0 = time.time()
        best_cfg, best_res, rows = tuning.tune_hemem(
            trace, PMEM_LARGE, common.K, budget=budget)
        wall = time.time() - t0
        default = _default_row(rows, tuning.HEMEM_DEFAULTS)
        gain = default.exec_time_s / best_res.exec_time_s
        gains.append(gain)
        emit(f"tuning_study.{wl}", wall * 1e6,
             f"tuned_gain={gain:.3f};best={best_cfg}")
    claim("tuning helps (geomean default/tuned)", f"{geomean(gains):.2f}x",
          ">=1.05x (paper: 1.05-2.09x per workload)", geomean(gains) >= 1.05)


# ------------------------------------------- Table 2: tuned-vs-untuned, all
def bench_tuned_baselines(budget: int = 16):
    """The paper's tuned-vs-untuned speedup table for every baseline family
    (Tuned-HeMem / Tuned-Memtis / Tuned-TPP), via the unified batched
    ``tuning.tune`` API — one compiled lane-batched sweep per family."""
    fams = [("hemem", tuning.tune_hemem, tuning.HEMEM_DEFAULTS),
            ("memtis", tuning.tune_memtis, tuning.MEMTIS_DEFAULTS),
            ("tpp", tuning.tune_tpp, tuning.TPP_DEFAULTS)]
    hemem_gains = []
    for wl in ("gups", "silo-tpcc", "xsbench"):
        trace = common.trace_for(wl)
        for fam, tune_fn, defaults in fams:
            t0 = time.time()
            best_cfg, best_res, rows = tune_fn(trace, PMEM_LARGE, common.K,
                                               budget=budget)
            wall = time.time() - t0
            gain = _default_row(rows, defaults).exec_time_s \
                / best_res.exec_time_s
            if fam == "hemem":
                hemem_gains.append(gain)
            emit(f"tuned_baselines.{wl}.{fam}", wall * 1e6,
                 f"tuned_gain={gain:.3f};"
                 f"lanes={scan_engine.last_dispatch['lanes']};"
                 f"best={best_cfg}")
    claim("tuned-baseline table: tuning HeMem helps on latest-style loads",
          f"max_gain={max(hemem_gains):.2f}x", ">= 1.02x somewhere",
          max(hemem_gains) >= 1.02)


# ------------------------------------- CI gate: sweeps must stay batched
def bench_baseline_sweep_gate():
    """Quick-gate: a small tuned-baseline sweep must (a) run as ONE
    lane-batched compiled dispatch — a regression that silently falls back
    to a sequential per-config loop fails here — and (b) agree exactly with
    the sequential numpy reference path under the shared CRN field."""
    T_, n, k, sim_seed = 96, 256, 32, 2
    trace = workloads.make("silo-tpcc", T=T_, n=n)
    t0 = time.time()
    _, _, rows = tuning.tune_hemem(trace, PMEM_LARGE, k, budget=6,
                                   sim_seed=sim_seed)
    wall = time.time() - t0
    lanes = scan_engine.last_dispatch.get("lanes")
    claim("tuned-baseline sweep runs lane-batched",
          f"lanes={lanes} for {len(rows)} configs",
          "one compiled dispatch covering the whole budget",
          lanes == len(rows) and scan_engine.last_dispatch.get(
              "sampling") == "crn")
    cfg, res = rows[0]
    ref = run(HeMemPolicy(**cfg), trace, PMEM_LARGE, k,
              sample_u=uniform_field(T_, n, seed=sim_seed))
    emit("baseline_sweep_gate.hemem", wall * 1e6,
         f"lanes={lanes};best_promotions={res.promotions}")
    claim("batched sweep == sequential numpy path (shared CRN)",
          f"P/D/W {res.promotions}/{res.demotions}/{res.wasteful}",
          f"numpy {ref.promotions}/{ref.demotions}/{ref.wasteful}",
          (res.promotions, res.demotions, res.wasteful)
          == (ref.promotions, ref.demotions, ref.wasteful))


# --------------------------------- CI gate: workload lanes must stay synth
def bench_workload_sweep_gate():
    """Quick-gate for the trace-synthesis path: a W-workload x B-config
    tuning sweep must (a) compile to ONE dispatch with W*B lanes, (b)
    never host-materialize a [T, n] trace (the whole point of the
    WorkloadSpec protocol: per-lane storage O(n), not O(T*n)), and (c)
    agree exactly with the sequential numpy reference replay of any lane
    on the materialized trace + reconstructed CRN noise rows."""
    from repro.baselines.hemem import HeMemPolicy
    from repro.simulator import workload_spec
    from repro.simulator.sampling import synth_noise_field

    wls = ["gups", "silo-tpcc", "xsbench"]
    T_, n, k, budget, sim_seed = 96, 256, 32, 4, 3
    mat_before = workload_spec.MATERIALIZE_CALLS
    t0 = time.time()
    per_wl = tuning.tune("hemem", None, PMEM_LARGE, k, budget=budget,
                         sim_seed=sim_seed, workloads=wls, T=T_, n=n)
    wall = time.time() - t0
    B = len(per_wl[wls[0]][2])
    lanes = scan_engine.last_dispatch.get("lanes")
    claim("workload sweep runs as one W*B-lane synth dispatch",
          f"lanes={lanes} for {len(wls)} workloads x {B} configs",
          "W*B lanes, synth=True, device CRN rows",
          lanes == len(wls) * B
          and scan_engine.last_dispatch.get("synth") is True
          and scan_engine.last_dispatch.get("sampling") == "crn_prng")
    claim("synth sweep never host-materializes a [T, n] trace",
          f"materialize_calls_delta="
          f"{workload_spec.MATERIALIZE_CALLS - mat_before}",
          "0", workload_spec.MATERIALIZE_CALLS == mat_before)
    # lane == sequential numpy replay on the materialized trace + the
    # host-reconstructed copy of the device CRN rows
    cfg, res = per_wl["silo-tpcc"][2][0]
    trace = workloads.spec("silo-tpcc", T=T_).materialize(T_, n)
    ref = run(HeMemPolicy(**cfg), trace, PMEM_LARGE, k,
              sample_u=synth_noise_field(T_, n, seed=sim_seed))
    emit("workload_sweep_gate.hemem", wall * 1e6,
         f"lanes={lanes};workloads={len(wls)};configs={B}")
    claim("synth lane == numpy replay of materialized trace (shared CRN)",
          f"P/D/W {res.promotions}/{res.demotions}/{res.wasteful}",
          f"numpy {ref.promotions}/{ref.demotions}/{ref.wasteful}",
          (res.promotions, res.demotions, res.wasteful)
          == (ref.promotions, ref.demotions, ref.wasteful))


# ------------------------------- CI gate: machine sweeps must stay batched
def bench_machine_sweep_gate():
    """Quick-gate for the machine axis: a P-config x M-machine sweep must
    (a) compile to ONE lane-batched dispatch covering the whole P*M
    product — a regression to per-machine recompiles or a sequential
    fallback fails here — with tier depths unified by neutral padding,
    and (b) agree exactly with a standalone single-machine dispatch on
    any lane.  Records the result in BENCH_machines.json."""
    import json

    from repro.baselines.hemem import HeMemSpec
    from repro.simulator import experiment, workload_spec

    T_, n, k, sim_seed = 96, 256, 32, 2
    cfgs = tuning.sample_configs(4)
    specs = [HeMemSpec.make(**c) for c in cfgs]
    mach_names = ["pmem-large", "numa", "cxl-1hop", "dram-cxl-pmem"]
    P, M = len(specs), len(mach_names)
    wl = workload_spec.named("silo-tpcc", T=T_)

    res, cold = common.timed(
        experiment.sweep, specs, workloads=[wl], machines=mach_names,
        k=k, T=T_, n=n, sim_seed=sim_seed)
    _, warm = common.timed(
        experiment.sweep, specs, workloads=[wl], machines=mach_names,
        k=k, T=T_, n=n, sim_seed=sim_seed)

    d = dict(scan_engine.last_dispatch)
    claim("machine sweep runs as ONE P*M-lane dispatch",
          f"lanes={d.get('lanes')} for {P} configs x {M} machines "
          f"(mixed 2/3-tier)",
          "P*M lanes, no per-machine recompiles or sequential fallback",
          d.get("lanes") == P * M and d.get("machines") == M
          and d.get("axis_product") is True)
    single = scan_engine.simulate_workload(specs[0], wl, "dram-cxl-pmem",
                                           k, T_, n, sim_seed=sim_seed)
    lane = res.at(policy=0, machine="dram-cxl-pmem")
    claim("machine-sweep lane == standalone single-machine run",
          f"P/D/W {lane.promotions}/{lane.demotions}/{lane.wasteful}",
          f"single {single.promotions}/{single.demotions}/"
          f"{single.wasteful}",
          (lane.promotions, lane.demotions, lane.wasteful)
          == (single.promotions, single.demotions, single.wasteful))
    emit("machine_sweep_gate.hemem", warm * 1e6,
         f"lanes={d.get('lanes')};machines={M};configs={P};"
         f"cold_s={cold:.3f}")
    rec = dict(workload="silo-tpcc", n_pages=n, T=T_, k=k,
               configs=P, machines=mach_names, lanes=d.get("lanes"),
               sampling=d.get("sampling"), cold_s=round(cold, 3),
               warm_s=round(warm, 3),
               best_config_per_machine={
                   m: min(range(P),
                          key=lambda p: res.at(policy=p,
                                               machine=m).exec_time_s)
                   for m in mach_names})
    with open("BENCH_machines.json", "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")


# --------------- CI gate: fused interval path + streaming reduction
def bench_kernel_gate():
    """Quick-gate for the fused interval fast path: (a) the fused route
    (``use_interval_kernel``, default) must be BITWISE identical to the
    unfused scan under the shared CRN field for every policy family on a
    2-tier and a 3-tier machine — scalars and all four timelines; (b) a
    default sweep must run under streaming reduction with no [T, ...]
    output anywhere (checked structurally here at gate scale and by
    abstract evaluation at n=65536/T=4096).  Records warm fused-vs-unfused
    step time in BENCH_kernels.json."""
    import json

    from benchmarks import bench_kernels
    from repro.simulator import experiment

    T_, n, k, sim_seed = 96, 256, 32, 2
    fams = ["arms", "hemem", "memtis", "tpp", "all-slow", "oracle"]
    machs = ["pmem-large", "dram-cxl-pmem"]
    trace = workloads.make("silo-tpcc", T=T_, n=n)
    u = uniform_field(T_, n, seed=sim_seed)

    fused, cold = common.timed(
        experiment.sweep, fams, trace=trace, machines=machs, k=k,
        sample_u=u, timelines=True)
    plain, _ = common.timed(
        experiment.sweep, fams, trace=trace, machines=machs, k=k,
        sample_u=u, timelines=True, use_interval_kernel=False)
    bad = []
    for (where, a), (_, b) in zip(fused.items(), plain.items()):
        same = (a.promotions, a.demotions, a.wasteful) \
            == (b.promotions, b.demotions, b.wasteful) \
            and a.exec_time_s == b.exec_time_s \
            and a.hot_recall == b.hot_recall \
            and all(np.array_equal(getattr(a, f), getattr(b, f))
                    for f in ("timeline_slow_bw", "timeline_fast_hits",
                              "timeline_mode", "timeline_promotions"))
        if not same:
            bad.append(f"{where['policy']}@{where['machine']}")
    claim("fused interval path bitwise == unfused (CRN, all families)",
          f"{len(fams)} families x {machs} (2- and 3-tier): "
          + ("all equal" if not bad else "DIFF " + ",".join(bad)),
          "every scalar and timeline bitwise identical", not bad)

    # streaming is the sweep default: no [T, ...] output, summaries set
    res, _ = common.timed(
        experiment.sweep, ["hemem", "arms"], workloads=["gups"],
        machines=machs, k=k, T=T_, n=n, sim_seed=sim_seed)
    d = dict(scan_engine.last_dispatch)
    stream_ok = d.get("reduce") == "stream" and all(
        r.timeline_slow_bw is None and r.mean_slow_bw is not None
        for _, r in res.items())
    alloc = bench_kernels.stream_alloc_proof()
    claim("streaming sweep allocates no [T, ...] timeline",
          f"dispatch reduce={d.get('reduce')}; eval_shape at "
          f"n={alloc['n_pages']}/T={alloc['T']}: "
          f"{alloc['stream_T_sized_outputs']} T-sized outputs "
          f"(stack: {alloc['stack_T_sized_outputs']})",
          "reduce=stream, 0 T-sized output leaves, summaries populated",
          stream_ok and alloc["stream_T_sized_outputs"] == 0
          and alloc["stack_T_sized_outputs"] > 0)

    # warm fused vs unfused step time at gate scale -> BENCH_kernels.json
    # (benchmarks/bench_kernels.py re-measures at full n=65536/T=4096).
    _, warm_fused = common.timed(
        experiment.sweep, fams, trace=trace, machines=machs, k=k,
        sample_u=u, timelines=True)
    _, warm_unfused = common.timed(
        experiment.sweep, fams, trace=trace, machines=machs, k=k,
        sample_u=u, timelines=True, use_interval_kernel=False)
    emit("kernel_gate.fused_sweep", warm_fused * 1e6,
         f"families={len(fams)};machines={len(machs)};cold_s={cold:.3f};"
         f"unfused_warm_us={warm_unfused * 1e6:.0f}")
    rec = dict(scale="gate-quick", workload="silo-tpcc", n_pages=n, T=T_,
               k=k, families=fams, machines=machs,
               bitwise_equal=not bad, streaming_default=stream_ok,
               cold_fused_s=round(cold, 3),
               warm_fused_s=round(warm_fused, 3),
               warm_unfused_s=round(warm_unfused, 3),
               step_time_win=round(warm_unfused / max(warm_fused, 1e-9),
                                   3),
               stream_alloc=alloc)
    # merge under "gate" so the full-scale record written by
    # benchmarks/bench_kernels.py survives CI passes.
    try:
        with open("BENCH_kernels.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out["gate"] = rec
    with open("BENCH_kernels.json", "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


# ------------------- CI gate: adaptive search rounds must stay compiled
def bench_search_gate():
    """Quick-gate for the adaptive search engine: (a) every round of every
    strategy — grid's single scoring pass, each ASHA elimination rung,
    each CE redraw generation — must run as ONE compiled dispatch per
    policy family (the engine records the dispatch-counter delta per
    round); (b) ASHA must land within 3% of the exhaustive grid's best
    exec time for HeMem/Memtis/TPP while spending <= 40% of the grid's
    lane-intervals, on the same seeded population under the same CRN
    field.  Records the compute-vs-quality curves in BENCH_search.json
    under "gate" (benchmarks/bench_search.py writes the full-scale
    record)."""
    import json

    from repro.simulator import search

    T_, n, k, budget = 120, 256, 32, 16
    trace = workloads.make("silo-tpcc", T=T_, n=n)
    rec = dict(T=T_, n_pages=n, k=k, budget=budget, workload="silo-tpcc",
               families={})
    gaps, fracs, rounds_bad = [], [], []
    for fam in ("hemem", "memtis", "tpp"):
        t0 = time.time()
        runs = {s: search.run(fam, s, trace=trace, k=k, budget=budget)
                for s in ("grid", "asha", "ce")}
        wall = time.time() - t0
        g, a, c = runs["grid"], runs["asha"], runs["ce"]
        for s, sr in runs.items():
            rounds_bad += [f"{fam}.{s}#{r.index}" for r in sr.rounds
                           if r.dispatches != 1]
        gap = float(a.best_result.exec_time_s
                    / g.best_result.exec_time_s) - 1.0
        frac = a.lane_intervals / g.lane_intervals
        gaps.append(gap)
        fracs.append(frac)
        rec["families"][fam] = dict(
            grid_best_s=round(float(g.best_result.exec_time_s), 6),
            asha_best_s=round(float(a.best_result.exec_time_s), 6),
            ce_best_s=round(float(c.best_result.exec_time_s), 6),
            asha_gap=round(gap, 4), asha_li_frac=round(frac, 4),
            grid_lane_intervals=g.lane_intervals,
            asha_lane_intervals=a.lane_intervals,
            asha_rounds=len(a.rounds), ce_rounds=len(c.rounds),
            asha_curve=[[int(li), round(float(t), 6)]
                        for li, t in a.curve()],
            ce_curve=[[int(li), round(float(t), 6)]
                      for li, t in c.curve()])
        emit(f"search_gate.{fam}", wall * 1e6,
             f"asha_gap={gap:+.4f};li_frac={frac:.3f};"
             f"asha_rounds={len(a.rounds)}")
    claim("every search round is ONE compiled dispatch per family",
          "all rounds single-dispatch" if not rounds_bad
          else "MULTI " + ",".join(rounds_bad),
          "grid/ASHA/CE rounds never fall back to per-config loops",
          not rounds_bad)
    claim("ASHA within 3% of grid best at <= 40% of grid lane-intervals",
          f"max_gap={max(gaps):+.4f} at max_li_frac={max(fracs):.3f} "
          f"(hemem/memtis/tpp)",
          "gap <= 0.03, li_frac <= 0.40", max(gaps) <= 0.03
          and max(fracs) <= 0.40)
    # merge under "gate" so the full-scale record written by
    # benchmarks/bench_search.py survives CI passes.
    try:
        with open("BENCH_search.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out.setdefault("gate", {})
    out["gate"].update(rec)
    with open("BENCH_search.json", "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


# -------------------- machine-transfer matrix ("From Good to Great" §5)
def bench_transfer_matrix():
    """"Tuned on machine A, deployed on machine B" robustness matrix over
    the machine presets — the companion tuning paper's headline
    experiment.  Uses strategy="grid" so the matrix is EXACT: phase 2
    re-scores every tuned config under the same CRN field phase 1 ranked
    them with, making the native config optimal among the tuned set —
    diagonal slowdown 1.0 and off-diagonal >= 1.0 are invariants the gate
    asserts, and any off-diagonal > 1 is a real transfer penalty, not
    noise.  (benchmarks/bench_search.py records the ASHA-driven matrix at
    full scale.)"""
    import json

    from repro.simulator import search

    mach_names = ["pmem-large", "numa", "cxl-1hop", "dram-cxl-pmem"]
    T_, n, k, budget = 120, 256, 32, 8
    trace = workloads.make("silo-tpcc", T=T_, n=n)
    t0 = time.time()
    tm = search.transfer_matrix("hemem", trace, mach_names, k,
                                budget=budget, strategy="grid")
    wall = time.time() - t0
    M = len(tm.machines)
    diag_ok = bool(np.allclose(np.diag(tm.slowdown), 1.0))
    off_ok = bool((tm.slowdown >= 1.0 - 1e-12).all())
    worst = max(float(tm.slowdown[a, b]) for a in range(M)
                for b in range(M) if a != b)
    for r in tm.rows():
        emit(f"transfer_matrix.{r['tuned_on']}", wall * 1e6 / M,
             ";".join(f"{b}={s:.4f}" for b, s in r["slowdown"].items()))
    claim("transfer matrix spans >= 3 machine presets",
          f"{M} machines: {tm.machines}", ">= 3 presets", M >= 3)
    claim("native tuning optimal under shared CRN (diag 1.0, off >= 1.0)",
          f"diag_ok={diag_ok}; min_off={float(tm.slowdown.min()):.6f}; "
          f"worst_foreign={worst:.4f}x",
          "exact invariant of the grid-strategy matrix", diag_ok and off_ok)
    try:
        with open("BENCH_search.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out.setdefault("gate", {})
    out["gate"]["transfer"] = dict(
        family="hemem", strategy="grid", machines=tm.machines,
        T=T_, n_pages=n, k=k, budget=budget,
        worst_foreign_slowdown=round(worst, 4), rows=tm.rows())
    with open("BENCH_search.json", "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


# --------------------------------- machine-sensitivity table (Fig. 11 ++)
def bench_machine_sensitivity():
    """Best-untuned policy per machine: the paper's robustness claim taken
    across the machine axis (two-tier PMem/NUMA/CXL presets plus the
    three-tier DRAM/CXL/PMem chain), each family's W*M grid one compiled
    dispatch."""
    from repro.simulator import experiment

    mach_names = ["pmem-large", "numa", "cxl-1hop", "dram-cxl-pmem"]
    pols = ["hemem", "memtis", "tpp", "arms"]
    wls = ["gups", "silo-tpcc", "xsbench"]
    T_, n, k = 120, 512, 64
    t0 = time.time()
    res = experiment.sweep(pols, workloads=wls, machines=mach_names,
                           k=k, T=T_, n=n)
    wall = time.time() - t0
    ok_all = True
    for m in mach_names:
        geo = {p: geomean([res.at(policy=p, workload=w,
                                  machine=m).exec_time_s for w in wls])
               for p in pols}
        best = min(geo, key=geo.get)
        ok_all &= geo["arms"] <= geo[best] * 1.10
        emit(f"machine_sensitivity.{m}", wall * 1e6 / len(mach_names),
             f"best={best};" + ";".join(
                 f"{p}={geo[p]:.3f}s" for p in pols))
    claim("ARMS within 10% of best untuned policy on EVERY machine",
          "per-machine geomeans above", "robust without re-tuning",
          ok_all)


# --------------------- CI gate: adversarial robustness leaderboard
def bench_robustness_gate():
    """Quick-gate for the robustness leaderboard
    (benchmarks/bench_robustness.py): every policy family — the four
    binary baselines through the tier-native shim, the three tier-native
    families, and the oracle — scored on the adversarial thrashing suite
    across three machine topologies.  Asserts (a) the whole
    mixed-family policy x scenario x machine board compiles to exactly
    ONE lane-batched dispatch (the union fabric, simulator/fabric.py),
    and (b) ARMS' worst-case slowdown vs the
    per-cell oracle stays bounded (with the oracle's self-slowdown
    exactly 1 as a scoring sanity check).  Records the gate-scale board
    in BENCH_robustness.json under "gate"
    (benchmarks/bench_robustness.py writes the full-scale record)."""
    import json

    from benchmarks.bench_robustness import run_robustness

    t0 = time.time()
    rec = run_robustness(T=96, n=256, k=32)
    wall = time.time() - t0
    arms = rec["leaderboard"]["arms"]
    oracle = rec["leaderboard"]["oracle"]
    emit("robustness_gate", wall * 1e6,
         f"dispatches={rec['dispatches']};families={rec['n_families']};"
         f"arms_worst={arms['worst_slowdown']:.3f}@{arms['worst_cell']};"
         f"arms_thrash={arms['mean_thrash']:.3f}")
    claim("mixed-family robustness board is exactly ONE compiled dispatch",
          f"{rec['dispatches']} dispatch(es) for {rec['n_families']} "
          "families",
          "union fabric fuses every family onto one lane axis, no loops",
          rec["single_dispatch"])
    claim("ARMS worst-case slowdown on the adversarial suite",
          f"{arms['worst_slowdown']:.2f}x at {arms['worst_cell']} "
          f"(mean {arms['mean_slowdown']:.2f}x)",
          "<= 8x vs per-cell oracle; oracle self-slowdown == 1",
          arms["worst_slowdown"] <= 8.0
          and abs(oracle["worst_slowdown"] - 1.0) < 1e-6)
    try:
        with open("BENCH_robustness.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    # drop per-cell detail from the gate record; the board summary is
    # what CI diffs care about.
    out["gate"] = dict(rec, leaderboard={
        p: {kk: v for kk, v in b.items() if kk != "cells"}
        for p, b in rec["leaderboard"].items()})
    with open("BENCH_robustness.json", "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


def bench_serving_gate():
    """Quick-gate for the closed serving loop (benchmarks/bench_serving.py):
    real decode traffic drives the policy-generic tiered paged-KV pool,
    its captured attention-mass trace is fitted to WorkloadSpec knobs and
    swept — together with the multi-tenant ``scenarios.serving_mix``
    built from the fit AND the raw trace replay — across every
    leaderboard policy family.  Asserts (a) the serving sweep and the
    trace replay each compile to exactly ONE mixed-family dispatch (the
    union fabric, simulator/fabric.py),
    (b) the captured trace appears as a scenario row of the board next
    to the fitted lane, and (c) the device-side telemetry carry did not
    collapse throughput vs the legacy per-token host-sync path.  Records
    the gate-scale board in BENCH_serving.json under "gate"
    (benchmarks/bench_serving.py writes the full-scale record)."""
    import json

    from benchmarks.bench_serving import run_serving

    t0 = time.time()
    rec = run_serving(n_tokens=16, batch=1, T=48, n=128, k=16,
                      arches=("granite-8b",),
                      serve_policies=("arms", "jenga"))
    wall = time.time() - t0
    sync = rec["telemetry_sync"]
    emit("serving_gate", wall * 1e6,
         f"sweep_disp={rec['sweep_dispatches']};"
         f"replay_disp={rec['replay_dispatches']};"
         f"families={rec['n_families']};"
         f"sync_speedup={sync['speedup']:.3f};"
         f"trace={rec['trace']['T']}x{rec['trace']['n']}")
    claim("serving sweep + trace replay are each ONE mixed-family dispatch",
          f"{rec['sweep_dispatches']}+{rec['replay_dispatches']} "
          f"dispatches for {rec['n_families']} families",
          "fitted/mix lanes and the replay ride one union lane axis",
          rec["single_dispatch"])
    claim("captured serving trace is a leaderboard scenario row",
          f"rows={rec['scenarios']}",
          "trace + fit:<label> + serving-mix rows present",
          "trace" in rec["scenarios"]
          and rec["fitted_label"] in rec["scenarios"]
          and any(s.startswith("serving-mix") for s in rec["scenarios"]))
    claim("device-side telemetry keeps serving throughput",
          f"{sync['tok_s_device']} tok/s device vs "
          f"{sync['tok_s_synced']} tok/s per-token sync "
          f"({sync['speedup']:.2f}x)",
          ">= 0.5x of the host-sync path (records the before/after)",
          sync["speedup"] >= 0.5)
    try:
        with open("BENCH_serving.json") as f:
            out = json.load(f)
    except (OSError, ValueError):
        out = {}
    out["gate"] = dict(rec, leaderboard={
        p: {kk: v for kk, v in b.items() if kk != "cells"}
        for p, b in rec["leaderboard"].items()})
    with open("BENCH_serving.json", "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")


# ----------------------- CI gate: mesh sweep fabric (sharding + union)
def bench_sharding_gate():
    """Quick-gate for the mesh sweep fabric (simulator/fabric.py, bench in
    benchmarks/bench_sharding.py): the mixed-family panel must (a) be
    bitwise-identical unsharded and at every mesh size in {1, 2, 4, 8}
    the devices allow (``bench_sharding.gate_record``: on CPU over
    virtual devices in a child, on an accelerator in this process), (b)
    compile to
    exactly ONE union dispatch where the grouped path needs one per
    family, and (c) keep sharded throughput within noise of the unsharded
    path (>= 0.5x on a single-core CI host; on real multi-device hosts
    the curve scales).  Records the curve in BENCH_sharding.json under "gate"
    (benchmarks/bench_sharding.py writes the full-scale record)."""
    from benchmarks import bench_sharding

    t0 = time.time()
    try:
        rec = bench_sharding.gate_record("BENCH_sharding.json")
    except RuntimeError as e:
        claim("mesh fabric gate produced a record", str(e),
              "BENCH_sharding.json gate record written", False)
        return
    wall = time.time() - t0
    curve = {c["mesh"]: c["lanes_per_s"] for c in rec["mesh_curve"]}
    emit("sharding_gate", wall * 1e6,
         f"lanes={rec['lanes']};devices={rec['devices']};"
         f"union_disp={rec['union']['dispatches']};"
         f"grouped_disp={rec['grouped']['dispatches']};"
         + ";".join(f"mesh{m}={v}l/s" for m, v in sorted(curve.items()))
         + f";unsharded={rec['union']['lanes_per_s']}l/s")
    claim("mesh-sharded sweep bitwise == unsharded at {1,2,4,8}",
          f"bitwise_all={rec['bitwise_all_meshes']} over "
          f"{len(rec['mesh_curve'])} mesh sizes x {rec['lanes']} lanes",
          "every cell bitwise-identical, padded lanes dropped",
          rec["bitwise_all_meshes"])
    claim("mixed-family board: ONE union dispatch vs one per family",
          f"union={rec['union']['dispatches']}, "
          f"grouped={rec['grouped']['dispatches']} "
          f"({rec['n_families']} families)",
          "union == 1 and grouped == n_families",
          rec["union_single_dispatch"]
          and rec["grouped_dispatch_per_family"])
    claim("sharded throughput within noise of unsharded",
          f"{rec['sharded_throughput_ratio']}x at mesh="
          f"{rec['best_mesh']}",
          ">= 0.5x on shared-core virtual devices",
          rec["sharded_throughput_ratio"] >= 0.5)


# ------------------------------------------------------------------ Fig. 7
def bench_main_comparison():
    """ARMS vs HeMem/tuned-HeMem/Memtis/TPP on pmem-large."""
    vs_hemem, vs_memtis, vs_tpp, vs_tuned = [], [], [], []
    for wl in common.WORKLOAD_SET:
        trace = common.trace_for(wl)
        res = {}
        for pol in ("all-slow", "hemem", "memtis", "tpp", "arms"):
            res[pol], wall = common.run_policy(pol, trace)
        _cfg, tuned, _ = tuning.tune_hemem(trace, PMEM_LARGE, common.K,
                                           budget=24)
        a = res["arms"].exec_time_s
        vs_hemem.append(res["hemem"].exec_time_s / a)
        vs_memtis.append(res["memtis"].exec_time_s / a)
        vs_tpp.append(res["tpp"].exec_time_s / a)
        vs_tuned.append(tuned.exec_time_s / a)
        emit(f"main_comparison.{wl}", wall * 1e6,
             f"arms_vs_hemem={vs_hemem[-1]:.3f};"
             f"arms_vs_memtis={vs_memtis[-1]:.3f};"
             f"arms_vs_tpp={vs_tpp[-1]:.3f};"
             f"arms_vs_tuned={vs_tuned[-1]:.3f}")
    claim("ARMS vs default HeMem (geomean)", f"{geomean(vs_hemem):.2f}x",
          "paper: 1.26x", geomean(vs_hemem) >= 1.2)
    claim("ARMS vs Memtis (geomean)", f"{geomean(vs_memtis):.2f}x",
          "paper: 1.34x", geomean(vs_memtis) >= 1.1)
    claim("ARMS vs TPP (geomean)", f"{geomean(vs_tpp):.2f}x",
          "paper: 2.3x", geomean(vs_tpp) >= 1.5)
    claim("ARMS within 3% of tuned HeMem (geomean)",
          f"{geomean(vs_tuned):.3f}", "paper: >=0.97",
          geomean(vs_tuned) >= 0.97)


# ----------------------------------------------------------------- Fig. 10
def bench_migrations():
    """Promotion counts + wasteful migrations per system."""
    tot = {p: 0 for p in ("hemem", "memtis", "tpp", "arms")}
    waste = dict(tot)
    for wl in common.WORKLOAD_SET:
        trace = common.trace_for(wl)
        for pol in tot:
            res, wall = common.run_policy(pol, trace)
            tot[pol] += res.promotions
            waste[pol] += res.wasteful
        emit(f"migrations.{wl}", wall * 1e6,
             ";".join(f"{p}={tot[p]}" for p in tot))
    emit("migrations.wasteful_total", 0,
         ";".join(f"{p}={waste[p]}" for p in waste))
    claim("TPP migrates most (paper: 'extremely high')",
          f"tpp={tot['tpp']}", f"> 2x arms={tot['arms']}",
          tot["tpp"] > 2 * tot["arms"])
    claim("ARMS wasteful migrations lowest among adaptive systems",
          f"arms={waste['arms']}",
          f"<= memtis={waste['memtis']}, tpp={waste['tpp']}",
          waste["arms"] <= waste["memtis"]
          and waste["arms"] <= waste["tpp"])


# ------------------------------------------------------------------ Fig. 9
def bench_adaptivity():
    """PHT change-point detection timeline (btree hot-set shift)."""
    trace = common.trace_for("btree")   # shuffles hot set at T/2
    res, wall = common.run_policy("arms", trace)
    mode = res.timeline_mode
    shift = common.T // 2
    detect = np.flatnonzero(mode[shift:] == 1)
    latency = int(detect[0]) if len(detect) else -1
    emit("adaptivity.btree", wall * 1e6,
         f"detect_latency_intervals={latency};"
         f"recency_intervals={int((mode == 1).sum())}")
    claim("PHT detects hot-set change (Fig. 9)",
          f"latency={latency} intervals", "< 25 intervals (2.5s)",
          0 <= latency < 25)


# ----------------------------------------------------------------- Fig. 13
def bench_tier_ratios():
    """ARMS vs default HeMem across fast:slow capacity ratios."""
    wins = []
    for wl in ("xsbench", "gups"):
        trace = common.trace_for(wl)
        for ratio in (16, 8, 4, 2):
            k = common.N_PAGES // ratio
            h, _ = common.run_policy("hemem", trace, k=k)
            a, wall = common.run_policy("arms", trace, k=k)
            sp = h.exec_time_s / a.exec_time_s
            wins.append(sp)
            emit(f"tier_ratios.{wl}.1to{ratio}", wall * 1e6,
                 f"arms_vs_hemem={sp:.3f}")
    claim("ARMS robust across tier ratios (Fig. 13)",
          f"min={min(wins):.2f}x", ">= 0.95x at every ratio",
          min(wins) >= 0.95)


# ----------------------------------------------------------------- Fig. 12
def bench_scaling():
    """Thread-count analogue: workload intensity scaling (MLP factor)."""
    import dataclasses
    trace = common.trace_for("silo-ycsb")
    for mlp in (16, 32, 64, 128):   # ~4..20 threads of MLP
        m = dataclasses.replace(PMEM_LARGE, mlp=float(mlp))
        h, _ = common.run_policy("hemem", trace, machine=m)
        a, wall = common.run_policy("arms", trace, machine=m)
        emit(f"scaling.mlp{mlp}", wall * 1e6,
             f"arms_vs_hemem={h.exec_time_s / a.exec_time_s:.3f}")


# ----------------------------------------------------------------- Fig. 11
def bench_numa_machine():
    """Different hardware (emulated-CXL NUMA node), no re-tuning."""
    sp = []
    for wl in ("gups", "btree", "silo-ycsb", "xsbench"):
        trace = common.trace_for(wl)
        h, _ = common.run_policy("hemem", trace, machine=NUMA)
        a, wall = common.run_policy("arms", trace, machine=NUMA)
        sp.append(h.exec_time_s / a.exec_time_s)
        emit(f"numa.{wl}", wall * 1e6, f"arms_vs_hemem={sp[-1]:.3f}")
    claim("ARMS wins on different hardware without re-tuning (Fig. 11)",
          f"{geomean(sp):.2f}x", ">= 1.0x geomean", geomean(sp) >= 1.0)


# -------------------------------------------- batched sweeps (scan engine)
def bench_arms_sweep(budget: int = 24, n_seeds: int = 8,
                     n: int = 4096, T: int = 512):
    """Batched lax.scan+vmap ARMS sweeps vs the sequential numpy loop.

    Runs at the acceptance scale (n_pages >= 4096, T >= 512).  Three
    numbers per sweep: sequential numpy loop, batched cold (includes the
    one-off compile), batched warm.  Returns a dict for BENCH_tuning.json.
    """
    import time

    from repro.baselines.arms_policy import ARMSPolicy
    from repro.core.state import ARMSConfig
    from repro.simulator import scan_engine, workloads

    trace = workloads.make("gups", T=T, n=n)
    k = n // 8
    rec = dict(workload="gups", n_pages=n, T=T, k=k, budget=budget,
               n_seeds=n_seeds)

    # --- config sweep (the tuning study) ---
    cfgs = tuning.sample_arms_configs(budget)
    t0 = time.time()
    for cfg in cfgs:
        run(ARMSPolicy(ARMSConfig(**cfg)), trace, PMEM_LARGE, k, seed=0)
    rec["config_sweep_sequential_s"] = round(time.time() - t0, 3)

    overrides = {key: [c[key] for c in cfgs] for key in tuning.ARMS_SPACE}
    t0 = time.time()
    scan_engine.sweep_arms_configs(trace, PMEM_LARGE, k, overrides)
    rec["config_sweep_batched_cold_s"] = round(time.time() - t0, 3)
    t0 = time.time()
    scan_engine.sweep_arms_configs(trace, PMEM_LARGE, k, overrides)
    rec["config_sweep_batched_warm_s"] = round(time.time() - t0, 3)

    # same sweep with the pure-jnp score path (the Pallas kernel runs in
    # interpret mode off-TPU, which costs extra under batching)
    jnp_cfg = ARMSConfig(use_score_kernel=False)
    scan_engine.sweep_arms_configs(trace, PMEM_LARGE, k, overrides,
                                   base_cfg=jnp_cfg)
    t0 = time.time()
    scan_engine.sweep_arms_configs(trace, PMEM_LARGE, k, overrides,
                                   base_cfg=jnp_cfg)
    rec["config_sweep_batched_warm_jnp_s"] = round(time.time() - t0, 3)

    # --- seed sweep ---
    seeds = list(range(n_seeds))
    t0 = time.time()
    for s in seeds:
        run(ARMSPolicy(), trace, PMEM_LARGE, k, seed=s)
    rec["seed_sweep_sequential_s"] = round(time.time() - t0, 3)
    t0 = time.time()
    scan_engine.sweep_seeds(trace, PMEM_LARGE, k, seeds)
    rec["seed_sweep_batched_cold_s"] = round(time.time() - t0, 3)
    t0 = time.time()
    scan_engine.sweep_seeds(trace, PMEM_LARGE, k, seeds)
    rec["seed_sweep_batched_warm_s"] = round(time.time() - t0, 3)

    sp_cfg = rec["config_sweep_sequential_s"] / \
        rec["config_sweep_batched_warm_s"]
    sp_cfg_jnp = rec["config_sweep_sequential_s"] / \
        rec["config_sweep_batched_warm_jnp_s"]
    sp_seed = rec["seed_sweep_sequential_s"] / \
        rec["seed_sweep_batched_warm_s"]
    rec["config_sweep_speedup"] = round(sp_cfg, 2)
    rec["config_sweep_speedup_jnp"] = round(sp_cfg_jnp, 2)
    rec["seed_sweep_speedup"] = round(sp_seed, 2)
    emit(f"arms_sweep.config.n{n}",
         rec["config_sweep_batched_warm_s"] * 1e6,
         f"seq={rec['config_sweep_sequential_s']}s;"
         f"speedup={sp_cfg:.2f}x;jnp_path={sp_cfg_jnp:.2f}x")
    emit(f"arms_sweep.seeds.n{n}",
         rec["seed_sweep_batched_warm_s"] * 1e6,
         f"seq={rec['seed_sweep_sequential_s']}s;speedup={sp_seed:.2f}x")
    # conservative CI gate (the recorded BENCH_tuning.json documents the
    # full before/after including the pre-PR per-interval-sync baseline,
    # which is what the >=5x acceptance figure is measured against)
    claim("batched ARMS sweep beats sequential numpy loop",
          f"{max(sp_cfg, sp_cfg_jnp):.2f}x", ">= 2x (5x vs pre-PR baseline)",
          max(sp_cfg, sp_cfg_jnp) >= 2.0)
    return rec


# --------------------------------------------------------- §5/§6 overheads
def bench_overheads():
    """ARMS controller cost per policy interval + metadata bytes/page."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import ARMSConfig, arms_step, init_state

    for n in (4096, 65536, 1 << 20):
        cfg = ARMSConfig()
        st = init_state(n, cfg)
        counts = jnp.ones((n,))
        st, _ = arms_step(st, counts, 0.5, 0.5, cfg=cfg, k=n // 8)  # compile
        jax.block_until_ready(st.score)
        t0 = time.time()
        iters = 20
        for _ in range(iters):
            st, _ = arms_step(st, counts, 0.5, 0.5, cfg=cfg, k=n // 8)
        jax.block_until_ready(st.score)
        us = (time.time() - t0) / iters * 1e6
        emit(f"overheads.controller.n{n}", us,
             f"us_per_page={us / n:.4f}")
    # metadata bytes/page: 2 EWMAs + 2 scores (f32) + hot_age (i32) + tier
    emit("overheads.metadata", 0, "bytes_per_page=21 (paper: ~20)")
