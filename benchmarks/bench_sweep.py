"""Record the tuning/sweep before-vs-after timings into BENCH_tuning.json.

Three tiers of "before" for the ARMS sweep:
  * ``seed``:    the pre-PR-1 ARMS simulator path — per-interval device
    syncs in ``ARMSPolicy.step`` (``int(policy_every(state.mode))`` +
    ``float(sampling_period(...))`` every simulator interval) and the
    per-interval oracle ``argpartition`` in the engine loop.  Replicated
    here as ``SeedSyncARMSPolicy``/``_seed_engine_run`` so the number stays
    reproducible after the optimized code replaced it.
  * ``sequential``: the post-PR-1 numpy loop (host-cached cadence, hoisted
    oracle) replaying the sweep one simulation at a time.
  * ``batched``: the compiled lax.scan + vmap sweep (scan_engine).

For the tuned-baseline study (``tune_hemem`` — the paper's "Tuned-X"
comparators), the "before" is the pre-functional-protocol path: HeMem as an
imperative numpy object (``SeqNumpyHeMemPolicy`` replica below) replaying
the whole budget sequentially through the reference engine.  The "after"
runs the same budget as ONE lane-batched compiled scan
(``tuning.tune_hemem`` -> ``search.run`` -> ``experiment.sweep``).

Usage: PYTHONPATH=src:. python benchmarks/bench_sweep.py [--out BENCH_tuning.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.baselines.arms_policy import ARMSPolicy
from repro.baselines.base import Policy
from repro.core import policy_every, sampling_period
from repro.core.state import ARMSConfig
from repro.simulator import scan_engine, tuning, workloads
from repro.simulator.engine import run
from repro.simulator.machine import PMEM_LARGE, interval_time
from repro.simulator.sampling import pebs_sample


class SeqNumpyHeMemPolicy(Policy):
    """Pre-PR HeMem: the imperative numpy implementation, verbatim.

    Replicated so the sequential-tuning baseline stays reproducible after
    the functional-protocol rewrite replaced it (the live ``HeMemPolicy``
    now runs the jittable spec even under the numpy engine).
    """

    name = "hemem"
    migration_limit = 12

    def __init__(self, hot_threshold=8.0, cooling_threshold=18.0,
                 migration_period=5, sample_period=10_000.0):
        self.hot_threshold = float(hot_threshold)
        self.cooling_threshold = float(cooling_threshold)
        self.migration_period = int(migration_period)
        self._sample_period = float(sample_period)

    def reset(self, n_pages, k, machine):
        self.n, self.k = n_pages, k
        self.counts = np.zeros(n_pages)
        self.in_fast = np.zeros(n_pages, bool)
        self.first_hot = np.full(n_pages, np.inf)
        self.t = 0

    def sampling_period(self):
        return self._sample_period

    def step(self, observed, slow_bw_frac, app_bw_frac):
        self.t += 1
        self.counts += observed
        if self.counts.max() >= self.cooling_threshold:
            self.counts *= 0.5
        hot = self.counts >= self.hot_threshold
        newly_hot = hot & np.isinf(self.first_hot)
        self.first_hot[newly_hot] = self.t
        self.first_hot[~hot] = np.inf
        if self.t % self.migration_period:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        want = np.flatnonzero(hot & ~self.in_fast)
        want = want[np.argsort(self.first_hot[want], kind="stable")]
        want = want[: self.migration_limit]
        free = self.k - int(self.in_fast.sum())
        need_victims = max(0, len(want) - free)
        cold_in_fast = np.flatnonzero(self.in_fast & ~hot)
        victims = cold_in_fast[np.argsort(self.counts[cold_in_fast],
                                          kind="stable")][:need_victims]
        want = want[: free + len(victims)]
        self.in_fast[victims] = False
        self.in_fast[want] = True
        return want, victims


class SeedSyncARMSPolicy(ARMSPolicy):
    """Pre-PR ARMSPolicy: device->host sync per simulator interval."""

    def sampling_period(self):
        return float(sampling_period(self.state.mode))

    def step(self, observed, slow_bw_frac, app_bw_frac):
        from repro.core import arms_step
        from repro.core.scheduler import observe_migration_cost
        self.t += 1
        self.buf += observed
        every = int(policy_every(self.state.mode))   # per-interval sync
        if self.t % every:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        self.state, plan = arms_step(
            self.state, self.buf / every, float(slow_bw_frac),
            float(app_bw_frac), cfg=self.cfg, k=self.k)
        self.buf[:] = 0.0
        valid = np.asarray(plan.valid)
        promote = np.asarray(plan.promote)[valid]
        demote = np.asarray(plan.demote)[valid]
        demote = demote[demote >= 0]
        if len(promote):
            self.state = observe_migration_cost(
                self.state, self._promo_us, self._demo_us, self.cfg)
        return promote.astype(np.int64), demote.astype(np.int64)

    @property
    def mode(self):
        return int(self.state.mode)


def _seed_engine_run(policy, trace, machine, k, seed=0):
    """Pre-PR engine loop: per-interval oracle argpartition, f64 cost model."""
    T, n = trace.shape
    rng = np.random.default_rng(seed)
    policy.reset(n, k, machine)
    in_fast = np.zeros(n, bool)
    slow_bw_frac, app_bw_frac = 1.0, 0.0
    exec_time = 0.0
    for t in range(T):
        true = trace[t]
        observed = pebs_sample(true, policy.sampling_period(), rng)
        promote, demote = policy.step(observed, slow_bw_frac, app_bw_frac)
        demote = np.asarray(demote, np.int64)
        promote = np.asarray(promote, np.int64)
        demote = demote[in_fast[demote]]
        in_fast[demote] = False
        promote = promote[~in_fast[promote]]
        room = k - int(in_fast.sum())
        promote = promote[:room]
        in_fast[promote] = True
        acc_fast = float(true[in_fast].sum())
        acc_slow = float(true.sum()) - acc_fast
        out = interval_time(machine, acc_fast, acc_slow,
                            len(promote), len(demote))
        exec_time += out.wall_s
        slow_bw_frac = acc_slow / max(acc_fast + acc_slow, 1e-9)
        # consumer-side clamp of the raw utilization ratio (engine.py does
        # the same before the policy sees it).
        app_bw_frac = min(1.0, out.app_bw_frac)
        np.argpartition(true, -k)  # per-interval oracle top-k (seed code)
    return exec_time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_tuning.json")
    ap.add_argument("--budget", type=int, default=24)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--T", type=int, default=512)
    args = ap.parse_args()

    from benchmarks import paper_tables

    n, T, budget = args.n, args.T, args.budget
    k = n // 8
    trace = workloads.make("gups", T=T, n=n)
    cfgs = tuning.sample_arms_configs(budget)

    print(f"[bench_sweep] ARMS config sweep, gups n={n} T={T} k={k} "
          f"budget={budget}", flush=True)
    # warm jit caches so the seed-replica loop isn't charged jax warmup
    _seed_engine_run(SeedSyncARMSPolicy(), trace[:32], PMEM_LARGE, k)
    t0 = time.time()
    for cfg in cfgs:
        _seed_engine_run(SeedSyncARMSPolicy(ARMSConfig(**cfg)), trace,
                         PMEM_LARGE, k)
    seed_style_s = round(time.time() - t0, 3)
    print(f"[bench_sweep] pre-PR (per-interval syncs) sequential: "
          f"{seed_style_s}s", flush=True)

    rec = paper_tables.bench_arms_sweep(budget=budget, n=n, T=T)
    rec["config_sweep_seed_style_sequential_s"] = seed_style_s
    rec["config_sweep_speedup_vs_seed"] = round(
        seed_style_s / rec["config_sweep_batched_warm_s"], 2)
    rec["config_sweep_speedup_vs_seed_jnp"] = round(
        seed_style_s / rec["config_sweep_batched_warm_jnp_s"], 2)

    # --- tuned-baseline sweeps (the paper's tuning study) ---
    # Before: the pre-PR sequential loop — imperative numpy HeMem, one
    # full reference-engine replay per config.  After: the same budget as
    # ONE lane-batched compiled scan (functional-protocol sweep).
    hm_trace = workloads.make("gups", T=300, n=2048)
    hm_k = 256
    hm_cfgs = tuning.sample_configs(budget, seed=0)
    run(SeqNumpyHeMemPolicy(), hm_trace[:32], PMEM_LARGE, hm_k)  # warm
    t0 = time.time()
    for cfg in hm_cfgs:
        run(SeqNumpyHeMemPolicy(**cfg), hm_trace, PMEM_LARGE, hm_k, seed=0)
    rec["tune_hemem_sequential_pre_pr_s"] = round(time.time() - t0, 3)
    print(f"[bench_sweep] tune_hemem pre-PR sequential numpy "
          f"({len(hm_cfgs)} configs): "
          f"{rec['tune_hemem_sequential_pre_pr_s']}s", flush=True)

    t0 = time.time()
    tuning.tune_hemem(hm_trace, PMEM_LARGE, hm_k, budget=budget)
    rec["tune_hemem_batched_cold_s"] = round(time.time() - t0, 3)
    t0 = time.time()
    tuning.tune_hemem(hm_trace, PMEM_LARGE, hm_k, budget=budget)
    rec["tune_hemem_batched_warm_s"] = round(time.time() - t0, 3)
    rec["tune_hemem_lanes"] = scan_engine.last_dispatch["lanes"]
    rec["tune_hemem_speedup_vs_pre_pr"] = round(
        rec["tune_hemem_sequential_pre_pr_s"]
        / rec["tune_hemem_batched_warm_s"], 2)
    print(f"[bench_sweep] tune_hemem batched: "
          f"cold {rec['tune_hemem_batched_cold_s']}s, "
          f"warm {rec['tune_hemem_batched_warm_s']}s "
          f"({rec['tune_hemem_speedup_vs_pre_pr']}x vs pre-PR)", flush=True)

    for fam, tune_fn in (("memtis", tuning.tune_memtis),
                         ("tpp", tuning.tune_tpp)):
        tune_fn(hm_trace, PMEM_LARGE, hm_k, budget=budget)   # compile
        t0 = time.time()
        tune_fn(hm_trace, PMEM_LARGE, hm_k, budget=budget)
        rec[f"tune_{fam}_batched_warm_s"] = round(time.time() - t0, 3)

    out = dict(
        description="Tuning/sweep bench before vs after the compiled "
                    "lax.scan+vmap simulation engine (PR 1) and the "
                    "lane-batched functional-policy sweeps (PR 2)",
        machine="pmem-large model; CI container CPU (2 cores)",
        notes=[
            "'seed_style' replays the pre-PR-1 code path: per-interval "
            "device syncs in ARMSPolicy.step and per-interval oracle "
            "argpartition in the engine loop.",
            "'sequential' is the numpy reference loop, one simulation "
            "per config.",
            "'batched' runs the whole sweep as one compiled lax.scan "
            "batched over configs; 'warm' excludes the one-off compile.",
            "'jnp' uses ARMSConfig(use_score_kernel=False): the fused "
            "Pallas score kernel runs in interpret mode off-TPU, which "
            "costs extra inside batched sweeps.",
            "'tune_hemem_sequential_pre_pr' replays the pre-functional-"
            "protocol tuning study: imperative numpy HeMem through the "
            "reference engine, one config at a time (gups, T=300, "
            "n=2048, k=256); 'tune_hemem_batched' is the same budget as "
            "one lane-batched compiled dispatch.",
        ],
        **rec,
    )
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
