"""Serving launcher with a policy-tiered paged KV cache (deliverable b).

Runs batched greedy decoding for a (reduced by default) architecture with
the attention KV cache paged across fast/slow tiers under ANY registered
placement policy (``--policy``, every family in
``experiment.POLICY_REGISTRY``), and reports throughput plus the SAME
slowdown/thrash telemetry as the robustness leaderboard
(benchmarks/bench_robustness.py): modeled tiered-vs-all-fast wall ratio,
wasteful-migration fraction, promotions/demotions.

Telemetry accumulates in a device-side carry (the TieredPool) and syncs
ONCE after the decode loop; ``--sync-telemetry`` restores the legacy
per-token host-sync path (kept for the before/after tok/s comparison in
benchmarks/bench_serving.py).  ``--capture`` saves the per-interval
paged-KV attention-mass stream as a replayable ``TraceWorkload``
(simulator/traces.py) — the capture->fit pipeline that turns serving
traffic into sweep/tuning/leaderboard lanes.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
      --tokens 96 --batch 4 --policy memtis
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import model as M
from repro.tiering import paged_kv as PK
from repro.tiering import tiered_pool as TP
from repro.utils.compilation import count_compiles, setup_compile_cache


@dataclasses.dataclass
class ServeReport:
    """One serving run's throughput + leaderboard-style telemetry."""
    arch: str
    policy: str
    tok_s: float
    promotions: int
    demotions: int
    wasteful: int
    thrash: float            # wasteful / migrations (leaderboard metric)
    slowdown: float          # modeled tiered wall / all-fast wall
    fast_mass: np.ndarray    # [T] fast-tier attention-mass share per step
    telemetry: dict          # full tiered_pool.telemetry record
    trace: object = None     # TraceWorkload when capture=True
    kv: object = None        # final PagedKV (tests inspect the pools)
    tokens: np.ndarray = None       # [batch, n_tokens] generated ids
    last_logits: np.ndarray = None  # [batch, vocab] f32 of the last step
    decode_compiles: int = 0  # executables built after the first token


#: the model's decode step, compiled once per (config, shapes): the layer
#: scan inside it is traced again on every call of the plain function.
_decode_step = jax.jit(M.decode_step, static_argnums=(4,))


def serve(arch: str, n_tokens: int, batch: int, full: bool = False,
          page_size: int = 16, fast_frac: float = 0.25, seed: int = 0,
          policy: str = "arms", machine: str = TP.DEFAULT_MACHINE,
          sync_telemetry: bool = False, capture: bool = False,
          quiet: bool = False) -> ServeReport:
    cfg = registry.get_arch(arch)
    if not full:
        cfg = registry.reduced(cfg)
    if cfg.family in ("ssm",):
        raise SystemExit(f"{arch}: attention-free arch — KV tiering "
                         "inapplicable (DESIGN.md §5); use plain decode.")
    rng = jax.random.PRNGKey(seed)
    params = M.init_params(rng, cfg)

    n_pages = max(4, -(-n_tokens // page_size))
    pk_cfg = PK.PagedKVConfig(
        page_size=page_size, n_pages=n_pages,
        fast_pages=max(1, int(n_pages * fast_frac)), policy_every=4,
        machine=machine)

    # one tiered paged-KV per attention layer is the production layout;
    # for the driver we tier layer 0 and use the model decode for the rest
    # of the stack (keeps the example readable).
    kv = PK.init_paged_kv(pk_cfg, batch, cfg.n_kv_heads, cfg.head_dim,
                          dtype=jnp.float32, policy=policy)
    cache = M.init_cache(cfg, batch, n_pages * page_size)

    token = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.time()
    promotions_sync = 0
    shares = []    # device scalars; one transfer after the loop
    masses = []    # device [n_pages] access rows (trace capture)
    # long-EWMA attention mass (the legacy fast-mass telemetry): the
    # share of DECAYED mass resident fast, not just this step's slice.
    mass_ewma = jnp.zeros((n_pages,), jnp.float32)
    tokens = []    # device [batch, 1] ids; one transfer after the loop
    after_first = 0
    with count_compiles() as compiles:
        for t in range(n_tokens):
            if t == 1:
                after_first = compiles.count
            logits, cache = _decode_step(params, token, cache,
                                         jnp.int32(t), cfg)
            token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            tokens.append(token)
            # drive the tiered layer with this step's q/k/v telemetry; K
            # and V are DISTINCT streams (the pools must be allowed to
            # diverge).
            q = jax.random.normal(jax.random.fold_in(rng, 3 * t),
                                  (batch, cfg.n_heads, cfg.head_dim))
            k_new = jax.random.normal(jax.random.fold_in(rng, 3 * t + 1),
                                      (batch, cfg.n_kv_heads, cfg.head_dim))
            v_new = jax.random.normal(jax.random.fold_in(rng, 3 * t + 2),
                                      (batch, cfg.n_kv_heads, cfg.head_dim))
            _, kv, plan = PK.serve_decode_step(kv, q, k_new, v_new,
                                               jnp.int32(t), pk_cfg)
            mass_ewma = 0.98 * mass_ewma + plan.access
            shares.append((mass_ewma * kv.pool.in_fast).sum()
                          / jnp.maximum(mass_ewma.sum(), 1e-9))
            if capture:
                masses.append(plan.access)
            if sync_telemetry:
                # legacy per-token host-sync path (perf comparison only)
                promotions_sync += int(plan.count)
                float(plan.fast_share)
    decode_compiles = compiles.count - after_first if n_tokens > 1 else 0
    jax.block_until_ready(kv.pool)
    dt = time.time() - t0
    tok_s = n_tokens * batch / dt

    tele = TP.telemetry(kv.pool)                   # the one host sync
    fast_mass = np.asarray(jnp.stack(shares))
    trace = None
    if capture:
        from repro.simulator import traces
        trace = traces.capture_from_steps(
            np.asarray(jnp.stack(masses)), group=pk_cfg.policy_every,
            label=f"{arch}-kv")
    if sync_telemetry:
        assert promotions_sync == tele["promotions"]
    rep = ServeReport(
        arch=arch, policy=str(policy), tok_s=tok_s,
        promotions=tele["promotions"], demotions=tele["demotions"],
        wasteful=tele["wasteful"], thrash=tele["thrash"],
        slowdown=tele["slowdown"], fast_mass=fast_mass,
        telemetry=tele, trace=trace, kv=kv,
        tokens=np.asarray(jnp.concatenate(tokens, axis=1)),
        last_logits=np.asarray(logits[:, -1], np.float32),
        decode_compiles=decode_compiles)
    if not quiet:
        print(f"[serve] {arch}/{rep.policy}: {n_tokens} steps x {batch} "
              f"seqs = {tok_s:,.0f} tok/s"
              + (" (sync telemetry)" if sync_telemetry else ""))
        print(f"[serve] tiering: {rep.promotions} promotions / "
              f"{rep.demotions} demotions, thrash={rep.thrash:.3f}, "
              f"modeled slowdown vs all-fast = {rep.slowdown:.2f}x, "
              f"fast-tier attention-mass share (end) = "
              f"{fast_mass[-1]:.2%}")
    return rep


def main():
    from repro.simulator.experiment import POLICY_REGISTRY
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--policy", default="arms",
                    choices=sorted(POLICY_REGISTRY))
    ap.add_argument("--machine", default=TP.DEFAULT_MACHINE)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync-telemetry", action="store_true",
                    help="legacy per-token host-sync telemetry (slow)")
    ap.add_argument("--capture", default=None, metavar="PATH",
                    help="save the paged-KV access trace as an .npz "
                         "TraceWorkload")
    args = ap.parse_args()
    setup_compile_cache()
    rep = serve(args.arch, args.tokens, args.batch, full=args.full,
                policy=args.policy, machine=args.machine, seed=args.seed,
                sync_telemetry=args.sync_telemetry,
                capture=args.capture is not None)
    if args.capture:
        rep.trace.save(args.capture)
        print(f"[serve] trace [{rep.trace.T}x{rep.trace.n}] -> "
              f"{args.capture}")


if __name__ == "__main__":
    main()
