import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every assigned (architecture x input shape) cell, lower + compile the
appropriate step function on the production mesh — 16x16 (single-pod) and
2x16x16 (multi-pod) — and record memory_analysis / cost_analysis /
collective bytes as JSON artifacts consumed by the roofline report.

The two XLA_FLAGS lines above MUST run before any other import: jax locks
the device count at first initialization.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402
from pathlib import Path  # noqa: E402

import jax          # noqa: E402

from repro import roofline  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.base import shape_applicable  # noqa: E402
from repro.launch import sharding, specs, steps  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.optim import adamw  # noqa: E402

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# grad-accumulation per train cell: keeps per-microbatch tokens/device ~4k.
GRAD_ACCUM = 8


def _sanitize(d):
    out = {}
    for k, v in (d or {}).items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            out[k] = str(v)
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               opt_overrides=None, sharding_overrides=None,
               dtype: str = "float32", force: bool = False):
    """Lower + compile one cell; returns the result record (dict).

    Roofline artifacts are lowered with a UNIFORM f32 model dtype: the CPU
    backend lowers bf16 dots via f32 with whole-buffer convert churn that a
    TPU lowering does not have, polluting byte accounting.  An f32-uniform
    module is structurally identical to the TPU bf16 module; the reported
    bf16-target memory term is bytes * 0.5 (documented in EXPERIMENTS.md).
    """
    import dataclasses
    cfg = registry.get_arch(arch)
    if dtype and cfg.dtype != dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    shape = registry.get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok and not force:
        return {"arch": cfg.name, "shape": shape.name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        params_sds = specs.param_specs(cfg)
        p_shard = sharding.param_shardings(params_sds, mesh)

        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig()
            opt_sds = specs.opt_specs(cfg, opt_cfg, params_sds)
            o_shard = sharding.param_shardings(
                jax.tree.map(lambda x: x, opt_sds), mesh)
            batch_sds = specs.batch_specs(cfg, shape)
            b_shard = sharding.batch_sharding(mesh, batch_sds)
            step = steps.make_train_step(cfg, opt_cfg,
                                         grad_accum=GRAD_ACCUM, remat=True,
                                         mesh=mesh)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None))
            lowered = jitted.lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_sds = specs.batch_specs(cfg, shape, with_labels=False)
            b_shard = sharding.batch_sharding(mesh, batch_sds)
            step = steps.make_prefill_step(cfg, mesh=mesh)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_sds, batch_sds)
        else:  # decode
            token, cache, pos = specs.decode_specs(cfg, shape)
            t_shard = sharding.batch_sharding(mesh, token)
            c_shard = sharding.cache_sharding(mesh, cache)
            p_shard = sharding.param_shardings(params_sds, mesh,
                                               serve=True)
            step = steps.make_serve_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, t_shard, c_shard,
                              sharding.replicated(mesh)),
                out_shardings=(t_shard, c_shard),
                donate_argnums=(2,))   # serving consumes the old cache
            lowered = jitted.lower(params_sds, token, cache, pos)

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = _sanitize(compiled.cost_analysis())
    hlo = compiled.as_text()
    chips = 512 if multi_pod else 256
    # scan-aware per-device cost model (XLA cost_analysis counts while
    # bodies once; see roofline.analyze_hlo) -> globals = per-device * chips
    analysis = roofline.analyze_hlo(hlo)
    coll = {k: int(v) for k, v in analysis["collectives"].items()}
    terms = roofline.roofline(
        {"flops": analysis["flops"] * chips,
         "bytes accessed": analysis["bytes"] * chips},
        coll["_total"] * chips, chips)
    mflops = roofline.model_flops(cfg, shape)

    rec = {
        "arch": cfg.name, "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "chips": chips,
        "memory_analysis": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)},
        "cost_analysis": {k: cost[k] for k in ("flops", "bytes accessed")
                          if k in cost},
        "collectives": coll,
        "roofline": terms.row(),
        "model_flops": mflops,
        "useful_flops_ratio": (mflops / terms.flops) if terms.flops else None,
        "params": int(jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda x: 1.0 * x.size, params_sds))),
    }
    return rec


def run_cells(cells, meshes, out_dir: Path, skip_existing: bool = False,
              args_ns=None):
    out_dir.mkdir(parents=True, exist_ok=True)
    results = []
    for arch, shape_name in cells:
        for mesh_name in meshes:
            multi = mesh_name == "pod2"
            tag = f"{arch}__{shape_name}__{mesh_name}"
            path = out_dir / f"{tag}.json"
            if skip_existing and path.exists():
                rec = json.loads(path.read_text())
                if rec.get("status") in ("ok", "skipped"):
                    results.append(rec)
                    print(f"[dryrun] {tag}: cached {rec['status']}",
                          flush=True)
                    continue
            try:
                rec = lower_cell(arch, shape_name, multi,
                                 force=getattr(args_ns, "force", False))
            except Exception as e:   # a failure here is a sharding bug
                rec = {"arch": arch, "shape": shape_name,
                       "mesh": mesh_name, "status": "FAILED",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
            path.write_text(json.dumps(rec, indent=2))
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f" compile={rec['compile_s']}s"
                         f" dom={r['dominant']}"
                         f" comp={r['compute_s']:.3e}s"
                         f" mem={r['memory_s']:.3e}s"
                         f" coll={r['collective_s']:.3e}s")
            print(f"[dryrun] {tag}: {status}{extra}", flush=True)
            results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="lower a cell the assignment rules would skip "
                         "(extra, non-assigned artifacts)")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a.name, s.name) for a, s, _ok, _why in registry.all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = run_cells(cells, meshes, Path(args.out),
                        skip_existing=args.skip_existing, args_ns=args)
    failed = [r for r in results if r["status"] == "FAILED"]
    print(f"[dryrun] done: {len(results)} cells, {len(failed)} failed")
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
