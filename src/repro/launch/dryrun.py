import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape) cell against the
production mesh, on 512 placeholder host devices.

The two lines above MUST precede any other import (jax locks the device
count at first init) — do not move them.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per cell it writes JSON with: compile ok, memory_analysis (per-device bytes),
cost_analysis (FLOPs / bytes accessed), and collective-bytes parsed from the
post-SPMD HLO — everything §Roofline consumes.
"""
import argparse
import json
import re
import sys
import time
import traceback

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (v5e pod).  Multi-pod: 2 pods x 256.
    Only the dry-run builds it, on placeholder host devices."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def _compile_bundle(mesh, bundle):
    import jax
    with compat.set_mesh(mesh):
        jitted = jax.jit(bundle.fn,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings)
        lowered = jitted.lower(*bundle.args)
        compiled = lowered.compile()
    return compiled


def _measure(compiled) -> dict:
    from repro.roofline import analysis
    cost = compat.cost_analysis(compiled)
    return {
        "memory": analysis.memory_dict(compiled.memory_analysis()),
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and
                 ("flops" in k or "bytes" in k or
                  "utilization" in k.lower() or k.startswith("optimal"))},
        "collectives": analysis.collective_bytes(compiled),
    }


def _fit_layers(arch_id, shape, mesh, record):
    """XLA's cost_analysis counts a scan body ONCE regardless of trip count
    (verified in tests/test_roofline.py).  For LM cells we therefore compile
    two small *fully-unrolled* variants (L0, L0+1 layers) and linearly
    extrapolate flops / bytes / collective bytes to the real depth; memory
    comes from the scanned artifact (that's the real residency behaviour)."""
    import dataclasses as dc
    import jax
    from repro.configs import registry
    from repro.launch import cells as cells_mod

    spec = registry.get(arch_id)
    if spec.family != "lm":
        return None                       # non-LM cells have no layer scan
    cfg = spec.config
    n_dense = cfg.n_layers - cfg.n_moe_layers
    # vary the dominant (scanned) stack; with a mixed dense+MoE model the
    # dense prefix is held at its exact depth and unrolled into the constant
    base = n_dense + 1 if (cfg.moe is not None and n_dense) else 1
    points = {}
    for ln in (base, base + 1):
        small = dc.replace(cfg, n_layers=ln, unroll=True, remat=False,
                           mtp_depth=0)
        cell = registry.cell_by_name(spec, shape)
        from repro.models import common as cm_mod
        mi = cm_mod.MeshInfo.from_mesh(mesh)
        bundle = cells_mod._lm_cell(arch_id, small, cell, mesh, mi)
        compiled = _compile_bundle(mesh, bundle)
        points[ln] = _measure(compiled)

    lo, hi = points[base], points[base + 1]

    def fit(get, n_extra):
        a, b = get(lo), get(hi)
        per_layer = b - a
        return a + per_layer * n_extra, per_layer

    n_extra = (cfg.n_layers - base)       # layers beyond the `base` compile
    fitted = {}
    for key in ("flops", "bytes accessed"):
        tot, per = fit(lambda p, k=key: p["cost"].get(k, 0.0), n_extra)
        fitted[key] = tot
        fitted[key + "_per_layer"] = per
    coll_tot, coll_per = fit(
        lambda p: p["collectives"].get("total", 0.0), n_extra)
    fitted["collective_total"] = coll_tot
    fitted["collective_per_layer"] = coll_per
    fitted["fit_base_layers"] = base
    fitted["mtp_excluded"] = cfg.mtp_depth > 0
    return fitted


def run_cell(arch_id: str, shape: str, multi_pod: bool, out_dir: str,
             variant: str = "baseline", force: bool = False,
             fit_layers: bool = True) -> dict:
    import jax
    from repro.launch import cells as cells_mod

    tag = f"{arch_id}__{shape}__{'pod2' if multi_pod else 'pod1'}"
    if variant != "baseline":
        tag += f"__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    record = {"arch": arch_id, "shape": shape,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "variant": variant, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        bundle = cells_mod.build_cell(arch_id, shape, mesh, variant=variant)
        compiled = _compile_bundle(mesh, bundle)
        t_compile = time.time()
        record.update(ok=True, compile_s=round(t_compile - t0, 2),
                      n_devices=mesh.devices.size, meta=bundle.meta,
                      **_measure(compiled))
        print(compiled.memory_analysis())
        if fit_layers and not multi_pod:    # roofline table is single-pod
            try:
                record["layer_fit"] = _fit_layers(arch_id, shape, mesh,
                                                  record)
            except Exception as e:   # noqa: BLE001
                record["layer_fit_error"] = f"{type(e).__name__}: {e}"
    except Exception as e:           # noqa: BLE001 — record the failure
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["wall_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    status = "OK" if record["ok"] else "FAIL"
    print(f"[{status}] {tag} wall={record['wall_s']}s", flush=True)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    from repro.configs import registry
    jobs = []
    if args.all:
        for arch in registry.all_arch_ids():
            for cell in registry.get(arch).cells:
                meshes = ([False, True] if args.both_meshes
                          else [args.multi_pod])
                for mp in meshes:
                    jobs.append((arch, cell.name, mp))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        jobs = [(args.arch, args.shape, args.multi_pod)]

    failures = 0
    for arch, shape, mp in jobs:
        rec = run_cell(arch, shape, mp, args.out, variant=args.variant,
                       force=args.force)
        failures += 0 if rec["ok"] else 1
    print(f"dry-run: {len(jobs) - failures}/{len(jobs)} cells compiled")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
