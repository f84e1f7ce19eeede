"""Training launcher: real-device runs of any arch's train cell.

    PYTHONPATH=src python -m repro.launch.train --arch deepfm \
        [--smoke] [--steps 100] [--ckpt-dir artifacts/ckpt/deepfm]

The mesh spans the devices present.  --smoke runs the reduced config (CPU
tests); without it the launcher runs the full config on those devices.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core import compat
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.data import synthetic, graph_sampler
from repro.launch import cells as cells_mod
from repro.launch import mesh as mesh_mod
from repro.launch.materialize import materialize_bundle
from repro.train import checkpoint as ckpt


def _real_batch(spec, cfg, cell, rng):
    """Synthetic but realistic batches per family (ids zipfian etc.)."""
    if spec.family == "recsys":
        b = synthetic.recsys_batch(rng, cfg, cell.dims["batch"])
        if cfg.arch == "two_tower":
            b.pop("label", None)
        return {k: jnp.asarray(v) for k, v in b.items()}
    if spec.family == "lm":
        return {k: jnp.asarray(v) for k, v in synthetic.lm_batch(
            rng, cell.dims["batch"], cell.dims["seq"], cfg.vocab).items()}
    d = cell.dims
    if cell.kind == "gnn_full":
        g = synthetic.random_graph(rng, d["n_nodes"], d["n_edges"],
                                   d["d_feat"], d["n_classes"])
        return {k: jnp.asarray(v) for k, v in g.items()}
    if cell.kind == "gnn_minibatch":
        g = synthetic.random_graph(rng, d["n_nodes"] if "n_nodes" in d
                                   else 1000, d.get("n_edges", 5000),
                                   d["d_feat"], d["n_classes"])
        csr = graph_sampler.CSRGraph(g["feats"].shape[0], g["edges"])
        seeds = rng.integers(0, g["feats"].shape[0], d["batch_nodes"])
        blk = graph_sampler.sample_block(rng, csr, g["feats"], g["labels"],
                                         seeds, tuple(d["fanouts"]))
        return {k: jnp.asarray(v) for k, v in blk.items()}
    m = synthetic.molecule_batch(rng, d["n_graphs"], d["n_nodes"],
                                 d["n_edges"], d["d_feat"], d["n_classes"])
    return {k: jnp.asarray(v) for k, v in m.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None, help="defaults to train cell")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()
    mesh_mod.enable_compile_cache()

    spec = registry.get(args.arch)
    shape = args.shape or {"lm": "train_4k", "gnn": "minibatch_lg",
                           "recsys": "train_batch"}[spec.family]
    mesh = mesh_mod.make_local_mesh()
    bundle = cells_mod.build_cell(args.arch, shape, mesh, smoke=args.smoke)
    assert bundle.meta.get("has_opt"), f"{shape} is not a train cell"
    cfg = spec.smoke if args.smoke else spec.config
    cell = bundle.cell
    rng = np.random.default_rng(0)

    args_m = list(materialize_bundle(bundle, seed=0))
    params, opt_state, step = args_m[0], args_m[1], jnp.int32(0)
    if args.ckpt_dir and ckpt.exists(args.ckpt_dir):
        params, opt_state, st0, _ = ckpt.restore(
            args.ckpt_dir, params_like=params, opt_like=opt_state)
        step = jnp.int32(st0)
        print(f"resumed at step {st0}")

    fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                 out_shardings=bundle.out_shardings)
    t0 = time.time()
    with compat.set_mesh(mesh):
        for i in range(args.steps):
            batch = _real_batch(spec, cfg, cell, rng)
            params, opt_state, step, metrics = fn(params, opt_state, step,
                                                  batch)
            if (i + 1) % 10 == 0 or i == 0:
                loss = float(metrics.get("loss", 0.0))
                print(f"step {int(step):4d} loss={loss:.4f} "
                      f"({(time.time() - t0) / (i + 1):.2f}s/step)",
                      flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, params=params, opt_state=opt_state,
                          step=int(step), meta={"arch": args.arch},
                          async_save=True)
    print("done")


if __name__ == "__main__":
    main()
