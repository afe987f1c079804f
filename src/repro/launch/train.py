"""Production train launcher: mesh + sharded params/opt + train loop.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --steps 100 --mesh 2x2     # host-scale mesh for local validation

On a real pod, --mesh 16x16 (or 2x16x16 with --multi-pod) matches the
dry-run configuration exactly; the data pipeline shards by process index.
"""
import argparse
import os

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    from jax.sharding import NamedSharding
    from ..configs import get_config, get_smoke
    from ..data import (PredicateFilteredDataset, default_quality_filter,
                        make_corpus_metadata)
    from ..models import api
    from ..runtime import StragglerWatchdog, TrainLoop
    from ..ckpt import CheckpointManager
    from ..sharding import named_sharding, use_mesh
    from ..train import make_train_step, opt_state_pspecs
    from .mesh import make_mesh

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    meta = make_corpus_metadata(50_000)
    ds = PredicateFilteredDataset(meta, default_quality_filter(),
                                  seq_len=args.seq, global_batch=args.batch,
                                  vocab=cfg.vocab)
    print("filter:", ds.filter_stats)

    with use_mesh(mesh):
        params = api.init(cfg, jax.random.PRNGKey(0))
        pspec = api.pspecs(cfg, mesh)
        params = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            params, pspec)
        step = make_train_step(cfg, lr=args.lr, params_pspecs=pspec)
        opt_state = step.init_state(params)
        jstep = jax.jit(step, donate_argnums=(0, 1))
        loop = TrainLoop(step_fn=lambda p, s, b: jstep(p, s, b),
                         data_fn=lambda i: {"tokens": jax.numpy.asarray(
                             ds(i)["tokens"])},
                         ckpt=CheckpointManager(args.ckpt_dir, keep=2),
                         ckpt_every=25, watchdog=StragglerWatchdog())
        params, opt_state, hist = loop.run(params, opt_state, args.steps)
    print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}, "
          f"{len(loop.watchdog.flagged_steps)} stragglers")


if __name__ == "__main__":
    main()
