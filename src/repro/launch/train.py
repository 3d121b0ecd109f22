"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 100 --batch 8 --seq 512 [--reduced] [--ckpt dir]

On this CPU container use --reduced (the smoke-scale variant); on a real
TPU slice the same entry point drives the full config on the production
mesh (--mesh prod).
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced smoke-scale variant")
    ap.add_argument("--mesh", choices=["none", "prod", "prod-multipod"],
                    default="none")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.layers import Dist, NO_DIST
    from repro.training.loop import train

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dist = NO_DIST
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "prod-multipod")
        dist = Dist(mesh=mesh)

    res = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                dist=dist, seed=args.seed, checkpoint_dir=args.ckpt,
                checkpoint_every=args.ckpt_every, resume=args.resume)
    print(f"done: {res.steps} steps, final loss {res.losses[-1]:.4f}, "
          f"{res.tokens_per_s:.0f} tok/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
