"""Training launcher: `--arch <id>` selects any assigned architecture.

CPU-scale run (reduced config of the arch family):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50

Production mesh run (on a real pod; the mesh is built over the devices
present):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --full \
        --mesh-data 16 --mesh-model 16
"""

from __future__ import annotations

import argparse

import repro.configs as configs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.runtime.trainer import TrainConfig, Trainer


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=configs.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="use the FULL config (needs a real pod)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mesh-data", type=int, default=0,
                    help=">0: build a (data, model) mesh over host devices")
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch) if args.full else \
        configs.get_smoke_config(args.arch)
    mesh = None
    if args.mesh_data > 0:
        mesh = make_host_mesh(data=args.mesh_data, model=args.mesh_model)

    tcfg = TrainConfig(
        global_batch=args.batch, seq_len=args.seq, steps=args.steps,
        accum=args.accum, checkpoint_dir=args.ckpt,
        checkpoint_every=max(10, args.steps // 4), lr=args.lr,
        warmup=max(2, args.steps // 10))
    out = Trainer(cfg, tcfg, mesh=mesh).train()
    print(f"[train] {args.arch}: loss {out['losses'][0]:.4f} -> "
          f"{out['final_loss']:.4f} in {out['wall_s']:.1f}s; "
          f"supervisor: {out['supervisor']}")


if __name__ == "__main__":
    main()
