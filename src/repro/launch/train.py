"""Training launcher with restart supervision (fault tolerance).

    python -m repro.launch.train --arch llama3.2-1b --smoke --steps 50 \
        --ckpt-dir /tmp/ckpt --resume auto --max-restarts 2

``--max-restarts N`` supervises the training call: on an exception the
launcher reloads the latest checkpoint and continues (the crash-restart
path exercised by tests/test_train_loop.py).  ``--mesh dxm`` picks the mesh
(e.g. ``1x1`` for local smoke, ``16x16`` for the production pod).
"""
from __future__ import annotations

import argparse
import logging
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--quant", default="none",
                    choices=["none", "w8", "w12", "mixed"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--max-restarts", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tuning-table", default=None,
                    help="repro.tune table JSON (DESIGN.md §10)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.train import optim
    from repro.train.loop import TrainConfig, run_training

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke, quant=args.quant)
    shape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(shape)
    tc = TrainConfig(
        steps=args.steps,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir if args.resume == "auto" else None,
        optimizer=optim.AdamWConfig(lr=args.lr, total_steps=args.steps),
        tuning_table=args.tuning_table,
    )
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, frontend=cfg.frontend,
        frontend_dim=cfg.frontend_dim, frontend_tokens=cfg.frontend_tokens,
        encdec=cfg.is_encdec)

    attempts = 0
    while True:
        try:
            result = run_training(cfg, mesh, tc, data_cfg)
            break
        except Exception as e:  # supervised restart
            attempts += 1
            logging.error("training failed (%s); restart %d/%d",
                          e, attempts, args.max_restarts)
            if attempts > args.max_restarts:
                raise
    final_loss = list(result.losses.values())[-1] if result.losses else None
    print(f"done: step={result.final_step} loss={final_loss} "
          f"resumed_from={result.restored_from} "
          f"stragglers={result.straggler_events}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
