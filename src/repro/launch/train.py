"""End-to-end training driver.

``--arch <id>`` selects any assigned architecture; on this CPU container the
smoke (reduced) config trains for real, while full configs are exercised via
the dry-run.  The loop is the full production stack: ProxyStream input
pipeline → fault-tolerant Trainer (async proxy checkpoints, watchdog,
restart) on a named mesh.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 100 --batch 8 --seq 128 --smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

from repro.configs import arch_names, get_config, get_smoke_config
from repro.data.pipeline import (
    DispatchingDataLoader,
    StreamingDataLoader,
    SyntheticCorpus,
)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, rules_for
from repro.models.layers import ModelContext
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m", choices=arch_names(True))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-train-ckpt")
    ap.add_argument("--dispatch-workers", type=int, default=0,
                    help="feed via the shard-dispatching loader (redispatch "
                         "on straggle/death) instead of the plain stream")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default=None, help="write history JSON here")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    ctx = ModelContext(cfg, mesh, rules_for(mesh))

    tc = TrainerConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1)),
        microbatch=args.microbatch,
        ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
    )
    trainer = Trainer(ctx, tc)
    if not args.resume:
        trainer.init_state()

    corpus = SyntheticCorpus(cfg, args.batch, args.seq)
    if args.dispatch_workers > 0:
        loader = DispatchingDataLoader(
            corpus.next_batch, num_steps=args.steps + 8,
            workers=args.dispatch_workers, prefetch=2,
        )
    else:
        loader = StreamingDataLoader(
            corpus.next_batch, num_steps=args.steps + 8, prefetch=2
        )
    t0 = time.perf_counter()
    history = trainer.train(loader, args.steps)
    wall = time.perf_counter() - t0
    loader.stop()

    first = history[0]["loss"] if history else float("nan")
    last = history[-1]["loss"] if history else float("nan")
    print(
        f"[train] {args.arch}{' (smoke)' if args.smoke else ''}: "
        f"{len(history)} steps in {wall:.1f}s; loss {first:.3f} → {last:.3f}; "
        f"stragglers {trainer.watchdog.stragglers}; failures {trainer.failures}"
    )
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": history, "wall_s": wall}, f)
    return 0 if (history and last < first) else 1


if __name__ == "__main__":
    sys.exit(main())
