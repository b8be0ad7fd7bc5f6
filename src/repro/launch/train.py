"""Training launcher: real training steps on a reduced config (CPU).

  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data import LMBatchIterator, make_modality_batch
from repro.optim import AdamWConfig, make_schedule
from repro.runtime import make_train_step, train_state_init
from repro.runtime.checkpoint import restore_checkpoint, save_checkpoint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="smoke")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default=None,
                    help="cosine|wsd (default: wsd for minicpm, else cosine)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    cfg = get_config(args.arch, preset=args.preset)
    sched_kind = args.schedule or ("wsd" if "minicpm" in args.arch
                                   else "cosine")
    opt_cfg = AdamWConfig(peak_lr=args.lr)
    schedule = make_schedule(sched_kind, args.lr, args.steps,
                             warmup_steps=max(2, args.steps // 10))
    state = train_state_init(cfg, jax.random.PRNGKey(0), opt_cfg)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, schedule, remat=False),
                      donate_argnums=(0,))

    if cfg.modality == "text":
        batches = iter(LMBatchIterator(cfg, args.batch, args.seq))
        next_batch = lambda i: next(batches)
    else:
        next_batch = lambda i: make_modality_batch(cfg, args.batch,
                                                   args.seq, seed=i)

    t0 = time.time()
    for i in range(args.steps):
        state, metrics = step_fn(state, next_batch(i))
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(metrics['total_loss']):.4f} "
                  f"ce={float(metrics['ce_loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.2f}")
    dt = time.time() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")

    if args.checkpoint:
        save_checkpoint(args.checkpoint, state, step=args.steps)
        restored = restore_checkpoint(args.checkpoint, state)
        print(f"checkpoint round-trip OK -> {args.checkpoint}")


if __name__ == "__main__":
    main()
