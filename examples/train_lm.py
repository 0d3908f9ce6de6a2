"""End-to-end LM training driver with fault tolerance.

    PYTHONPATH=src python examples/train_lm.py [--arch qwen3-1.7b]

Trains a reduced-config model for a few hundred steps through the
production path — sharded params (host mesh), microbatched grad
accumulation, AdamW + clipping, async atomic checkpointing — then
kills itself mid-run and resumes from the last committed checkpoint,
demonstrating the restart story. Use ``--full`` for the real config
(needs a pod; the dry-run proves the lowering).
"""
import argparse
import shutil
import tempfile

from repro import compile_cache
from repro.checkpoint import latest_step
from repro.launch.train import train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    ckpt = tempfile.mkdtemp(prefix="vxjax_ckpt_")
    try:
        crash_at = args.steps // 2
        print(f"=== phase 1: train to step {crash_at}, then crash")
        try:
            train(args.arch, smoke=not args.full, steps=args.steps,
                  batch=8, seq=64, ckpt_dir=ckpt, ckpt_every=25,
                  fail_at=crash_at, log_every=25)
        except RuntimeError as e:
            print(f"    crashed as planned: {e}")
        print(f"    last committed checkpoint: step {latest_step(ckpt)}")

        print("=== phase 2: restart — resumes from the checkpoint")
        out = train(args.arch, smoke=not args.full, steps=args.steps,
                    batch=8, seq=64, ckpt_dir=ckpt, ckpt_every=25,
                    log_every=25)
        print(f"=== done: {len(out['losses'])} post-resume steps, "
              f"final loss {out['losses'][-1]:.4f} "
              f"({out['wall_s']:.1f}s)")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


if __name__ == "__main__":
    main()
