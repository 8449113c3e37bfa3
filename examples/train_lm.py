"""Train a small LM for a few hundred steps with the fault-tolerant loop
(async checkpointing, auto-resume, straggler watchdog).

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse

from repro import compile_cache
from repro.configs import get_config
from repro.train.loop import TrainLoopConfig, train


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--ckpt", default="/tmp/repro_example_train")
    args = ap.parse_args()
    cfg = get_config(args.arch, smoke=True)
    stats = train(
        cfg,
        TrainLoopConfig(
            total_steps=args.steps,
            checkpoint_every=50,
            checkpoint_dir=args.ckpt,
            seq_len=64,
            global_batch=8,
            learning_rate=3e-3,
            log_every=20,
        ),
    )
    print(
        f"loss {stats['first_loss']:.3f} -> {stats['last_loss']:.3f} "
        f"over {len(stats['losses'])} steps"
    )


if __name__ == "__main__":
    main()
