"""End-to-end driver #2: train an LM for a few hundred steps.

Uses the production Trainer (checkpointing, watchdog, optimizer) on a
reduced config so it runs on CPU in minutes; pass --full on real
hardware.  Loss must drop well below ln(vocab) on the synthetic motif
dataset.

Run:  PYTHONPATH=src python examples/train_lm.py --arch olmo-1b --steps 300
"""
import argparse

from repro import compile_cache
from repro.configs import get_arch, reduced
from repro.launch.train import Trainer


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    spec = get_arch(args.arch)
    cfg = spec.model if args.full else reduced(spec.model)
    cfg = cfg.replace(max_seq=max(cfg.max_seq, 128))
    tr = Trainer(cfg, optimizer=spec.optimizer, seq_len=128, global_batch=8,
                 ckpt_dir=args.ckpt_dir, peak_lr=3e-3)
    if tr.maybe_restore():
        print(f"resumed from step {tr.step}")
    hist = tr.train(args.steps, log_every=25)
    start, end = hist["loss"][0], hist["loss"][-1]
    print(f"\nloss {start:.3f} -> {end:.3f} over {args.steps} steps "
          f"({'LEARNING' if end < start - 0.3 else 'check config'})")


if __name__ == "__main__":
    main()
