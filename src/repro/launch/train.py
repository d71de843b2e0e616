"""Training launcher with restart-on-failure (fault-tolerant outer loop).

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 50 --ckpt-dir /tmp/ckpt

On a pod fleet this process runs per host (jax.distributed); here the outer
retry loop + checkpoint restore + elastic resharding are the same code the
fleet would run (exercised by tests/test_fault.py and the fleet simulator).
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs.base import SHAPES
from repro.core.program import Program
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.weave import default_weave
from repro.models.registry import ARCHS
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mode", default="lcg", choices=("lcg", "uniform", "memmap"))
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--set", dest="sets", action="append", default=[])
    args = ap.parse_args()
    use_compile_cache()

    overrides = {}
    for kv in args.sets:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = json.loads(v)
        except json.JSONDecodeError:
            overrides[k] = v
    overrides.setdefault("accum_steps", 1)

    program = Program.from_arch(args.arch, kind="train", reduced=args.reduced)
    shape = SHAPES["train_4k"]
    woven = default_weave(program, shape, {}, overrides=overrides)
    pipeline = TokenPipeline(PipelineConfig(
        vocab=program.cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        mode=args.mode,
    ))
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every)

    # fault-tolerant outer loop: any step-level failure restores the latest
    # checkpoint and resumes (bounded retries)
    attempts = 0
    while True:
        trainer = Trainer(woven, pipeline, tcfg)
        try:
            history = trainer.run(args.steps - trainer.step
                                  if trainer.maybe_restore() else args.steps)
            break
        except Exception as e:  # noqa: BLE001 - launcher-level barrier
            attempts += 1
            print(f"step failure ({e!r}); restart {attempts}/{args.max_retries}")
            if attempts > args.max_retries:
                raise
    if history:
        first, last = history[0], history[-1]
        print(f"loss {first.get('loss'):.4f} -> {last.get('loss'):.4f} over "
              f"{len(history)} steps")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
