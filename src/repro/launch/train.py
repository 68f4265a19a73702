"""Production launcher: RL training entry point.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
        --dry-run  # lower + compile the train step on the target mesh

``--smoke`` trains the reduced config on a synthetic task; ``--dry-run``
compiles the full config's train step for the production mesh. Full
width does not train on one chip: AdamW keeps ~16 bytes per parameter
(~24 GB for Qwen2-1.5B against 16 GB on a TPU v5e), and the learner is
not sharded. Serving runs at full width (``repro.launch.serve``).
"""

from __future__ import annotations

import argparse
import json


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced variant on CPU")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile the full config on the prod mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--no-das", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        # dryrun must own the process (XLA_FLAGS before jax import)
        import os
        import subprocess
        import sys

        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", args.arch, "--shape", "train_4k",
        ]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd))

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    from repro.configs import get_config, smoke_variant
    from repro.core.drafter import DrafterConfig
    from repro.core.spec_engine import EngineConfig
    from repro.data.tasks import PatternTask
    from repro.data.tokenizer import TOKENIZER
    from repro.optim.adamw import AdamWConfig
    from repro.rl.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg).replace(
            vocab_size=TOKENIZER.vocab_size, vocab_pad_multiple=8
        )
    task = PatternTask(n_problems=8, mean_len=12.0, sigma=0.6, max_len=32)
    tcfg = TrainerConfig(
        steps=args.steps, prompts_per_step=4, group_size=2,
        max_new_tokens=32, temperature=0.6, sft_warmup_steps=10,
        optim=AdamWConfig(lr=5e-4, warmup_steps=2),
        engine=EngineConfig(spec_enabled=not args.no_das, max_draft=8,
                            block_buckets=(0, 4, 8), eos_token=1),
        drafter=DrafterConfig(scope="problem+request", min_match=2),
    )
    tr = Trainer(cfg, task, tcfg)
    for h in tr.run():
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                          for k, v in h.items()}))


if __name__ == "__main__":
    main()
