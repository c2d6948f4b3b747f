"""Training entrypoint.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

On a real pod the same entrypoint runs under `jax.distributed.initialize()`
with the production mesh; on this container use --smoke (reduced config,
local devices).  All fault-tolerance machinery (checkpoint/restart, NaN
guards, straggler watchdog, SIGTERM-safe preemption) is active either way.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from ..configs.registry import get_config, smoke_config
from ..data.lm_data import DataConfig
from ..train.loop import TrainLoop
from .compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--kan-ffn", action="store_true",
                    help="swap in the paper's KAN-FFN")
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.kan_ffn:
        cfg = cfg.kan_variant()
    if args.smoke:
        cfg = dataclasses.replace(cfg, microbatch=0)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    loop = TrainLoop(cfg, dcfg, args.ckpt_dir, ckpt_every=args.ckpt_every)
    loop.install_sigterm_handler()
    print(f"arch={cfg.name} devices={jax.device_count()} "
          f"start_step={loop.start_step}")
    hist = loop.run(args.steps)
    if hist:
        print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}; "
              f"stragglers={loop.watchdog.straggler_steps}")


if __name__ == "__main__":
    main()
