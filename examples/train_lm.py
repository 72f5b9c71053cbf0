"""End-to-end LM training driver: ~100M-parameter decoder, a few hundred
steps, checkpoints + auto-resume + straggler tracking. This is the
framework path the dry-run lowers at 256/512 chips, running on the local
device set.

  PYTHONPATH=src python examples/train_lm.py            # ~100M, 300 steps
  PYTHONPATH=src python examples/train_lm.py --ci       # small + fast
"""
import argparse

import jax.numpy as jnp

from repro.launch.train import train
from repro.models.arch import ArchCfg


def cfg_100m():
    return ArchCfg(name="repro-100m", family="dense", num_layers=10,
                   d_model=640, num_heads=10, num_kv_heads=5, head_dim=64,
                   d_ff=2560, vocab=16384, act="silu", dtype=jnp.float32)


def cfg_ci():
    return ArchCfg(name="repro-ci", family="dense", num_layers=4,
                   d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                   d_ff=512, vocab=2048, act="silu", dtype=jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ci", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="results/lm_ckpt")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache()

    cfg = cfg_ci() if args.ci else cfg_100m()
    steps = args.steps or (60 if args.ci else 300)
    print(f"[train_lm] {cfg.name}: {cfg.param_count()/1e6:.0f}M params, "
          f"{steps} steps")
    _, losses = train(
        cfg, steps=steps,
        global_batch=4 if args.ci else 8,
        seq_len=64 if args.ci else 256,
        lr=1e-3, ckpt_dir=args.ckpt_dir, ckpt_every=max(steps // 5, 10),
        resume="auto")
    print(f"[train_lm] loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({(1 - losses[-1]/losses[0]):.0%} reduction)")
    assert losses[-1] < losses[0], "training must reduce loss"


if __name__ == "__main__":
    main()
