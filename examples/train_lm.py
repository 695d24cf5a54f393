"""End-to-end training driver (deliverable b): train a small LM on the
deterministic synthetic pipeline with the fault-tolerant loop.

    PYTHONPATH=src python examples/train_lm.py              # ~25M, CPU-sized
    PYTHONPATH=src python examples/train_lm.py --hundred-m  # ~100M config

The ~100M variant is the documented "train a ~100M model for a few hundred
steps" driver; the default is mechanically identical but CPU-sized so the
example finishes in minutes in this container.
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import PipelineConfig, SyntheticTokenPipeline
from repro.ft.loop import FaultTolerantLoop, LoopConfig
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step


def small_lm(hundred_m: bool) -> configs.ArchConfig:
    base = configs.get_arch("qwen1.5-4b")
    if hundred_m:
        return dataclasses.replace(
            base, name="lm-100m", num_layers=12, d_model=768, num_heads=12,
            num_kv_heads=12, head_dim=64, d_ff=2048, vocab_size=32_000)
    return dataclasses.replace(
        base, name="lm-8m", num_layers=4, d_model=256, num_heads=4,
        num_kv_heads=4, head_dim=64, d_ff=768, vocab_size=2_048)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = small_lm(args.hundred_m)
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    pipe = SyntheticTokenPipeline(PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch))
    step = jax.jit(make_train_step(
        cfg, opt=AdamWConfig(lr=args.lr), ce_chunk=min(args.seq, 256),
        total_steps=args.steps, warmup_steps=max(args.steps // 20, 10)),
        donate_argnums=(0, 1))
    ckpt = CheckpointManager(f"artifacts/ckpt/{cfg.name}")
    loop = FaultTolerantLoop(
        LoopConfig(total_steps=args.steps, ckpt_every=max(args.steps // 4, 25),
                   install_signal_handlers=True),
        ckpt, step, pipe)
    state, log = loop.run(params, opt)
    for rec in log[:: max(len(log) // 12, 1)]:
        print(f"step {rec['step']:5d} loss {rec['loss']:.4f}")
    first = sum(r["loss"] for r in log[:10]) / 10
    last = sum(r["loss"] for r in log[-10:]) / 10
    print(f"loss: {first:.3f} -> {last:.3f} "
          f"({'DECREASED' if last < first else 'no progress'})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
