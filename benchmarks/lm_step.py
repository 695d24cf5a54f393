"""Framework benchmark: LM train/decode step throughput on the smoke
configs (CPU) — exercises the full step machinery end to end."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro import configs
from repro.models import registry as R
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.train.step import make_train_step

ARCHS = ["qwen1.5-4b", "olmoe-1b-7b", "rwkv6-1.6b", "recurrentgemma-2b"]


def main() -> None:
    for arch in ARCHS:
        cfg = configs.get_arch(arch).smoke()
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        B, S = 4, 64
        batch = R.make_dummy_batch(cfg, "train", B, S)
        step = jax.jit(make_train_step(cfg, ce_chunk=32, moe_dense=True))
        us = time_call(step, params, opt, batch, jnp.int32(0), iters=3)
        emit(f"lm_train_step_{arch}", us,
             f"tokens_per_s={B * S / (us / 1e6):.0f};smoke_params="
             f"{cfg.param_count() / 1e6:.1f}M")

        bparams = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        _, caches = T.prefill(cfg, bparams,
                              R.make_dummy_batch(cfg, "prefill", B, 16), 32,
                              moe_dense=True)
        dec = jax.jit(lambda p, c, pos, b: T.decode_step(cfg, p, c, pos, b,
                                                         moe_dense=True))
        db = R.make_dummy_batch(cfg, "decode", B, 1)
        us = time_call(dec, bparams, caches, jnp.int32(16), db, iters=3)
        emit(f"lm_decode_step_{arch}", us,
             f"tokens_per_s={B / (us / 1e6):.0f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
