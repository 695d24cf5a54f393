"""Batched serving with queue-driven (spike-FIFO-style) batch widths.

    PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro import configs
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

enable_compilation_cache()

cfg = configs.get_arch("glm4-9b").smoke()
params = T.init_params(cfg, jax.random.PRNGKey(0))
params = jax.tree.map(
    lambda x: x.astype(jnp.bfloat16)
    if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

eng = ServeEngine(cfg, params, max_seq=64)
rng = np.random.default_rng(0)
for i in range(11):
    eng.submit(Request(rid=i,
                       prompt=rng.integers(0, cfg.vocab_size, 12,
                                           dtype=np.int32),
                       max_new_tokens=8))
stats = eng.run()
print(f"served {stats['tokens']} tokens in {stats['rounds']} rounds")
print(f"queue-DVFS batch widths: {stats['batch_hist']} "
      f"(levels {eng.dvfs.batch_levels}, thresholds {eng.dvfs.thresholds})")
print("deep queue -> wide batch (PL3-like); drained queue -> narrow (PL1)")
