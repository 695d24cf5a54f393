"""Checkpointing + fault-tolerance behaviors."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import PipelineConfig, SyntheticTokenPipeline
from repro.ft.loop import FaultTolerantLoop, LoopConfig
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.train.step import make_train_step


def _tiny_setup(tmp_path, steps=30, ckpt_every=10):
    cfg = configs.get_arch("qwen1.5-4b").smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    pipe = SyntheticTokenPipeline(PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=32, global_batch=4))
    step = jax.jit(make_train_step(cfg, opt=AdamWConfig(lr=1e-3),
                                   ce_chunk=16, moe_dense=True))
    ckpt = CheckpointManager(tmp_path / "ckpt", keep=2, async_save=False)
    loop = FaultTolerantLoop(
        LoopConfig(total_steps=steps, ckpt_every=ckpt_every), ckpt, step, pipe)
    return cfg, params, opt, pipe, step, ckpt, loop


def test_roundtrip_identity(tmp_path):
    cfg = configs.get_arch("rwkv6-1.6b").smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(7, {"params": params}, meta={"note": "x"})
    restored, manifest = m.restore({"params": params})
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))


def test_atomic_publish_never_partial(tmp_path):
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(1, {"x": jnp.arange(5)})
    # a later tmp dir (simulated crash mid-save) must not be visible
    (tmp_path / "step_00000002.tmp").mkdir()
    assert m.latest_step() == 1
    t, _ = m.restore({"x": jnp.zeros(5, jnp.int32)})
    assert bool(jnp.all(t["x"] == jnp.arange(5)))


def test_gc_keeps_last_n(tmp_path):
    m = CheckpointManager(tmp_path, keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        m.save(s, {"x": jnp.ones(2) * s})
    assert sorted(m.all_steps()) == [3, 4]


def test_resume_is_deterministic(tmp_path):
    """Train 30 straight vs train 30 with a restart at 20: identical params
    (checkpoint + seekable data => exact resume)."""
    cfg, params, opt, pipe, step, _, _ = _tiny_setup(tmp_path)

    def run(p, o, lo, hi):
        for s in range(lo, hi):
            p, o, _ = step(p, o, pipe.batch(s), jnp.int32(s))
        return p, o

    pA, oA = run(params, opt, 0, 30)

    pB, oB = run(params, opt, 0, 20)
    m = CheckpointManager(tmp_path / "c2", async_save=False)
    m.save(19, {"params": pB, "opt": oB})
    restored, man = m.restore({"params": pB, "opt": oB})
    pC, oC = run(restored["params"], restored["opt"], man["step"] + 1, 30)

    for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pC)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retry_on_injected_failure(tmp_path):
    cfg, params, opt, pipe, step, ckpt, loop = _tiny_setup(
        tmp_path, steps=25, ckpt_every=5)
    fails = {12}

    def injector(s):
        if s in fails:
            fails.discard(s)
            return True
        return False

    state, log = loop.run(params, opt, fail_injector=injector)
    assert log[-1]["step"] == 24
    assert all(np.isfinite(r["loss"]) for r in log)


def test_elastic_restore_new_mesh(tmp_path):
    """Save unsharded, restore with explicit shardings on a (1,1) mesh —
    the elastic-rescale path (mesh shape independent of the saved one)."""
    pytest.importorskip("repro.dist.cells")
    cfg = configs.get_arch("glm4-9b").smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(3, {"params": params})

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    from repro.dist.cells import _param_shardings
    from repro.dist import sharding as SH
    shards = _param_shardings(cfg, mesh, SH.PARAM_RULES)
    restored, _ = m.restore({"params": params},
                            shardings={"params": shards})
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        assert bool(jnp.all(a == b))


def test_straggler_detection(tmp_path):
    cfg, params, opt, pipe, step, ckpt, _ = _tiny_setup(tmp_path, steps=15)
    seen = []
    import time

    def slow_step(p, o, b, s):
        if int(s) == 10:
            time.sleep(0.5)
        return step(p, o, b, s)

    loop = FaultTolerantLoop(
        LoopConfig(total_steps=15, ckpt_every=100, straggler_factor=3.0),
        ckpt, slow_step, pipe,
        on_straggler=lambda s, dt, ema: seen.append(s))
    loop.run(params, opt)
    assert 10 in seen


def test_engine_scan_carry_roundtrip_bitwise(tmp_path):
    """The neuromorphic engine's scan carry (LIF + plant + LEARN state)
    saved mid-run, restored into a fresh tree, and continued must be
    bitwise identical to the uninterrupted run — the property the
    serving tier's session checkpoint/restore is built on."""
    from repro.chip.chip import ChipSim
    from repro.chip.compile import compile as compile_graph
    from repro.learn.adaptive import adaptive_control_graph

    g = adaptive_control_graph(n_channels=2, n_neurons=24, n_ticks=64)
    init, step, params = ChipSim(compile_graph(g)).make_stepper()

    def run(st, t0, n):
        return jax.lax.scan(lambda s, t: step(params, s, t), st,
                            t0 + jnp.arange(n))
    runj = jax.jit(run, static_argnums=2)

    ref_st, ref_recs = runj(init, 0, 32)

    st16, recs_a = runj(init, 0, 16)
    assert "learn" in st16                      # the plastic subtree rides
    m = CheckpointManager(tmp_path, async_save=False)
    m.save(16, st16, meta={"ticks_done": 16})
    restored, manifest = m.restore(st16)
    assert manifest["step"] == 16
    assert manifest["meta"]["ticks_done"] == 16
    st32, recs_b = runj(restored, 16, 16)

    for a, b in zip(jax.tree.leaves(ref_st), jax.tree.leaves(st32)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    for k in ("u", "track_err", "dec_norm", "n_spk"):
        full = np.concatenate([np.asarray(recs_a[k]), np.asarray(recs_b[k])])
        np.testing.assert_array_equal(full, np.asarray(ref_recs[k]))
