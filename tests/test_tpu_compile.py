"""Ahead-of-time compiles for TPU v5e of the main path's Pallas kernels
and of one engine step, at the shapes the chip runs.

Nothing here runs on a chip: the TPU compiler, installed with JAX,
compiles for a described ``v5e:2x2`` topology.  What it refuses here
(tiling, unsupported ops, scalar stores to VMEM) it would refuse on the
chip.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.chip.chip import ChipSim
from repro.chip.compile import compile as compile_graph
from repro.chip.workloads import SynfireSemantics, synfire_graph
from repro.kernels.event_gather.event_gather import onehot_link_accum_pallas
from repro.kernels.explog.explog import fx_exp_pallas, fx_log_pallas
from repro.kernels.lif.ops import lif_step
from repro.kernels.link_load.link_load import flat_prefix_sum_pallas
from repro.kernels.mac_gemm.ops import mac_gemm


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    an entry written for a described chip cannot be read back without
    one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (kernel, argument shapes/dtypes): the shapes of the 4096-PE synfire
# ring (nnz 1054 -> 16 rows; 3968 links; the event NoC gathers every
# source's padded tree row, 4096 x 31 entries; 250 x 4096 neurons) and
# of the 4x12 hybrid-farm board (nnz 10752 -> 88 rows; a 256-tick x
# 1-dim drive encoded onto 32 neurons)
KERNELS = {
    "prefix_sum_synfire4096": (
        flat_prefix_sum_pallas, [((16, 128), jnp.float32)]),
    "prefix_sum_board4x12": (
        flat_prefix_sum_pallas, [((88, 128), jnp.float32)]),
    "onehot_accum_synfire4096": (
        lambda ids, w: onehot_link_accum_pallas(ids, w, n_links=3968),
        [((4096 * 31,), jnp.int32), ((4096 * 31,), jnp.float32)]),
    "fx_exp": (fx_exp_pallas, [((256, 128), jnp.int32)]),
    "fx_log": (fx_log_pallas, [((256, 128), jnp.int32)]),
    "lif_step_synfire4096": (
        lambda v, r, i: lif_step(v, r, i, alpha=29_650, v_th=32_768,
                                 v_reset=0, ref_ticks=2, v_min=-32_768),
        [((250 * 4096,), jnp.int32)] * 3),
    "mac_gemm_board_drive": (
        mac_gemm, [((256, 1), jnp.int8), ((1, 32), jnp.int8)]),
    "mac_gemm_fleet_segment": (
        mac_gemm, [((64, 1), jnp.int8), ((1, 64), jnp.int8)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def synfire256():
    return compile_graph(synfire_graph(256))


@pytest.mark.parametrize("settings", [
    {},                                                  # auto: event mode
    {"exec_mode": "dense", "noc_mode": "sparse",
     "link_load_impl": "pallas"},
    {"exec_mode": "event", "noc_mode": "sparse"},
])
def test_synfire_engine_step_takes_weights_as_arguments(settings, one_chip,
                                                        synfire256):
    """One tick of a 256-PE synfire ring compiles for v5e, and its
    synaptic masks are arguments of the program, each one read, and not
    code: the program holds no constant of a table's size."""
    net = synfire256.graph.semantics.net
    sp = net.params
    P, NE, N = sp.n_pes, sp.n_exc, sp.neurons_per_core
    n_weights = P * NE * (N + sp.n_inh)
    sim = ChipSim(synfire256, event_impl="pallas" if settings else None)
    init, step, params = sim.make_stepper(**settings)
    assert {(7, P, N), (2, P, NE)} <= {
        a.shape for a in params if a.dtype == jnp.uint32}
    as_sds = lambda x: _sds(x.shape, x.dtype, one_chip)
    compiled = jax.jit(step).lower(
        jax.tree.map(as_sds, params), jax.tree.map(as_sds, init),
        _sds((), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    # jit prunes an argument the program never reads
    assert mem.argument_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                             for a in params)
    assert max(_constant_sizes(compiled.as_text())) < net.m_inh.size // 100
    # under a quarter byte of code per synapse slot
    assert mem.generated_code_size_in_bytes < n_weights // 4
    if settings:
        assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def farm4x12():
    """The benchmark's hybrid farm at cell size: 768 NEF -> event-MAC
    channels of 512 neurons and 64 hidden units on the 4x12 board of
    4x2-QPE chips, the drive tabled over one 97-tick period."""
    from repro.board import compile_for_board
    from repro.chip.workloads import hybrid_farm_board_graph
    return compile_for_board(hybrid_farm_board_graph(
        "4x12", chip="4x2", n_neurons=512, hidden=64, table_ticks=97))


def test_farm_engine_step_compiles_at_cell_size(one_chip, farm4x12):
    """One tick of the 4x12-board farm compiles for v5e with ChipSim's
    defaults (the dense NoC einsum: chip-to-chip links carry up to 192
    channels each), its drive table, MLP weights and NoC incidence
    arguments of the program, not code."""
    sem = farm4x12.graph.semantics
    assert (farm4x12.n_pes, sem.ens.n_neurons, sem.w_eff.shape[1]) == (
        1536, 512, 64)
    init, step, params = ChipSim(farm4x12).make_stepper()
    as_sds = lambda x: _sds(x.shape, x.dtype, one_chip)
    compiled = jax.jit(step).lower(
        jax.tree.map(as_sds, params), jax.tree.map(as_sds, init),
        _sds((), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    param_bytes = sum(p.size * p.dtype.itemsize for p in params)
    assert param_bytes >= (sem.drive_fx.nbytes + sem.w_eff.nbytes
                           + 4 * farm4x12.n_pes * farm4x12.noc.n_links)
    assert mem.argument_size_in_bytes >= param_bytes
    assert mem.generated_code_size_in_bytes < param_bytes // 4
    # the event-MAC GEMM, (768, 512) spikes by (512, 64) weights
    assert re.search(r"f32\[768,64\]\S* (?:convolution|dot|fusion)",
                     compiled.as_text())


def _constant_sizes(hlo: str) -> list:
    """Element counts of the constants an optimised module holds."""
    return [int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"= \w+\[([\d,]*)\]\S* constant\(", hlo)]


def _materialised(hlo: str) -> set:
    """Shapes (``s32[256,200,250]``) of the arrays an optimised module
    writes to memory: results of instructions outside fused
    computations, whose intermediates live in registers only."""
    fused = set(re.findall(r" fusion\(.*calls=(%[\w.\-]+)", hlo))
    shapes, inside = set(), False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and line.endswith("{"):
            inside = head.group(1) in fused
        elif not inside:
            shapes.update(re.findall(r"^\s+(?:ROOT )?%\S+ = (\w+\[[\d,]*\])",
                                     line))
    return shapes


def _compile_scan(program, exec_mode, sharding):
    """The engine's 4-tick scan over ``program``, compiled for the
    described chip; returns the stepper's params and the executable."""
    init, step, params = ChipSim(program).make_stepper(exec_mode=exec_mode)

    def scan(params, init):
        return jax.lax.scan(lambda s, t: step(params, s, t), init,
                            jnp.arange(4))[1]
    as_sds = lambda x: _sds(x.shape, x.dtype, sharding)
    return params, jax.jit(scan).lower(jax.tree.map(as_sds, params),
                                       jax.tree.map(as_sds, init)).compile()


def _tiled_bytes(shape, dtype, layout) -> int:
    """Bytes of an array in a device layout: its minor dimensions padded
    to the layout's tile."""
    dims = [shape[i] for i in layout.major_to_minor]
    tile = layout.tiling[0]
    for k, t in enumerate(tile, start=len(dims) - len(tile)):
        dims[k] = -(-dims[k] // t) * t
    return int(np.prod(dims)) * np.dtype(dtype).itemsize


@pytest.mark.parametrize("exec_mode", ["dense", "event"])
def test_synfire_scan_streams_bitmasks(exec_mode, one_chip, synfire256):
    """The engine's scan over a 256-PE ring reads its synapses as
    bit-packed masks: as the v5e compile lays them out, tiles padded,
    they take under a tenth of the int16 slabs' bytes, and no
    slab-shaped array is written."""
    net = synfire256.graph.semantics.net
    sp = net.params
    P, NE, NI, N = sp.n_pes, sp.n_exc, sp.n_inh, sp.neurons_per_core
    masks = {(7, P, N), (2, P, NE)}
    assert {(m.shape, m.dtype) for m in (net.m_ff, net.m_inh)} == {
        (s, np.dtype(np.uint32)) for s in masks}
    params, compiled = _compile_scan(synfire256, exec_mode, one_chip)
    tables = [(a, f.layout)
              for a, f in zip(params, compiled.input_formats[0][0])
              if a.shape in masks and a.dtype == jnp.uint32]
    assert {a.shape for a, _ in tables} == masks
    int16_bytes = 2 * P * NE * (N + NI)
    assert sum(_tiled_bytes(a.shape, a.dtype, layout)
               for a, layout in tables) <= int16_bytes // 10
    written = _materialised(compiled.as_text())
    for shape in ((P, NE, N), (P, NI, NE)):
        dims = f"[{','.join(map(str, shape))}]"
        assert not [w for w in written if w.endswith(dims)], dims
