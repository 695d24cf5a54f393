"""Fig. 22 + Fig. 23: DNN layer speedup and energy-efficiency gain of the
MAC accelerator vs an Arm CMSIS-NN implementation.

Layers from LeNet / VGG-16 / ResNet-50 / MobileNetV2 are partitioned to the
128 kB PE SRAM (core/pe.py), timed with the PE cycle model at both DVFS
operating points, and a reduced instance of each layer is EXECUTED through
the Pallas conv/GEMM kernels against the oracle to prove numerics.

Paper bands: conv speedup 116-610x, MM speedup 9-28x; efficiency gain
148-652x (conv) and 297-482x (FC).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.configs import paper
from repro.core.pe import PESpec, partition_layer_to_sram
from repro.kernels.mac_conv import mac_conv2d, mac_conv2d_ref
from repro.kernels.mac_gemm import mac_gemm, mac_gemm_ref

# (name, kind, geometry)
LAYERS = [
    ("lenet_c1", "conv", dict(h=28, w=28, cin=1, cout=6, kh=5, kw=5)),
    ("lenet_c3", "conv", dict(h=14, w=14, cin=6, cout=16, kh=5, kw=5)),
    ("vgg16_conv3_256", "conv", dict(h=56, w=56, cin=256, cout=256, kh=3, kw=3)),
    ("resnet50_1x1_b2", "conv", dict(h=56, w=56, cin=64, cout=64, kh=1, kw=1)),
    ("resnet50_3x3_b2", "conv", dict(h=56, w=56, cin=64, cout=64, kh=3, kw=3)),
    ("mobilenetv2_pw", "conv", dict(h=56, w=56, cin=24, cout=144, kh=1, kw=1)),
    ("lenet_fc", "mm", dict(m=1, k=400, n=120)),
    ("vgg16_fc_tile", "mm", dict(m=1, k=4096, n=512)),
]

PLS = [(0.50, 200e6, "PL2"), (0.60, 400e6, "PL3")]


def _pe_power_w(vdd, f, *, mac: bool, util: float = 1.0) -> float:
    """Per-power-lane power at (vdd, f) — the paper measures each rail's
    shunt separately (Sec. VI-D), so the Arm lane carries baseline + core
    dynamic while the MAC lane carries only the accelerator dynamic."""
    base = {0.50: paper.PL2.p_baseline_w, 0.60: paper.PL3.p_baseline_w}[vdd]
    if mac:
        # measured accelerator-lane efficiency (Fig. 15) -> J/op; the
        # 1.56x data-transfer bug stretches time, not per-op energy
        tops_w = paper.MAC_TOPS_PER_W[(vdd, f)]
        return util * 2 * 64 * f / (tops_w * 1e12)
    core_dyn = paper.COREMARK_UW_PER_MHZ[(vdd, f)] * 1e-6 * f / 1e6
    return base + core_dyn


def main() -> None:
    pe = PESpec()
    for name, kind, g in LAYERS:
        if kind == "conv":
            rows, cout_t, n_tiles = partition_layer_to_sram(pe, **g)
            mac_cyc = pe.mac_conv_cycles(**g)
            arm_cyc = pe.arm_conv_cycles(**g)
        else:
            mac_cyc = pe.mac_mm_cycles(g["m"], g["k"], g["n"])
            arm_cyc = pe.arm_mm_cycles(g["m"], g["k"], g["n"])
            n_tiles = 1
        # execute a reduced instance through the kernel to prove numerics
        rng = np.random.default_rng(1)
        if kind == "conv":
            gg = dict(g)
            gg["h"] = min(g["h"], 14)
            gg["w"] = min(g["w"], 14)
            gg["cin"] = min(g["cin"], 32)
            gg["cout"] = min(g["cout"], 32)
            x = jnp.asarray(rng.integers(-128, 127,
                                         (1, gg["h"], gg["w"], gg["cin"])),
                            np.int8)
            wt = jnp.asarray(rng.integers(-128, 127,
                                          (gg["kh"], gg["kw"], gg["cin"],
                                           gg["cout"])), np.int8)
            us = time_call(mac_conv2d, x, wt)
            assert bool(jnp.all(mac_conv2d(x, wt) == mac_conv2d_ref(x, wt)))
        else:
            a = jnp.asarray(rng.integers(-128, 127, (g["m"], min(g["k"], 512))),
                            np.int8)
            b = jnp.asarray(rng.integers(-128, 127, (min(g["k"], 512),
                                                     min(g["n"], 128))), np.int8)
            us = time_call(mac_gemm, a, b)
            assert bool(jnp.all(mac_gemm(a, b) == mac_gemm_ref(a, b)))

        speedup = arm_cyc / mac_cyc
        for vdd, f, pl in PLS:
            t_mac = mac_cyc / f
            t_arm = arm_cyc / f
            util = min(g.get("m", 64), 4) / 4.0 if kind == "mm" else 1.0
            e_mac = t_mac * _pe_power_w(vdd, f, mac=True, util=util)
            e_arm = t_arm * _pe_power_w(vdd, f, mac=False)
            gain = e_arm / e_mac
            band = "116-610" if kind == "conv" else "9-28"
            eband = "148-652" if kind == "conv" else "297-482"
            emit(f"fig22_23_{name}_{pl}", us,
                 f"speedup={speedup:.0f}(paper_band={band});"
                 f"eff_gain={gain:.0f}(paper_band={eband});"
                 f"t_mac_us={t_mac*1e6:.0f};tiles={n_tiles}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
