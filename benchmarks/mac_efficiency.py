"""Fig. 14 + Fig. 15: PE CoreMark efficiency and MAC-array matrix-multiply
energy efficiency at the DVFS performance levels.

The kernel is executed to check its correctness; energy derives from
the cycle model (core/pe.py) + the paper's measured operating points.
Checks: modeled TOPS/W lands on the measured 1.47 / 1.51 (and 1.75 at the
0.5 V / 320 MHz point) within 10%, including the paper's 1.56x data-path
bug derating.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.configs import paper
from repro.core.pe import PESpec
from repro.kernels.mac_gemm import mac_gemm, mac_gemm_ref


def modeled_tops_per_w(vdd: float, freq_hz: float) -> float:
    """TOPS/W of the MAC array running MM from local SRAM.

    Two-parameter model P = P0 + c * f * (V/0.5)^2: a fixed overhead
    (leakage + clocking, amortized at higher f — this is why the measured
    efficiency RISES from 1.47 to 1.75 between 200 and 320 MHz) plus CV^2f
    switching.  Fitted on the (0.5 V, 200 MHz) and (0.5 V, 320 MHz)
    measurements; the (0.6 V, 400 MHz) point validates within 10%.
    """
    pe = PESpec()
    ops = lambda f: 2 * pe.macs_per_cycle * f
    p200 = ops(200e6) / (paper.MAC_TOPS_PER_W[(0.50, 200e6)] * 1e12)
    p320 = ops(320e6) / (paper.MAC_TOPS_PER_W[(0.50, 320e6)] * 1e12)
    c = (p320 - p200) / (320e6 - 200e6)
    p0 = p200 - c * 200e6
    p = p0 + c * freq_hz * (vdd / 0.50) ** 2
    return ops(freq_hz) / p / 1e12


def main() -> None:
    # Fig. 14 — CoreMark uW/MHz at the two PLs (anchored constants)
    for (v, f), uw in paper.COREMARK_UW_PER_MHZ.items():
        emit(f"fig14_coremark_{int(v*100)}V_{int(f/1e6)}MHz", 0.0,
             f"uW_per_MHz={uw}")

    # Fig. 15 — MAC MM efficiency: execute the kernel + model the energy
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 255, (64, 128)), np.uint8)
    b = jnp.asarray(rng.integers(0, 255, (128, 64)), np.uint8)
    us = time_call(mac_gemm, a, b)
    assert bool(jnp.all(mac_gemm(a, b) == mac_gemm_ref(a, b)))

    for (v, f), measured in paper.MAC_TOPS_PER_W.items():
        got = modeled_tops_per_w(v, f)
        ok = abs(got - measured) / measured < 0.10
        emit(f"fig15_mac_mm_{int(v*100)}V_{int(f/1e6)}MHz", us,
             f"model_TOPS_W={got:.2f};paper={measured};within10pct={ok}")
    eff_bug = paper.MAC_TOPS_PER_W[(0.50, 200e6)] / paper.MAC_HW_BUG_FACTOR
    emit("fig15_mac_mm_with_hw_bug", us,
         f"effective_TOPS_W={eff_bug:.2f};derate=1.56x")


if __name__ == "__main__":
    from repro.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    main()
