"""NEF communication channel (paper Sec. VI-C, Fig. 19/20): encode on the
MAC array, spike on fixed-point LIF, decode event-driven.

    PYTHONPATH=src python examples/nef_channel.py
"""
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core.nef import build_ensemble, run_channel

enable_compilation_cache()

ens = build_ensemble(n_neurons=512, dims=1, seed=0)
t = np.arange(1200)
x = 0.8 * np.sin(2 * np.pi * t / 500)[:, None]
out = run_channel(ens, x, use_mac=True)

xhat = out["xhat"][:, 0]
rmse = float(np.sqrt(np.mean((xhat[300:] - x[300:, 0]) ** 2)))
rate = out["spikes_per_tick"].mean() / 512 * 1000

print("input vs decoded output (ASCII, 60 cols):")
for label, sig in (("x   ", x[:, 0]), ("xhat", xhat)):
    cols = sig[::20][:60]
    row = "".join("-+*#"[min(3, int((v + 1) * 2))] if abs(v) <= 1 else "!"
                  for v in cols)
    print(f"{label} |{row}|")
print(f"\nRMSE (steady state) = {rmse:.3f}; population rate = {rate:.0f} Hz")
print("encode ran through the int8 MAC-array kernel (Fig. 19 pipeline)")
