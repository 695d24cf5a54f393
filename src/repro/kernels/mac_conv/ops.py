"""jit'd wrapper: padding / blocking for the MAC conv kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mac_conv.mac_conv import mac_conv2d_pallas


@functools.partial(jax.jit,
                   static_argnames=("stride", "padding", "bh", "bcout"))
def mac_conv2d(x, w, *, stride=(1, 1), padding="VALID", bh=8, bcout=128):
    """x: (B,H,W,Cin) int8/uint8; w: (KH,KW,Cin,Cout) -> (B,Ho,Wo,Cout) int32."""
    B, H, W, Cin = x.shape
    KH, KW, _, Cout = w.shape
    sh, sw = stride
    if padding == "SAME":
        Ho = -(-H // sh)
        Wo = -(-W // sw)
        ph = max((Ho - 1) * sh + KH - H, 0)
        pw = max((Wo - 1) * sw + KW - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    H, W = x.shape[1], x.shape[2]
    Ho = (H - KH) // sh + 1
    Wo = (W - KW) // sw + 1

    bh_eff = min(bh, Ho)
    pad_rows = (-Ho) % bh_eff
    if pad_rows:                              # pad input so Ho divides bh
        x = jnp.pad(x, ((0, 0), (0, pad_rows * sh), (0, 0), (0, 0)))
    bc_eff = min(bcout, max(128, 1)) if Cout >= 128 else Cout
    pad_c = (-Cout) % bc_eff
    if pad_c:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pad_c)))
    out = mac_conv2d_pallas(x, w, stride=stride, bh=bh_eff, bcout=bc_eff)
    return out[:, :Ho, :Wo, :Cout]
