"""The int8 uplink: symmetric per-group quantization of the row, dequantized.

Groups of 256 values share one float32 scale, ``max|x| / L`` with
``L = 2**(bits-1) - 1`` levels a side; ``bits`` below 8 gives the same
codec one precision step down (the control's int4).
"""

from __future__ import annotations

import jax.numpy as jnp

BITS = 8
GROUP = 256


def transmit(row, bits: int = BITS):
    levels = 2 ** (bits - 1) - 1
    n = row.shape[0]
    pad = (-n) % GROUP
    g = jnp.pad(row.astype(jnp.float32), (0, pad)).reshape(-1, GROUP)
    amax = jnp.max(jnp.abs(g), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / levels, 1.0)
    q = jnp.clip(jnp.round(g / scale), -levels, levels)
    return (q * scale).reshape(-1)[:n]
