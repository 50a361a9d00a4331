"""jit'd wrapper for the limb matmul kernel: padding + dispatch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.limb_matmul.kernel import limb_matmul_pallas


def _pad_to(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_bn(n: int) -> int:
    """Row block: 128, or the whole (power-of-two padded) row extent below
    that — a block equal to the full dimension meets Mosaic's 8-bit tiling
    at any height."""
    if n >= 128:
        return 128
    b = 8
    while b < n:
        b *= 2
    return b


def limb_matmul(a_u8, b_s8, *, accum: str = "int32_native",
                interpret: bool = False):
    """(N, K) u8 × (K, M) s8 -> (N, M) int32 via the Pallas kernel.

    Pads every dim to MXU-aligned block multiples (exact: zero padding).
    ``interpret=True`` runs the kernel body in Python (CPU tests).
    """
    n, k = a_u8.shape
    m = b_s8.shape[1]
    bn = _pick_bn(n)
    a_p = _pad_to(_pad_to(a_u8, 0, bn), 1, 128)
    b_p = _pad_to(_pad_to(b_s8, 0, 128), 1, 128)
    out = limb_matmul_pallas(a_p, b_p, bn=bn, accum=accum, interpret=interpret)
    return out[:n, :m]
