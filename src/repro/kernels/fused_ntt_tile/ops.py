"""jit'd wrapper for the fused staging-pass kernel + its operand layout."""
from __future__ import annotations

from repro.kernels.fused_ntt_tile.kernel import fused_ntt_tile_pallas
from repro.kernels.limb_matmul.ops import _pad_to, _pick_bn


def _pick_bd(d: int) -> int:
    return 128 if d % 128 == 0 else d


def diag_major(b3_s8):
    """(K, D, n_diag) -> (K, D·n_diag), diagonal-major inside each ``bd``-wide
    coefficient block — the fused kernel's operand layout.  Works on numpy
    (host-side plan layout) and jax arrays alike."""
    k, d, n_diag = b3_s8.shape
    bd = _pick_bd(d)
    return (b3_s8.reshape(k, d // bd, bd, n_diag)
            .transpose(0, 1, 3, 2).reshape(k, d * n_diag))


def fused_ntt_tile(a_u8, b_s8, *, modulus: int, n_diag: int,
                   accum: str = "int32_native", interpret: bool = False):
    """(N, K) u8 × :func:`diag_major` (K, D·n_diag) s8 -> (N, D) uint32
    folded mod m.  ``interpret=True`` runs the kernel body in Python."""
    n, _ = a_u8.shape
    d = b_s8.shape[1] // n_diag
    bn = _pick_bn(n)
    a_p = _pad_to(_pad_to(a_u8, 0, bn), 1, 128)
    b_p = _pad_to(b_s8, 0, 128)
    out = fused_ntt_tile_pallas(a_p, b_p, modulus=modulus, n_diag=n_diag,
                                accum=accum, bn=bn, bd=_pick_bd(d),
                                interpret=interpret)
    return out[:n, :d]
