"""Pallas TPU kernels for the compute hot-spots + staged_transform adapters.

* ``limb_matmul``     — fused limb-interleaved u8×s8 matmul (one staging pass
  of the matrix-form NTT), int32 or fp32-mantissa VMEM accumulation.
* ``mont_fold``       — the per-pass VPU fold (diagonals → residue mod m).
* ``fused_ntt_tile``  — beyond-paper: matmul + fold in one kernel; diagonal
  planes never round-trip HBM (single-tenant fast path).

``pallas_tile_fn``/``pallas_fused_transform`` plug these into
:func:`repro.core.limb_gemm.staged_transform`.  The served path does not call
them: ``SliceCoScheduler`` compiles ``engine.e2e``, whose staging passes are
plain XLA dots and folds.  Every entry point compiles its kernel with Mosaic
unless the caller passes ``interpret=True`` (the CPU tests do).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.limb_matmul.ops import limb_matmul
from repro.kernels.mont_fold.ops import mont_fold, mont_fold_window_fn
from repro.kernels.fused_ntt_tile.ops import diag_major, fused_ntt_tile


def staging_passes() -> dict:
    """Real staging-pass widths at d = 256, for the checks that compile and
    run the kernels on the chip: per workload, the limb rows K (d_tile ×
    data limbs), the degree d, the diagonal count and a channel modulus.
    The limb GEMM's output is d × n_diag wide."""
    from repro.core import field as F
    from repro.core import rns as R
    return {"dilithium": dict(k=171 * 3, d=256, n_diag=5,
                              modulus=F.DILITHIUM_Q),
            "bn254": dict(k=128 * 4, d=256, n_diag=7,
                          modulus=R.make_chain(9).moduli[0])}


def pallas_tile_fn(interpret: bool = False):
    """kernel_fn for staged_transform: Pallas limb matmul per staging pass."""

    def fn(a_tile_u32, w_planes_tile, fused_tile, plan):
        from repro.core import limbs as L
        if fused_tile is None:
            raise ValueError("pallas tile fn requires the fused operand layout")
        n = a_tile_u32.shape[0]
        limbs = L.decompose_u8(a_tile_u32, plan.data_limbs).reshape(n, -1)
        out = limb_matmul(limbs, fused_tile, accum=plan.accum,
                          interpret=interpret)
        return out.reshape(n, plan.d, plan.n_diag)

    return fn


def fused_operand_diag_major(plan) -> np.ndarray:
    """(d·La, d·n_diag) int8 plan operand in the fused kernel's layout."""
    return diag_major(plan.fused_operand.reshape(
        plan.d * plan.data_limbs, plan.d, plan.n_diag))


def pallas_fused_transform(a_u32, plan, *, interpret: bool = False):
    """Full staged transform with the fused matmul+fold kernel per pass.

    Eager per-pass folding (Invariant 5.1 ordering preserved in-kernel), but
    the diagonals stay in VMEM — the beyond-paper single-tenant fast path.
    """
    from repro.core import field as F
    from repro.core import limbs as L

    b = jnp.asarray(fused_operand_diag_major(plan))
    m = jnp.uint32(plan.modulus)
    la = plan.data_limbs
    n = a_u32.shape[0]
    y = jnp.zeros((n, plan.d), jnp.uint32)
    for lo, hi in plan.tile_bounds():
        limbs = L.decompose_u8(a_u32[:, lo:hi], la).reshape(n, -1)
        y_t = fused_ntt_tile(limbs, b[lo * la:hi * la], modulus=plan.modulus,
                             n_diag=plan.n_diag, accum=plan.accum,
                             interpret=interpret)
        y = F.addmod_u32(y, y_t, m)
    return y
