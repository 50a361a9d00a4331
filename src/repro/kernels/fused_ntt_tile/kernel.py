"""Pallas TPU kernel (beyond-paper): one staging pass, MXU matmul + VPU fold
fused in a single kernel — the int32 diagonal planes never round-trip HBM.

Memory-term napkin math (BN254, d=256, N=128): the unfused pipeline writes
and re-reads (N, d, 7) int32 diagonals = 2 × 128·256·7·4B ≈ 1.8 MB per pass;
fused, only the (N, d) uint32 result (128 KB) leaves VMEM — a ~14× cut in
pass-local HBM traffic.  The fold still runs *after* the pass's summation
completes (Invariant 5.1 is an ordering constraint, which the in-kernel
sequencing preserves), but the paper's multi-tenant discipline keeps the
phases in separate HLO ops — so this kernel is the single-tenant /
relaxed-separation fast path (DESIGN.md §Perf).

The twiddle operand arrives 2-D and diagonal-major inside each ``bd``-wide
coefficient block (:func:`repro.kernels.fused_ntt_tile.ops.diag_major`):
column ``j·bd·n_diag + k·bd + l`` holds coefficient ``j·bd + l`` of
diagonal ``k``.  The epilogue then reads ``n_diag`` lane-aligned ``bd``-wide
slices of the accumulator; a ``(bn, bd, n_diag)`` reshape with ``n_diag``
as the lane dimension is a layout Mosaic cannot lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.limb_matmul.kernel import (BK_EXACT_MAX, accumulate,
                                              block_product)


def _fused_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, accum: str,
                  modulus: int, n_diag: int, bd: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate(acc_ref, block_product(a_ref, b_ref), accum)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _fold_and_flush():
        m = jnp.uint32(modulus)
        acc = jnp.zeros(o_ref.shape, jnp.uint32)
        for k in range(n_diag - 1, -1, -1):
            for _ in range(8):
                acc = acc << jnp.uint32(1)
                acc = jnp.where(acc >= m, acc - m, acc)
            diag = acc_ref[:, k * bd:(k + 1) * bd].astype(jnp.int32)
            dk = jnp.mod(diag, jnp.int32(modulus)).astype(jnp.uint32)
            s = acc + dk
            acc = jnp.where(s >= m, s - m, s)
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=(
    "modulus", "n_diag", "accum", "bn", "bd", "bk", "interpret"))
def fused_ntt_tile_pallas(a_u8, b_s8, *, modulus: int, n_diag: int,
                          accum: str = "int32_native", bn: int = 128,
                          bd: int = 128, bk: int = 128,
                          interpret: bool = False):
    """(N, K) u8 × diagonal-major (K, D·n_diag) s8 -> (N, D) uint32 mod m."""
    n, k = a_u8.shape
    k2, cols = b_s8.shape
    d = cols // n_diag
    assert k == k2 and cols == d * n_diag
    assert n % bn == 0 and d % bd == 0 and k % bk == 0
    assert bk <= BK_EXACT_MAX, f"bk={bk} block products would round in f32"
    k_steps = k // bk
    acc_dtype = jnp.float32 if accum == "fp32_mantissa" else jnp.int32

    return pl.pallas_call(
        functools.partial(_fused_kernel, k_steps=k_steps, accum=accum,
                          modulus=modulus, n_diag=n_diag, bd=bd),
        grid=(n // bn, d // bd, k_steps),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bd * n_diag), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, bd * n_diag), acc_dtype)],
        interpret=interpret,
    )(a_u8, b_s8)
