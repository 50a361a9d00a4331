"""Pallas TPU kernel: fused limb-interleaved u8×s8 matmul with int32/f32 VMEM
accumulation (one staging pass of the matrix-form NTT).

Tiling: grid (N/bn, M/bm, K/bk); A (bn, bk) u8 and B (bk, bm) s8 blocks are
staged HBM→VMEM per step, partial sums live in a VMEM scratch accumulator and
are written back once per (n, m) tile — K is the innermost ("arbitrary")
grid dimension so the accumulator never round-trips HBM.

MXU alignment: all block dims are multiples of 128 (the systolic tile edge);
ops.py zero-pads K/M/N to block multiples, which is exact for this integer
workload.

Each (bn, bk)×(bk, bm) block product runs on the MXU in float32: Mosaic has
no int32×int32 matmul and no direct u8→f32 cast, so A widens u8→int32→f32.
u8 and s8 values are exact in f32, and one block sums to at most
bk·255·128 < 2**24 (bk ≤ 514), so every block product is exact.  The
``int32_native`` model then accumulates the blocks across k-steps in an
int32 scratch (exact to 2**31 - 1); ``fp32_mantissa`` accumulates them in
float32, reproducing the TPU v4 MXU partial-sum path of paper Property 5.1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Largest K block whose f32 block product is exact: bk·255·128 < 2**24.
BK_EXACT_MAX = (1 << 24) // (255 * 128)


def block_product(a_ref, b_ref):
    """Exact f32 MXU product of one u8 (bn, bk) × s8 (bk, bm) block pair."""
    a = a_ref[...].astype(jnp.int32).astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def accumulate(acc_ref, part, accum: str):
    """Add one exact block product into the accumulator scratch."""
    if accum == "fp32_mantissa":
        acc_ref[...] += part
    else:
        acc_ref[...] += part.astype(jnp.int32)


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, accum: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate(acc_ref, block_product(a_ref, b_ref), accum)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "bk", "accum", "interpret"))
def limb_matmul_pallas(a_u8, b_s8, *, bn: int = 128, bm: int = 128,
                       bk: int = 128, accum: str = "int32_native",
                       interpret: bool = False):
    """(N, K) u8 × (K, M) s8 -> (N, M) int32. Caller pads to block multiples."""
    n, k = a_u8.shape
    k2, m = b_s8.shape
    assert k == k2 and n % bn == 0 and m % bm == 0 and k % bk == 0, (
        "ops.py must pad operands to block multiples")
    assert bk <= BK_EXACT_MAX, f"bk={bk} block products would round in f32"
    k_steps = k // bk
    acc_dtype = jnp.float32 if accum == "fp32_mantissa" else jnp.int32

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps, accum=accum),
        grid=(n // bn, m // bm, k_steps),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bn, bm), acc_dtype)],
        interpret=interpret,
    )(a_u8, b_s8)
