"""Pallas TPU kernel: the per-pass VPU fold (diagonals → field residue mod m).

Elementwise Horner over the limb weight classes with conditional-subtract
modular doublings — pure VPU work, no MXU.  Blocked over the (rows, coeffs)
plane with the full (small) n_diag axis resident per block.

This is the operation whose *eager* per-pass scheduling the paper's Invariant
5.1 mandates; keeping it a separate kernel (vs. fused_ntt_tile) mirrors the
multi-tenant isolation discipline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fold_kernel(d_ref, o_ref, *, modulus: int, n_diag: int):
    m = jnp.uint32(modulus)
    acc = jnp.zeros(o_ref.shape, jnp.uint32)
    for k in range(n_diag - 1, -1, -1):
        # acc = (acc << 8) mod m via 8 conditional doublings (acc < m < 2^31)
        for _ in range(8):
            acc = acc << jnp.uint32(1)
            acc = jnp.where(acc >= m, acc - m, acc)
        dk = jnp.mod(d_ref[..., k], jnp.int32(modulus)).astype(jnp.uint32)
        s = acc + dk
        acc = jnp.where(s >= m, s - m, s)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("modulus", "bn", "bd", "interpret"))
def mont_fold_pallas(diags, *, modulus: int, bn: int = 8, bd: int = 256,
                     interpret: bool = False):
    """int32 (N, D, n_diag) -> uint32 (N, D): Σ_k diag_k·2^{8k} mod m."""
    n, d, n_diag = diags.shape
    assert n % bn == 0 and d % bd == 0, "ops.py must pad to block multiples"
    return pl.pallas_call(
        functools.partial(_fold_kernel, modulus=modulus, n_diag=n_diag),
        grid=(n // bn, d // bd),
        in_specs=[pl.BlockSpec((bn, bd, n_diag), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.uint32),
        interpret=interpret,
    )(diags)
