"""Prime-field parameters and exact 32-bit modular arithmetic in JAX.

TPUs (and this framework's device code) have no 64-bit integer ALU path worth
using — the whole point of the paper.  Every device-side primitive here is
exact using only uint32/int32 operations:

* ``addmod_u32`` / ``submod_u32`` — trivial conditional-subtract forms.
* ``mulmod_u32`` — 16-bit schoolbook split with shift-by-one modular doubling,
  exact for any modulus m < 2**31.

These are the "VPU-side" scalar primitives.  The MXU-side path (int8 limb
matmuls) lives in :mod:`repro.core.limb_gemm`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp

# --- Field constants (host-side Python bignums) -----------------------------

# BN254 scalar field (Fr) — the NTT field of Groth16/PLONK over BN254.
BN254_FR = 21888242871839275222246405745257275088548364400416034343698204186575808495617
BN254_FR_TWO_ADICITY = 28

# CRYSTALS-Dilithium / ML-DSA prime, q = 2^23 - 2^13 + 1.
DILITHIUM_Q = 8380417
DILITHIUM_ZETA = 1753  # primitive 512th root of unity mod Q (FIPS 204)


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """A prime field as staged on the accelerator."""

    name: str
    modulus: int          # Python bignum; may exceed 32 bits (BN254)
    limbs: int            # u8 limbs per 32-bit staged word
    n_channels: int       # RNS channels (1 = direct single-word field)

    @property
    def bits(self) -> int:
        return self.modulus.bit_length()


DILITHIUM_FIELD = FieldSpec("dilithium", DILITHIUM_Q, limbs=3, n_channels=1)
BN254_FIELD = FieldSpec("bn254", BN254_FR, limbs=4, n_channels=9)


# --- Exact uint32 modular arithmetic (vectorised, jit-safe) ------------------


def addmod_u32(a, b, m):
    """(a + b) mod m for a, b < m < 2**31 (uint32 arrays)."""
    s = a + b
    return jnp.where(s >= m, s - m, s)


def submod_u32(a, b, m):
    """(a - b) mod m for a, b < m < 2**31 (uint32 arrays)."""
    return jnp.where(a >= b, a - b, a + m - b)


def _shiftk_mod(x, m, k: int):
    """(x << k) mod m via k conditional doublings; x < m < 2**31."""
    for _ in range(k):
        x = x << jnp.uint32(1)
        x = jnp.where(x >= m, x - m, x)
    return x


def shift8_mod(x, m):
    return _shiftk_mod(x, m, 8)


def shift12_mod(x, m):
    return _shiftk_mod(x, m, 12)


def shift16_mod(x, m):
    return _shiftk_mod(x, m, 16)


def mulmod_u32(a, b, m):
    """(a * b) mod m, exact, for a, b < m < 2**31. All uint32.

    16-bit schoolbook: a·b = p11·2^32 + (p10+p01)·2^16 + p00 with every
    partial product representable in uint32.
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    a1, a0 = a >> jnp.uint32(16), a & jnp.uint32(0xFFFF)
    b1, b0 = b >> jnp.uint32(16), b & jnp.uint32(0xFFFF)
    p11 = a1 * b1           # < 2^30
    p10 = a1 * b0           # < 2^31
    p01 = a0 * b1           # < 2^31
    p00 = a0 * b0           # < 2^32 (uint32-exact)
    r = shift16_mod(p11 % m, m)
    r = addmod_u32(r, p10 % m, m)
    r = addmod_u32(r, p01 % m, m)
    r = shift16_mod(r, m)
    return addmod_u32(r, p00 % m, m)


def negmod_u32(a, m):
    return jnp.where(a == 0, a, m - a)


def fold_diagonals_u32(diags, m):
    """Fold limb-weight diagonals into a field value mod m (the "VPU fold").

    diags: int32 [..., n_diag] — diagonal k carries weight 2**(8k); entries may
    be negative (balanced twiddle recode).  m: uint32 scalar (m < 2**31).
    Returns uint32 [...] = (sum_k diags[...,k] << 8k) mod m.

    Horner from the top: acc = (acc << 8 + D_k) mod m.  acc < m < 2**31 so the
    doubling chain never overflows uint32.
    """
    m_i32 = m.astype(jnp.int32) if hasattr(m, "astype") else jnp.int32(m)
    n_diag = diags.shape[-1]
    acc = jnp.zeros(diags.shape[:-1], jnp.uint32)
    for k in range(n_diag - 1, -1, -1):
        acc = shift8_mod(acc, m)
        dk = jnp.mod(diags[..., k], m_i32).astype(jnp.uint32)  # non-negative
        acc = addmod_u32(acc, dk, m)
    return acc


@functools.lru_cache(maxsize=None)
def field_for(name: str) -> FieldSpec:
    if name == "dilithium":
        return DILITHIUM_FIELD
    if name == "bn254":
        return BN254_FIELD
    raise KeyError(name)
