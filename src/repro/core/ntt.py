"""Number Theoretic Transform constructions.

* ``ntt_matrix`` — the dense matrix-form NTT operand (paper's O(d²) object),
  host-precomputed with Python bignums / numpy gathers.
* ``cooley_tukey_ntt`` — the asymptotically optimal O(d log d) radix-2 NTT in
  pure JAX uint32 arithmetic (the "GPU-style" algorithmic baseline of Fig. 3).
* ``morph_stage_matrices`` — the MORPH single-tenant baseline: the radix-2
  butterfly expressed as a sequence of log2(d) dense tile-resident GEMMs
  against permuted twiddle blocks (paper §7.2.1).
* ``four_step_factors`` — the four-step (Bailey) split of a cyclic
  transform into two √d-sized matrix transforms and a twiddle Hadamard, for
  degrees whose dense matrix no longer fits a chip.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import field as F
from repro.core import primes as P


# --- Host-side matrix construction -------------------------------------------


def _power_table(base: int, count: int, m: int) -> np.ndarray:
    out = np.empty(count, object)
    acc = 1
    for k in range(count):
        out[k] = acc
        acc = acc * base % m
    return out.astype(np.uint32) if m < 2**32 else out


@functools.lru_cache(maxsize=64)
def _roots(m: int, order: int) -> int:
    return P.primitive_root_of_unity(m, order)


def ntt_matrix(d: int, m: int, *, negacyclic: bool = False) -> np.ndarray:
    """Dense forward-NTT matrix W (uint32, d×d) with y = a @ W (mod m).

    Cyclic:      W[i, j] = ω^{ij},          ω a primitive d-th root.
    Negacyclic:  W[i, j] = ψ^{i(2j+1)},     ψ a primitive 2d-th root
                 (evaluation at odd powers of ψ — the Dilithium convention).
    """
    if negacyclic:
        psi = _roots(m, 2 * d)
        table = _power_table(psi, 2 * d, m)
        i = np.arange(d, dtype=np.int64)[:, None]
        j = np.arange(d, dtype=np.int64)[None, :]
        idx = (i * (2 * j + 1)) % (2 * d)
        return table[idx]
    omega = _roots(m, d)
    table = _power_table(omega, d, m)
    i = np.arange(d, dtype=np.int64)[:, None]
    j = np.arange(d, dtype=np.int64)[None, :]
    idx = (i * j) % d
    return table[idx]


def intt_matrix(d: int, m: int, *, negacyclic: bool = False) -> np.ndarray:
    """Inverse transform matrix: (a @ W) @ Winv == a (mod m)."""
    w = ntt_matrix(d, m, negacyclic=negacyclic).astype(object)
    dinv = pow(d, m - 2, m)
    if negacyclic:
        psi = _roots(m, 2 * d)
        psi_inv = pow(psi, 2 * d - 1, m)
        # Winv[j, i] = d^{-1} ψ^{-i(2j+1)}
        i = np.arange(d, dtype=np.int64)[None, :]
        j = np.arange(d, dtype=np.int64)[:, None]
        table = _power_table(psi_inv, 2 * d, m)
        idx = (i * (2 * j + 1)) % (2 * d)
        out = (table[idx].astype(object) * dinv) % m
        return out.astype(np.uint32)
    omega = _roots(m, d)
    omega_inv = pow(omega, d - 1, m)
    table = _power_table(omega_inv, d, m)
    i = np.arange(d, dtype=np.int64)[None, :]
    j = np.arange(d, dtype=np.int64)[:, None]
    idx = (i * j) % d
    out = (table[idx].astype(object) * dinv) % m
    return out.astype(np.uint32)


def matrix_ntt_oracle_np(a: np.ndarray, w: np.ndarray, m: int) -> np.ndarray:
    """Exact host oracle: (a @ W) mod m with Python bignums."""
    acc = a.astype(object) @ w.astype(object)
    return (acc % m).astype(np.uint32)


# --- Four-step (Bailey) decomposition ----------------------------------------


def four_step_split(d: int) -> tuple[int, int]:
    """(d1, d2) = (2^⌈k/2⌉, 2^⌊k/2⌋) for d = 2^k: the sizes of the column
    and row transforms of the four-step decomposition."""
    if d < 4 or d & (d - 1):
        raise ValueError(f"four-step transform needs a power of two d >= 4, "
                         f"got {d}")
    k = d.bit_length() - 1
    return 1 << (k - k // 2), 1 << (k // 2)


def four_step_factors(d: int, m: int) -> tuple:
    """Host operands of the cyclic length-d transform mod m as a four-step.

    With input index i = d2·i1 + i2 and output index j = j1 + d1·j2,
    ω^(ij) = ω^(d2·i1·j1) · ω^(i2·j1) · ω^(d1·i2·j2), so

    * stage 1: d2 column transforms of size d1 with W1[i1, j1] = ω^(d2·i1·j1);
    * the twiddle Hadamard T[i2, j1] = ω^(i2·j1), shape (d2, d1);
    * stage 2: d1 row transforms of size d2 with W2[i2, j2] = ω^(d1·i2·j2);

    and X[j1 + d1·j2] is the stage-2 output at (j1, j2): a transpose back to
    natural order.  Every factor is a power of the one primitive d-th root
    ω, so the result is the same transform as ``ntt_matrix(d, m)``'s.
    Returns ``(W1, W2, T)``, entries in [0, m) (object ints for m ≥ 2^32).
    """
    d1, d2 = four_step_split(d)
    table = _power_table(_roots(m, d), d, m)

    def powers(rows: int, cols: int, scale: int) -> np.ndarray:
        i = np.arange(rows, dtype=np.int64)[:, None]
        j = np.arange(cols, dtype=np.int64)[None, :]
        return table[(scale * i * j) % d]

    return powers(d1, d1, d2), powers(d2, d2, d1), powers(d2, d1, 1)


def four_step_oracle_np(a: np.ndarray, m: int) -> np.ndarray:
    """Exact host four-step of rows ``a`` (..., d) with Python bignums, the
    decomposition itself (for tests): equals ``a @ ntt_matrix(d, m) mod m``."""
    d = a.shape[-1]
    d1, d2 = four_step_split(d)
    w1, w2, t = (x.astype(object) for x in four_step_factors(d, m))
    x = a.astype(object).reshape(a.shape[:-1] + (d1, d2))
    x = np.swapaxes(x, -1, -2)                      # [..., i2, i1]
    y = (x @ w1) % m * t % m                        # [..., i2, j1]
    z = (np.swapaxes(y, -1, -2) @ w2) % m           # [..., j1, j2]
    return np.swapaxes(z, -1, -2).reshape(a.shape)  # X[j1 + d1·j2]


# --- O(d log d) Cooley-Tukey in pure JAX uint32 ------------------------------


def _bit_reverse_perm(d: int) -> np.ndarray:
    bits = d.bit_length() - 1
    idx = np.arange(d)
    rev = np.zeros(d, np.int64)
    for b in range(bits):
        rev |= ((idx >> b) & 1) << (bits - 1 - b)
    return rev


@functools.lru_cache(maxsize=64)
def _ct_stage_twiddles(d: int, m: int) -> tuple:
    """Per-stage twiddle vectors for iterative radix-2 DIT (cyclic)."""
    omega = _roots(m, d)
    stages = []
    span = 1
    while span < d:
        w_span = pow(omega, d // (2 * span), m)
        stages.append(_power_table(w_span, span, m))
        span *= 2
    return tuple(stages)


def cooley_tukey_ntt(a_u32, m: int, *, negacyclic: bool = False):
    """Radix-2 DIT NTT over uint32; a_u32: (..., d). O(d log d) mulmods."""
    d = a_u32.shape[-1]
    mj = jnp.uint32(m)
    if negacyclic:
        psi = _roots(m, 2 * d)
        pre = jnp.asarray(_power_table(psi, d, m))
        a_u32 = F.mulmod_u32(a_u32, pre, mj)
    rev = jnp.asarray(_bit_reverse_perm(d))
    x = jnp.take(a_u32, rev, axis=-1)
    for tw in _ct_stage_twiddles(d, m):
        span = tw.shape[0]
        tw_j = jnp.asarray(tw)
        shp = x.shape[:-1] + (d // (2 * span), 2, span)
        xr = x.reshape(shp)
        u = xr[..., 0, :]
        t = F.mulmod_u32(xr[..., 1, :], tw_j, mj)
        lo = F.addmod_u32(u, t, mj)
        hi = F.submod_u32(u, t, mj)
        x = jnp.stack([lo, hi], axis=-2).reshape(x.shape[:-1] + (d,))
    return x


def cooley_tukey_oracle_np(a: np.ndarray, m: int, *, negacyclic: bool = False) -> np.ndarray:
    """Host bignum oracle for the CT transform = matrix NTT (same convention).

    Cyclic CT computes â_j = Σ a_i ω^{ij}; equals a @ ntt_matrix. The
    negacyclic pre-twist ψ^i gives evaluation at ψ^{2j+1}... but the DIT
    output ordering matches the cyclic matrix on the twisted input, so we
    simply reuse the matrix oracle.
    """
    if negacyclic:
        psi = _roots(m, 2 * len(a) if a.ndim == 1 else 2 * a.shape[-1])
        d = a.shape[-1]
        pre = _power_table(psi, d, m).astype(object)
        a = (a.astype(object) * pre) % m
    w = ntt_matrix(a.shape[-1], m, negacyclic=False)
    return matrix_ntt_oracle_np(a, w, m)


# --- MORPH baseline: butterfly as dense per-stage GEMMs ----------------------


@functools.lru_cache(maxsize=16)
def morph_stage_matrices(d: int, m: int) -> tuple:
    """Dense (d×d) uint32 matrices S_1..S_log2(d) plus the bit-reversal
    permutation matrix P such that a @ P @ S_1 @ ... @ S_k == cyclic NTT(a).

    Built by applying the iterative butterfly stages to identity columns with
    bignum arithmetic — each S_s has exactly 2 nonzeros per row, but MORPH
    dispatches it as a dense tile-resident GEMM.
    """
    rev = _bit_reverse_perm(d)
    perm = np.zeros((d, d), np.uint32)
    perm[rev, np.arange(d)] = 1

    mats = []
    span = 1
    omega = _roots(m, d)
    while span < d:
        w_span = pow(omega, d // (2 * span), m)
        tw = _power_table(w_span, span, m)
        s = np.zeros((d, d), object)
        nblocks = d // (2 * span)
        for blk in range(nblocks):
            base = blk * 2 * span
            for j in range(span):
                u, v = base + j, base + span + j
                # lo = u + tw*v ; hi = u - tw*v   (row = input, col = output)
                s[u, u] = 1
                s[u, v] = 1
                s[v, u] = int(tw[j])
                s[v, v] = (m - int(tw[j])) % m
        mats.append((s % m).astype(np.uint32))
        span *= 2
    return (perm,) + tuple(mats)
