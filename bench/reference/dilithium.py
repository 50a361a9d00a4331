"""Plain reference for the served Dilithium transform.

The server's Dilithium op is the forward negacyclic NTT over Z_q,
q = 8,380,417, of a degree-d row zero-padded to its power-of-two bucket d^:

    y_j = sum_i a_i * psi^(i * (2j + 1))  (mod q),   j = 0 .. d^ - 1,

with psi the primitive 2d^-th root of unity g^((q - 1) / 2d^) for the least
g >= 2 that makes it primitive, in natural output order.  Here it is a dense
matrix product in float64, made exact by splitting the matrix into 12-bit
halves, W = W_hi * 2^12 + W_lo: a coefficient (< 2^23) times a half (< 2^12)
is below 2^35, so a row of up to 2^17 such products sums below 2^52, inside
float64's exact integers.  Nothing is imported from the program under test.
"""
from __future__ import annotations

import functools

import numpy as np

Q = 8380417


def least_root(m: int, order: int) -> int:
    """psi = g^((m-1)/order) for the least g >= 2 with psi of exact order
    ``order`` (a power of two: psi^(order/2) != 1 suffices)."""
    if order & (order - 1) or (m - 1) % order:
        raise ValueError(f"order {order} is not a power of two dividing {m}-1")
    for g in range(2, 1 << 16):
        w = pow(g, (m - 1) // order, m)
        if order == 1 or pow(w, order // 2, m) != 1:
            return w
    raise ValueError(f"no primitive {order}-th root of unity mod {m}")


@functools.lru_cache(maxsize=16)
def ntt_matrix(d: int, q: int = Q) -> np.ndarray:
    """(d, d) int64 with W[i, j] = psi^(i(2j+1)) mod q."""
    psi = least_root(q, 2 * d)
    powers = np.empty(2 * d, np.int64)
    acc = 1
    for k in range(2 * d):
        powers[k] = acc
        acc = acc * psi % q
    i = np.arange(d, dtype=np.int64)[:, None]
    j = np.arange(d, dtype=np.int64)[None, :]
    return powers[(i * (2 * j + 1)) % (2 * d)]


def ntt(a: np.ndarray, q: int = Q, block: int = 8192) -> np.ndarray:
    """(N, d) coefficients in [0, q) -> (N, d) int64 negacyclic NTT mod q."""
    a = np.asarray(a, np.int64)
    n, d = a.shape
    if q >= 1 << 23 or d > (1 << 17):
        raise ValueError(f"q = {q}, d = {d}: the float64 sums would round")
    w = ntt_matrix(d, q)
    w_hi = (w >> 12).astype(np.float64)
    w_lo = (w & 0xFFF).astype(np.float64)
    out = np.empty((n, d), np.int64)
    for lo in range(0, n, block):
        x = a[lo:lo + block].astype(np.float64)
        hi = _mod(x @ w_hi, q) * 4096.0           # < 2^35
        out[lo:lo + block] = _mod(hi + x @ w_lo, q).astype(np.int64)
    return out


def _mod(x: np.ndarray, q: int) -> np.ndarray:
    """x mod q for float64 integers x in [0, 2^53): floor(x / q) * q is
    exact, and the rounded quotient is off by at most one."""
    r = x - np.floor(x / q) * q
    r += np.where(r < 0, q, 0)
    r -= np.where(r >= q, q, 0)
    return r
