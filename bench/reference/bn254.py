"""Plain reference for the served BN254 transform: exact evaluation mod p.

The server's BN254 op takes a row of field elements a_i in [0, p), p the
BN254 scalar-field prime, zero-padded to its power-of-two bucket d^, and
returns its cyclic evaluation

    X_j = sum_i a_i * omega^(i * j)  (mod p),   j = 0 .. d^ - 1,

with omega the primitive d^-th root g^((p - 1) / d^) for the least g >= 2
that makes it primitive.  ``evaluate`` is the textbook iterative radix-2
transform on Python integers; ``evaluate_direct`` is the O(d^2) definition,
kept for the tests.  Nothing is imported from the program under test.
"""
from __future__ import annotations

import functools

from bench.reference.dilithium import least_root

P = 21888242871839275222246405745257275088548364400416034343698204186575808495617


def _bit_reverse(i: int, bits: int) -> int:
    return int(format(i, f"0{bits}b")[::-1], 2) if bits else 0


@functools.lru_cache(maxsize=16)
def _stage_roots(d: int, p: int) -> tuple:
    """For each butterfly span s = 1, 2, 4, ..: the powers w_s^k, k < s, of
    the primitive 2s-th root w_s = omega^(d / 2s)."""
    omega = least_root(p, d)
    out, span = [], 1
    while span < d:
        w = pow(omega, d // (2 * span), p)
        powers, acc = [], 1
        for _ in range(span):
            powers.append(acc)
            acc = acc * w % p
        out.append(tuple(powers))
        span *= 2
    return tuple(out)


def evaluate(coeffs, d: int, p: int = P) -> list:
    """Cyclic length-d transform of ``coeffs`` (len <= d, zero-padded)."""
    if d & (d - 1):
        raise ValueError(f"d = {d} is not a power of two")
    a = [int(c) % p for c in coeffs] + [0] * (d - len(coeffs))
    bits = d.bit_length() - 1
    x = [a[_bit_reverse(i, bits)] for i in range(d)]
    span = 1
    for roots in _stage_roots(d, p):
        for base in range(0, d, 2 * span):
            for k in range(span):
                u = x[base + k]
                t = x[base + span + k] * roots[k] % p
                x[base + k] = (u + t) % p
                x[base + span + k] = (u - t) % p
        span *= 2
    return x


def evaluate_direct(coeffs, d: int, p: int = P) -> list:
    """The definition, O(d^2): X_j = sum_i a_i omega^(ij) mod p."""
    omega = least_root(p, d)
    out = []
    for j in range(d):
        w, acc, wij = pow(omega, j, p), 0, 1
        for c in coeffs:
            acc = (acc + int(c) * wij) % p
            wij = wij * w % p
        out.append(acc)
    return out
