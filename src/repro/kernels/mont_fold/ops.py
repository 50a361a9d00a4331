"""jit'd wrapper for the VPU fold kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.mont_fold.kernel import mont_fold_pallas


def mont_fold(diags, modulus: int, *, interpret: bool = False):
    """int32 (N, D, n_diag) -> uint32 (N, D) folded mod m.
    ``interpret=True`` runs the kernel body in Python (CPU tests)."""
    n, d, n_diag = diags.shape
    bn = min(n, 8)
    bd = min(d, 256)
    x = jnp.pad(diags, ((0, (-n) % bn), (0, (-d) % bd), (0, 0)))
    out = mont_fold_pallas(x, modulus=modulus, bn=bn, bd=bd,
                           interpret=interpret)
    return out[:n, :d]


def mont_fold_window_fn(*, interpret: bool = False):
    """``fold_fn`` adapter for κ-window lazy mode.

    Returned callable has the ``fold_fn(acc_diag, modulus) -> uint32``
    contract of :func:`repro.core.montgomery.deferred_fold`, so the once-per-
    window deferred reduction runs through the Pallas VPU kernel instead of
    the elementwise jnp fold.  Semantics are identical (same Horner/
    conditional-subtract recurrence); diagonals may be κ-pass sums — the
    kernel's per-diagonal ``mod`` handles any int32 magnitude.
    """

    def fold(acc_diag, modulus):
        return mont_fold(acc_diag, int(modulus), interpret=interpret)

    return fold
