"""Workload engines: the paper's unit-of-work "op" per cryptographic class.

* ``DilithiumEngine`` — forward negacyclic NTT over Q = 8,380,417 (3-limb
  u8×s8, single channel).  One op = one forward NTT of degree d (paper §7).
* ``BN254Engine``     — 9-channel ERNS matrix-form transform with
  CRT-consistent twiddles + per-coefficient Shenoy–Kumaresan / Montgomery
  reduction.  One op = one coefficient-wise full-field polynomial
  multiplication: dual staging passes + in-GEMM matmul + >2,100
  base-extension Montgomery ops (paper §6.2).  ``n_channels=18`` selects the
  extended full-exactness chain (``bn254_full``).
* ``BN254FourStepEngine`` — the same field transform at prover-scale
  degrees (``bn254_prover``, d up to 2^16): a four-step NTT of two √d-sized
  limb-GEMM stages on the 18-channel chain, re-entering F_p between them.
  One op = one cyclic evaluation of a row of d field elements.

Engines are pure-JAX modules; ``evaluate``/``reduce``/``e2e`` jit cleanly and
are dispatched by the Tier-1/Tier-2 schedulers.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import field as F
from repro.core import limb_gemm as G
from repro.core import ntt as NTT
from repro.core import rns as R


@dataclasses.dataclass(frozen=True)
class WorkloadClass:
    """Workload-class descriptor used by the scheduler for zone segregation."""

    name: str
    precision_zone: int    # limb count — MXU type-homogeneity class
    data_limbs: int
    tw_limbs: int
    n_channels: int


DILITHIUM = WorkloadClass("dilithium", precision_zone=3, data_limbs=3,
                          tw_limbs=3, n_channels=1)
BN254 = WorkloadClass("bn254", precision_zone=4, data_limbs=4, tw_limbs=4,
                      n_channels=9)
BN254_FULL = WorkloadClass("bn254_full", precision_zone=4, data_limbs=4,
                           tw_limbs=4, n_channels=18)
BN254_PROVER = WorkloadClass("bn254_prover", precision_zone=4, data_limbs=4,
                             tw_limbs=4, n_channels=18)
BN254_PROVER9 = WorkloadClass("bn254_prover9", precision_zone=4,
                              data_limbs=4, tw_limbs=4, n_channels=9)

CLASSES = {c.name: c for c in (DILITHIUM, BN254, BN254_FULL, BN254_PROVER,
                               BN254_PROVER9)}


def _fold_profile(plans, reduction: str, kappa: int | None,
                  d_tile: int | None) -> dict:
    """Static fold/window census of an engine's compiled program (one entry
    per channel plan; all channels share a plan shape).  Mirrors the window
    maths of :func:`repro.core.limb_gemm.staged_transform` exactly — the
    serve telemetry and HLO validator both consume this."""
    plan = plans[0]
    step = min(d_tile or plan.d_max, plan.d)
    if step > plan.d_max:
        raise ValueError(
            f"staging tile d_tile={step} exceeds the {plan.accum} per-pass "
            f"ceiling d_max={plan.d_max}")
    n_passes = math.ceil(plan.d / step)
    if reduction == "eager":
        windows_per_channel = n_passes
    else:
        c = min(plan.data_limbs, plan.tw_limbs)
        windows_per_channel = len(
            G.lazy_window_sizes(n_passes, step, c, plan.accum, kappa))
    return {
        "reduction": reduction,
        "kappa": kappa,
        "n_passes": n_passes,
        "n_channels": len(plans),
        "windows_per_channel": windows_per_channel,
        "n_folds": windows_per_channel * len(plans),
        "n_diag": plan.n_diag,
    }


class DilithiumEngine:
    """Forward negacyclic NTT over F_Q; exact end-to-end for all inputs."""

    wclass = DILITHIUM
    # Per launch: limb-GEMM transform stages and RNS-to-field reductions.
    transform_stages = 1
    field_reductions = 0

    def __init__(self, d: int, *, accum: G.AccumModel = "fp32_mantissa",
                 reduction: G.Reduction = "eager", kappa: int | None = None,
                 d_tile: int | None = None):
        self.d = d
        self.accum = accum
        self.reduction = G.check_reduction(reduction)
        self.kappa = kappa
        # Staging-pass tile override: None → the accumulator-window ceiling
        # d_max.  A smaller tile (e.g. the fp32-era 171) under int32_native
        # keeps the paper's pass structure while κ defers the folds.
        self.d_tile = d_tile
        # FIPS-204 negacyclic convention needs 2d | Q-1 (2-adicity 13 → d ≤
        # 4096); larger edge-polynomial degrees use the cyclic transform.
        self.negacyclic = (F.DILITHIUM_Q - 1) % (2 * d) == 0
        w = NTT.ntt_matrix(d, F.DILITHIUM_Q, negacyclic=self.negacyclic)
        self.plan = G.make_channel_plan(
            w, F.DILITHIUM_Q, data_limbs=3, tw_limbs=3, accum=accum)
        self.fold_profile = _fold_profile([self.plan], self.reduction, kappa,
                                          d_tile)
        self._device_planes = None

    @property
    def n_passes(self) -> int:
        return self.fold_profile["n_passes"]

    @property
    def n_diag(self) -> int:
        return self.plan.n_diag

    def device_planes(self):
        """Per-channel ``(w_planes, fused)`` twiddle tensors, uploaded to the
        device once per engine (dispatch fast path: retraces at new batch
        heights reuse these buffers instead of re-embedding host constants)."""
        if self._device_planes is None:
            self._device_planes = [G.plane_operands(self.plan)]
        return self._device_planes

    def evaluate(self, a_u32, *, kernel_fn=None, planes=None):
        """(N, d) uint32 -> (N, d) uint32 forward NTT (one op per row)."""
        with jax.named_scope("wzone_dilithium"), jax.named_scope("pzone_3limb"):
            y, self.last_stats = G.staged_transform(
                a_u32, self.plan, reduction=self.reduction, kappa=self.kappa,
                d_max=self.d_tile, kernel_fn=kernel_fn,
                planes=planes[0] if planes is not None else None)
        return y

    e2e = evaluate  # Dilithium op == the forward transform

    def oracle_np(self, a_np: np.ndarray) -> np.ndarray:
        w = NTT.ntt_matrix(self.d, F.DILITHIUM_Q, negacyclic=self.negacyclic)
        return NTT.matrix_ntt_oracle_np(a_np, w, F.DILITHIUM_Q)


class BN254Engine:
    """ERNS matrix transform + per-coefficient Montgomery reduction."""

    transform_stages = 1
    field_reductions = 1

    def __init__(self, d: int, *, accum: G.AccumModel = "fp32_mantissa",
                 reduction: G.Reduction = "eager", kappa: int | None = None,
                 d_tile: int | None = None, n_channels: int = 9,
                 p: int = F.BN254_FR, evaluation_matrix: np.ndarray | None = None):
        self.wclass = BN254 if n_channels == 9 else BN254_FULL
        self.d = d
        self.accum = accum
        self.reduction = G.check_reduction(reduction)
        self.kappa = kappa
        self.d_tile = d_tile
        self.chain = R.make_chain(n_channels, p=p)
        # CRT-consistent evaluation operand: residues of one integer matrix Ω.
        if evaluation_matrix is None:
            evaluation_matrix = NTT.ntt_matrix(d, p)  # F_p NTT twiddles
        self.omega = evaluation_matrix
        self.plans = []
        for m in self.chain.moduli:
            w_ch = (evaluation_matrix.astype(object) % m).astype(np.uint32)
            self.plans.append(G.make_channel_plan(
                w_ch, m, data_limbs=4, tw_limbs=4, accum=accum))
        self.fold_profile = _fold_profile(self.plans, self.reduction, kappa,
                                          d_tile)
        self._device_planes = None

    @property
    def n_channels(self) -> int:
        return len(self.chain.moduli)

    @property
    def n_passes(self) -> int:
        return self.fold_profile["n_passes"]

    @property
    def n_diag(self) -> int:
        return self.plans[0].n_diag

    def ingest(self, coeffs_np: np.ndarray):
        """Host object-int coefficients [..., d] -> (..., d, C) uint32."""
        return jnp.asarray(R.to_rns_np(coeffs_np, self.chain))

    def device_planes(self):
        """Per-channel ``(w_planes, fused)`` twiddle tensors, uploaded to the
        device once per engine (dispatch fast path: retraces at new batch
        heights reuse these buffers instead of re-embedding host constants)."""
        if self._device_planes is None:
            self._device_planes = [G.plane_operands(p) for p in self.plans]
        return self._device_planes

    def evaluate(self, a_res, *, kernel_fn=None, planes=None):
        """(N, d, C) uint32 residues -> (N, d, C) transformed residues."""
        outs = []
        self.last_stats = None
        with jax.named_scope("wzone_bn254"), jax.named_scope("pzone_4limb"):
            for ci, plan in enumerate(self.plans):
                with jax.named_scope(f"channel_{ci}"):
                    y, st = G.staged_transform(
                        a_res[..., ci], plan, reduction=self.reduction,
                        kappa=self.kappa, d_max=self.d_tile,
                        kernel_fn=kernel_fn,
                        planes=planes[ci] if planes is not None else None)
                outs.append(y)
                self.last_stats = st
        return jnp.stack(outs, axis=-1)

    def reduce(self, y_res):
        """(N, d, C) transformed residues -> (N, d, nred) field digits."""
        with jax.named_scope("wzone_bn254"), jax.named_scope("vpu_montgomery"):
            return R.rns_to_field(y_res, self.chain)

    def e2e(self, a_res, *, kernel_fn=None, planes=None):
        """The paper's BN254 op for N stacked tenant rows."""
        return self.reduce(self.evaluate(a_res, kernel_fn=kernel_fn,
                                         planes=planes))

    # --- host oracles ---------------------------------------------------------

    def oracle_eval_np(self, coeffs_np: np.ndarray) -> np.ndarray:
        """Exact bignum evaluation X_j = Σ a_i Ω_ij (object ints)."""
        return coeffs_np.astype(object) @ self.omega.astype(object)

    def in_envelope(self, coeffs_np: np.ndarray) -> bool:
        x = self.oracle_eval_np(coeffs_np)
        return int(np.max(x)) < self.chain.M


class BN254FourStepEngine:
    """Prover-scale cyclic transform over F_p as a four-step (Bailey) NTT.

    A dense d×d plan stops fitting a chip near d = 2^13; the four-step runs
    the transform as two limb-GEMM stages of √d-sized plans
    (:func:`repro.core.ntt.four_step_factors`).  Per channel, each stage is
    a :func:`repro.core.limb_gemm.staged_transform`.  A stage sums
    ``d1 · (p−1)²`` at most (2^516 at d = 2^16), inside the 18-channel
    chain's M = 2^527, but a stage fed an unreduced stage-1 output would
    not be, so the values re-enter F_p between the stages: ``rns_to_field``
    after stage 1, after the twiddle product and after stage 2, with
    ``field_to_rns`` re-encoding twice.  The output is ``(N, d, nred)``
    uint16 digit-12 of X_j = Σ_i a_i ω^(ij) mod p, natural order.

    ``n_channels=9`` (the paper's chain, M = 2^248 < p) is the class
    ``bn254_prover9``: the same program whose every row is out of its
    exactness envelope.
    """

    transform_stages = 2
    field_reductions = 3

    def __init__(self, d: int, *, accum: G.AccumModel = "fp32_mantissa",
                 reduction: G.Reduction = "eager", kappa: int | None = None,
                 d_tile: int | None = None, n_channels: int = 18,
                 p: int = F.BN254_FR):
        self.wclass = BN254_PROVER if n_channels == 18 else BN254_PROVER9
        self.d = d
        self.d1, self.d2 = NTT.four_step_split(d)
        self.accum = accum
        if G.check_reduction(reduction, kappa) != "eager":
            raise ValueError("the four-step engine folds eagerly: its two "
                             "stages' lazy windows are not validated")
        self.d_tile = d_tile
        self.chain = R.make_chain(n_channels, p=p)
        w1, w2, tw = NTT.four_step_factors(d, p)
        self.plans1 = self._plans(w1)
        self.plans2 = self.plans1 if self.d1 == self.d2 else self._plans(w2)
        self.twiddle = R.to_rns_np(tw, self.chain)           # (d2, d1, C)
        p1 = _fold_profile(self.plans1, reduction, kappa, d_tile)
        p2 = _fold_profile(self.plans2, reduction, kappa, d_tile)
        self.fold_profile = dict(
            p1, n_passes=p1["n_passes"] + p2["n_passes"],
            windows_per_channel=(p1["windows_per_channel"]
                                 + p2["windows_per_channel"]),
            n_folds=p1["n_folds"] + p2["n_folds"],
            contraction=self.d1 + self.d2,
            stage_passes={"fourstep_stage1": p1["n_passes"],
                          "fourstep_stage2": p2["n_passes"]})
        self._device_planes = None

    def _plans(self, w: np.ndarray) -> list:
        return [G.make_channel_plan((w % m).astype(np.uint32), m,
                                    data_limbs=4, tw_limbs=4,
                                    accum=self.accum)
                for m in self.chain.moduli]

    @property
    def n_channels(self) -> int:
        return len(self.chain.moduli)

    @property
    def n_passes(self) -> int:
        return self.fold_profile["n_passes"]

    def ingest(self, coeffs_np: np.ndarray):
        """Host object-int coefficients [..., d] -> (..., d, C) uint32."""
        return jnp.asarray(R.to_rns_np(coeffs_np, self.chain))

    def device_planes(self):
        """Both stages' per-channel plane pairs and the twiddle residues,
        uploaded to the device once per engine (stage 2 shares stage 1's
        planes when d1 = d2)."""
        if self._device_planes is None:
            planes = {"stage1": [G.plane_operands(p) for p in self.plans1],
                      "twiddle": jax.device_put(self.twiddle)}
            if self.plans2 is not self.plans1:
                planes["stage2"] = [G.plane_operands(p) for p in self.plans2]
            self._device_planes = planes
        return self._device_planes

    def _stage(self, x, plans, planes):
        """(rows, n, C) residues -> per-channel size-n transforms."""
        outs = []
        for ci, plan in enumerate(plans):
            with jax.named_scope(f"channel_{ci}"):
                y, self.last_stats = G.staged_transform(
                    x[..., ci], plan, d_max=self.d_tile,
                    planes=planes[ci] if planes is not None else None)
            outs.append(y)
        return jnp.stack(outs, axis=-1)

    def reduce(self, y_res):
        """(..., C) residues of values < M -> (..., nred) digits mod p."""
        with jax.named_scope("vpu_montgomery"):
            return R.rns_to_field(y_res, self.chain)

    def _renormalise(self, y_res):
        """Residues of a value < M -> residues of that value mod p, so that
        the next product stays inside the envelope."""
        digits = self.reduce(y_res)
        with jax.named_scope("rns_encode"):
            return R.field_to_rns(digits, self.chain)

    def e2e(self, a_res, *, planes=None):
        """(N, d, C) uint32 residues -> (N, d, nred) uint16 field digits.

        The reductions run on the flat (N·d, C) layout, so all three share
        one shape."""
        n, d, d1, d2 = a_res.shape[0], self.d, self.d1, self.d2
        c = self.n_channels
        if planes is None:
            p1, p2, tw = None, None, jnp.asarray(self.twiddle)
        else:
            p1, tw = planes["stage1"], planes["twiddle"]
            p2 = planes.get("stage2", p1)
        with jax.named_scope("wzone_bn254"), jax.named_scope("pzone_4limb"):
            with jax.named_scope("fourstep_stage1"):
                x = a_res.reshape(n, d1, d2, c).transpose(0, 2, 1, 3)
                y = self._stage(x.reshape(n * d2, d1, c), self.plans1,
                                p1)                          # [(n, i2), j1]
            z = self._renormalise(y.reshape(n * d, c)).reshape(n, d, c)
            with jax.named_scope("fourstep_twiddle"):
                z = F.mulmod_u32(z, tw.reshape(d, c), jnp.asarray(
                    np.array(self.chain.moduli, np.uint32)))
            z = self._renormalise(z.reshape(n * d, c))
            with jax.named_scope("fourstep_stage2"):
                z = z.reshape(n, d2, d1, c).transpose(0, 2, 1, 3)
                y = self._stage(z.reshape(n * d1, d2, c), self.plans2,
                                p2)                          # [(n, j1), j2]
            digits = self.reduce(y.reshape(n * d, c))
            with jax.named_scope("fourstep_transpose"):
                digits = digits.reshape(n, d1, d2, -1).transpose(0, 2, 1, 3)
                return digits.reshape(n, d, -1).astype(jnp.uint16)


@functools.lru_cache(maxsize=32)
def make_engine(name: str, d: int, accum: str = "fp32_mantissa",
                reduction: str = "eager", kappa: int | None = None,
                d_tile: int | None = None):
    kw = dict(accum=accum, reduction=reduction, kappa=kappa, d_tile=d_tile)
    if name == "dilithium":
        return DilithiumEngine(d, **kw)
    if name == "bn254":
        return BN254Engine(d, n_channels=9, **kw)
    if name == "bn254_full":
        return BN254Engine(d, n_channels=18, **kw)
    if name == "bn254_prover":
        return BN254FourStepEngine(d, n_channels=18, **kw)
    if name == "bn254_prover9":
        return BN254FourStepEngine(d, n_channels=9, **kw)
    raise KeyError(name)
