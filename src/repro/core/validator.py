"""Post-hoc Structural HLO Validator (paper §6.3).

Intercepts the lowered + compiled module just prior to dispatch and statically
asserts the separation invariants against the *stock* XLA output:

  V1 (Invariant 5.1, strict reduction ordering): within each staged transform,
      every pass-k VPU fold is emitted after pass-k's MXU dot and before
      pass-(k+1)'s MXU dot — no reduction inside an open summation window.
      A summation window is keyed by its stage (``fourstep_stage{s}`` of a
      four-step program), channel and pass, so the same pass index of two
      stages is never taken for one window.
  V2 (barrier survival): the lowered module carries one
      ``optimization_barrier`` per adjacent staging-pass pair — counted per
      stage scope when the program has several.
  V3 (workload-zone fusion separation): no fused computation in the optimized
      HLO mixes ops from two distinct ``wzone_*`` scopes.
  V4 (precision-zone homogeneity): no fused computation mixes distinct
      ``pzone_*`` scopes (e.g. 3-limb Dilithium with 4-limb BN254 blocks).
  V5 (disjoint addressing): no input/output buffer donation aliases tensors
      across distinct workload zones.
  V6 (κ-window fold survival, lazy modules): the optimized module carries
      exactly one ``vpu_fold_lazy`` site per deferral window (scope
      ``lazy_window_{i}``, qualified by channel for multi-channel engines)
      and **zero** eager per-pass folds — XLA must not have re-fused the
      deferred schedule back to the eager one (paper §7.2.1).
  V7 (single fold per window, lazy modules): in the trace-order-faithful
      lowered module, each window scope contains exactly one fold's worth of
      modular-reduction ops (``n_diag`` remainders) — a window that reduces
      twice is an eager fold hiding under a lazy label.

Any violation raises :class:`ValidationError` (dispatch abort) and carries the
offending subgraph snippet for triage.  The validator also returns the static
op census (dots, folds, barriers) used for the κ lazy-amortisation analysis
(paper §7.2.1).
"""
from __future__ import annotations

import dataclasses
import re

import jax

WZONE_RE = re.compile(r"wzone_[A-Za-z0-9_]+")
PZONE_RE = re.compile(r"pzone_[A-Za-z0-9_]+")
OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# The transform stage of a four-step program; a dense program has one,
# unnamed.
STAGE_RE = re.compile(r"fourstep_stage\d+")
# Summation-window key: stage, channel and pass.
WINDOW_RE = re.compile(r"((?:fourstep_stage\d+/)?(?:channel_\d+/)?"
                       r"staging_pass_\d+)")
# Window key carries the channel qualifier so BN254's per-channel windows
# with the same index stay distinct.
LAZY_WIN_RE = re.compile(r"(?:channel_\d+/)?lazy_window_\d+(?=/vpu_fold_lazy)")
EAGER_FOLD_RE = re.compile(r"staging_pass_\d+/vpu_fold(?!_lazy)")


class ValidationError(AssertionError):
    def __init__(self, violations):
        self.violations = violations
        super().__init__("HLO structural validation failed:\n" +
                         "\n".join(f"  [{v[0]}] {v[1]}" for v in violations))


@dataclasses.dataclass
class ValidationReport:
    ok: bool
    violations: list
    n_barriers: int
    n_dots: int
    n_folds: int
    zones: set
    precision_zones: set

    def raise_if_failed(self):
        if not self.ok:
            raise ValidationError(self.violations)


def _entry_computation(hlo_text: str) -> str:
    """The ENTRY computation block of an optimized HLO module."""
    idx = hlo_text.find("ENTRY ")
    return hlo_text[idx:] if idx >= 0 else hlo_text


def _fusion_blocks(hlo_text: str) -> list[str]:
    """All non-entry computation bodies (fused computations and callees)."""
    blocks, cur, inside = [], [], False
    for line in hlo_text.splitlines():
        if line.startswith("%") and line.rstrip().endswith("{"):
            inside, cur = True, [line]
        elif inside and line.startswith("}"):
            cur.append(line)
            blocks.append("\n".join(cur))
            inside = False
        elif inside:
            cur.append(line)
    return blocks


def validate_module(lowered_text: str, compiled_text: str, *,
                    expected_passes: int | dict | None = None,
                    expect_eager: bool = True,
                    expected_windows: int | None = None,
                    n_diag: int | None = None) -> ValidationReport:
    violations = []

    # --- V6/V7: κ-window fold structure of a lazy module ----------------------
    if expected_windows is not None:
        win_scopes = set(LAZY_WIN_RE.findall(compiled_text))
        if len(win_scopes) != expected_windows:
            violations.append((
                "V6", f"{len(win_scopes)} deferred-fold windows in the "
                f"optimized module, expected {expected_windows} "
                f"(windows seen: {sorted(win_scopes)[:8]})"))
        eager_folds = set(EAGER_FOLD_RE.findall(compiled_text))
        if eager_folds:
            violations.append((
                "V6", f"lazy module contains eager per-pass folds "
                f"{sorted(eager_folds)[:4]} — XLA (or the trace) re-fused "
                f"the deferred schedule back to eager"))
        if n_diag is not None:
            # Count the modular-reduction instructions each window scope
            # carries in the optimized module (op_name metadata survives
            # fusion).  One fold reduces exactly n_diag diagonals → n_diag
            # remainder instructions per window; 2·n_diag means a second fold
            # is hiding under the window's lazy label, 0 means the fold is
            # missing or not the elementwise form this check audits (kernel
            # fold_fn programs lower to custom-calls — don't pass n_diag for
            # those).  Every discovered window scope is checked, so a window
            # with no remainders at all is flagged, not skipped.
            per_window: dict[str, int] = {}
            for ln in compiled_text.splitlines():
                if not re.search(r"= \S+ remainder\(", ln):
                    continue
                mo = OPNAME_RE.search(ln)
                name = mo.group(1) if mo else ""
                wm = LAZY_WIN_RE.search(name)
                if wm:
                    per_window[wm.group(0)] = per_window.get(wm.group(0), 0) + 1
            for win in sorted(win_scopes | set(per_window)):
                count = per_window.get(win, 0)
                if count != n_diag:
                    violations.append((
                        "V7", f"window {win} carries {count} modular-reduction "
                        f"ops (expected {n_diag} — exactly one fold per "
                        f"window)"))

    # --- V1/V2 read the lowered module's MLIR loc table ----------------------
    # (debug_info=True) to name each op's scope path.
    low_lines = lowered_text.splitlines()
    loc_names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', lowered_text,
                                re.M))
    has_locs = bool(loc_names)

    def _scope(ln: str) -> str:
        m = re.search(r"loc\((#loc\d+)\)", ln)
        return loc_names.get(m.group(1), "") if m else ""

    # --- V2: barrier survival in the lowered module --------------------------
    # ``expected_passes``: one count for the program, or {stage scope: count}
    # for a program of several transform stages, each checked on its own
    # barriers where the loc table names them.
    n_barriers = len(re.findall(r"optimization_barrier", lowered_text))
    if expect_eager and expected_passes:
        want = (expected_passes if isinstance(expected_passes, dict)
                else {"": expected_passes})
        if len(want) > 1 and has_locs:
            have: dict = {}
            for ln in low_lines:
                if "stablehlo.optimization_barrier" in ln:
                    m = STAGE_RE.search(_scope(ln))
                    key = m.group(0) if m else ""
                    have[key] = have.get(key, 0) + 1
        else:
            have = {"": n_barriers}
            want = {"": sum(p - 1 for p in want.values()) + 1}
        for stage, passes in want.items():
            if passes > 1 and have.get(stage, 0) < passes - 1:
                violations.append((
                    "V2", f"{have.get(stage, 0)} optimization_barriers for "
                    f"{passes} staging passes{f' in {stage}' if stage else ''}"
                    f" (need >= {passes - 1})"))

    # --- V1: strict reduction ordering (program order of the traced module) --
    # The lowered StableHLO preserves trace emission order (no hoisting yet):
    # between any two consecutive MXU summation windows (dot_general / pallas
    # kernel calls) there must be >= 1 modular-reduction op (stablehlo.remainder
    # from the fold) — i.e. no reduction is deferred into the next open
    # summation, and no summation starts before the previous fold ran.  Only
    # *pointwise-phase* dots count as summation windows — the
    # Montgomery/base-extension digit matmuls legitimately run fold-free
    # (they ARE the reduction).
    dot_pat = re.compile(
        r"stablehlo\.dot_general|stablehlo\.custom_call.*(tpu_custom_call|pallas)")

    def _window_key(ln: str):
        """None if not a pointwise dot; else the summation-window scope key
        (stage/channel/pass) — several partial-product dots inside one pass
        share a window."""
        if not dot_pat.search(ln):
            return None
        if not has_locs:
            return "?"
        name = _scope(ln)
        if name and "mxu_pointwise" not in name:
            return None  # Montgomery/base-extension matmul — not a window
        wm = WINDOW_RE.search(name)
        return wm.group(1) if wm else (name or "?")

    dots = [(i, _window_key(ln)) for i, ln in enumerate(low_lines)]
    dots = [(i, k) for i, k in dots if k is not None]
    rem_idx = [i for i, ln in enumerate(low_lines)
               if "stablehlo.remainder" in ln or "call @remainder" in ln]
    barrier_idx = [i for i, ln in enumerate(low_lines)
                   if "optimization_barrier" in ln]
    if expect_eager and len(dots) > 1:
        for (a, ka), (b, kb) in zip(dots, dots[1:]):
            if ka == kb:
                continue  # same summation window (multi-plane partials)
            n_rem = sum(1 for r in rem_idx if a < r < b)
            if n_rem == 0:
                violations.append((
                    "V1", f"no VPU reduction between summation windows "
                    f"{ka}→{kb} at lowered lines {a}..{b} (open-summation "
                    f"fold violation)"))

    # --- census over the optimized entry computation --------------------------
    entry = _entry_computation(compiled_text)
    dots, folds = [], []
    for i, ln in enumerate(entry.splitlines()):
        mo = OPNAME_RE.search(ln)
        if not mo:
            continue
        op_name = mo.group(1)
        if "mxu_pointwise" in op_name and ("dot" in ln or "fusion" in ln):
            dots.append(i)
        if "vpu_fold" in op_name:
            folds.append(i)

    # --- V3/V4: fusion zone separation ---------------------------------------
    zones_seen, pzones_seen = set(), set()
    for block in _fusion_blocks(compiled_text) + [entry]:
        is_fusion = block.lstrip().startswith("%fused")
        wz = set(WZONE_RE.findall(block))
        pz = set(PZONE_RE.findall(block))
        zones_seen |= wz
        pzones_seen |= pz
        if is_fusion:
            if len(wz) > 1:
                violations.append((
                    "V3", f"fused computation mixes workload zones {sorted(wz)}: "
                    f"{block.splitlines()[0][:120]}"))
            if len(pz) > 1:
                violations.append((
                    "V4", f"fused computation mixes precision zones {sorted(pz)}:"
                    f" {block.splitlines()[0][:120]}"))

    # --- V5: no cross-zone buffer donation ------------------------------------
    alias = re.findall(r"input_output_alias=\{[^}]*\}", compiled_text)
    if alias and len(zones_seen) > 1:
        # donation is allowed, but only within a single-zone module
        violations.append((
            "V5", f"buffer donation present in a multi-zone module: {alias[0][:120]}"))

    return ValidationReport(
        ok=not violations, violations=violations, n_barriers=n_barriers,
        n_dots=len(dots), n_folds=len(folds), zones=zones_seen,
        precision_zones=pzones_seen)


def validate_fn(fn, *args, expected_passes: int | dict | None = None,
                expect_eager: bool = True, expected_windows: int | None = None,
                n_diag: int | None = None,
                donate_argnums=()) -> ValidationReport:
    """Lower + compile ``fn`` and run the structural validator on both texts.

    ``expected_windows``/``n_diag`` arm the lazy-mode V6/V7 checks (pass
    ``expect_eager=False`` alongside — a κ-amortised program intentionally
    defers folds out of the per-pass schedule V1/V2 police)."""
    lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    low_txt = lowered.as_text(debug_info=True)
    return validate_module(low_txt, lowered.compile().as_text(),
                           expected_passes=expected_passes,
                           expect_eager=expect_eager,
                           expected_windows=expected_windows,
                           n_diag=n_diag)


def fold_census(fn, *args) -> dict:
    """Static op census for the κ analysis (paper §7.2.1): counts distinct
    VPU-fold scheduling sites in the compiled module — one per staging pass
    under the eager discipline, one total under the lazy/MORPH discipline."""
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    rep = validate_module(lowered.as_text(), compiled.as_text(),
                          expect_eager=False)
    txt = compiled.as_text()
    pass_folds = set(re.findall(r"staging_pass_(\d+)/vpu_fold", txt))
    lazy_windows = set(LAZY_WIN_RE.findall(txt))
    # κ-window scopes when present; plain vpu_fold_lazy (scan form) counts 1.
    n_lazy = len(lazy_windows) or (1 if "vpu_fold_lazy" in txt else 0)
    n_fold_ops = len(re.findall(r"vpu_fold", txt))
    return {"n_dots": rep.n_dots,
            "n_fold_scopes": len(pass_folds) + n_lazy,
            "n_lazy_windows": len(lazy_windows),
            "n_fold_tagged_ops": n_fold_ops, "n_barriers": rep.n_barriers}
