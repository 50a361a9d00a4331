#!/usr/bin/env python3
"""Bring-up smoke test: the crypto serving path on a TPU, checked bit for bit.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a four-chip host: multi-device paths

With one chip it runs four phases, one line each:

* device: platform, kind and count, as JAX reports them;
* kernels: each Pallas kernel compiled by Mosaic at real staging-pass
  widths, against its ``ref.py`` computed on the host;
* offline replay: ``serve_crypto`` with the HLO validator on the chip's
  compiled programs;
* online serving: ``serve_crypto_online`` on the paper's §7.4 Poisson trace
  (4,096 req/s, Dilithium:BN254 50:50, degrees U[64, 512]) under the CLI
  defaults and under the v5e configuration.  Every Dilithium row must equal
  ``DilithiumEngine.oracle_np``; every BN254 row must equal
  ``BN254Engine.reduce`` of host-bignum residues of the exact evaluation.

``--chips 4`` runs only the paths that exist across devices: the unpinned
co-scheduler's disjoint device groups and the device-parallel fleet, each
compared per tenant with a one-device run of the same trace.

The last line of standard output is one JSON object naming the device; it
is printed only when every phase passed.  Without a TPU the script exits
non-zero before any phase: it never falls back to the CPU.  Seconds it
prints are smoke timings of one run, not measurements.  Compiled programs
persist in ``$JAX_COMPILATION_CACHE_DIR`` or, when that is unset, in the
checkout's ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# The paper's §7.4 trace is PoissonTrace's default; 0.5 s of virtual time is
# about 2,000 requests.
TRACE = dict(rate_hz=4096.0, duration_s=0.5, seed=0)
CLI_DEFAULTS = dict(accum="fp32_mantissa")
V5E = dict(accum="int32_native", reduction_by_workload={"dilithium": "lazy"},
           d_tile=171, row_ladder_max=64, async_pipeline=True, donate=True)

# JAX's duration events for tracing, lowering and compiling (or fetching
# from the persistent cache) a program: their sum is the compile set-up.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, seconds, **_):
    if event in COMPILE_EVENTS:
        _compile_s[0] += seconds


def run_phase(name, fn, failed):
    """Run one phase; print its result line with its compile and wall
    seconds (smoke timings, not measurements)."""
    c0, t0 = _compile_s[0], time.perf_counter()
    try:
        detail, ok = fn(), True
    except Exception as e:
        traceback.print_exc()
        detail, ok = f"{type(e).__name__}: {e}", False
    if not ok:
        failed.append(name)
    print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail} "
          f"(smoke timing: compile {_compile_s[0] - c0:.1f}s, "
          f"wall {time.perf_counter() - t0:.1f}s)", flush=True)


def host_scope():
    """Run references on the host's CPU backend where JAX has one."""
    import jax
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


# --- phases ------------------------------------------------------------------

def kernels(seed=0):
    """Every Pallas kernel, compiled (interpret=False), against its ref."""
    import jax.numpy as jnp
    from repro.kernels import (diag_major, fused_ntt_tile, limb_matmul,
                               mont_fold, staging_passes)
    from repro.kernels.fused_ntt_tile.ref import fused_ntt_tile_ref
    from repro.kernels.limb_matmul.ref import limb_matmul_ref
    from repro.kernels.mont_fold.ref import mont_fold_ref

    rng = np.random.default_rng(seed)
    cases = bad = 0

    def compare(label, got, ref_fn):
        nonlocal cases, bad
        with host_scope():
            want = np.asarray(ref_fn())
        n_bad = int(np.sum(np.asarray(got) != want))
        cases += 1
        bad += n_bad
        print(f"  {label}: {'exact' if not n_bad else f'{n_bad} MISMATCHES'}",
              flush=True)

    for workload, sp in staging_passes().items():
        k, d, nd, m = sp["k"], sp["d"], sp["n_diag"], sp["modulus"]
        for n in (128, 3):          # a full row block, and a ragged one
            a = rng.integers(0, 256, (n, k), dtype=np.uint8)
            b3 = rng.integers(-128, 128, (k, d, nd), dtype=np.int8)
            b = b3.reshape(k, d * nd)
            diags = rng.integers(-2**31, 2**31, (n, d, nd), dtype=np.int32)
            for accum in ("int32_native", "fp32_mantissa"):
                compare(f"limb_matmul {workload} {accum} N={n} K={k} "
                        f"M={d * nd}",
                        limb_matmul(jnp.asarray(a), jnp.asarray(b),
                                    accum=accum),
                        lambda: limb_matmul_ref(a, b, accum))
                compare(f"fused_ntt_tile {workload} {accum} N={n} K={k} "
                        f"d={d}",
                        fused_ntt_tile(jnp.asarray(a),
                                       jnp.asarray(diag_major(b3)),
                                       modulus=m, n_diag=nd, accum=accum),
                        lambda: fused_ntt_tile_ref(a, b3, m, accum))
            compare(f"mont_fold {workload} N={n} d={d} n_diag={nd}",
                    mont_fold(jnp.asarray(diags), m),
                    lambda: mont_fold_ref(diags, m))
    if bad:
        raise AssertionError(f"{bad} mismatches over {cases} kernel cases")
    return f"{cases} kernel cases bit-exact against their refs"


class References:
    """Host references per request row, computed once per payload and
    shared by every run of the same trace."""

    def __init__(self):
        self._dilithium = {}

    def check(self, pairs) -> dict:
        """``pairs`` of (request, served row) -> row and mismatch counts."""
        import jax
        from repro.core import rns as R
        from repro.core import workloads as WK

        dil = [(r, row) for r, row in pairs if r.workload == "dilithium"]
        bn = [(r, row) for r, row in pairs if r.workload == "bn254"]
        todo: dict = {}
        for r, row in dil:
            key = (row.shape[0], r.coeffs.tobytes())
            if key not in self._dilithium:
                todo.setdefault(row.shape[0], {})[key] = r.coeffs
        for d, rows in todo.items():
            a = np.zeros((len(rows), d), np.uint32)
            for i, c in enumerate(rows.values()):
                a[i, :c.shape[0]] = c
            want = WK.DilithiumEngine(d).oracle_np(a)
            self._dilithium.update(zip(rows, want))
        dil_bad = sum(
            not np.array_equal(
                row, self._dilithium[(row.shape[0], r.coeffs.tobytes())])
            for r, row in dil)

        bn_bad = 0
        by_d: dict = {}
        for r, row in bn:
            by_d.setdefault(row.shape[0], []).append((r, row))
        for d, group in by_d.items():
            eng = WK.make_engine("bn254", d)
            res = np.zeros((len(group), d, eng.n_channels), np.uint32)
            for i, (r, _) in enumerate(group):
                res[i, :r.coeffs.shape[0]] = r.coeffs
            coeffs = R.from_rns_np(res, eng.chain)      # exact: < 2^31 < M
            exact = R.to_rns_np(eng.oracle_eval_np(coeffs), eng.chain)
            with host_scope():
                want = np.asarray(jax.jit(eng.reduce)(exact))
            got = np.stack([row for _, row in group])
            bn_bad += int(np.sum(np.any(got != want, axis=(1, 2))))
        return {"dilithium_rows": len(dil), "dilithium_mismatches": dil_bad,
                "bn254_rows": len(bn), "bn254_mismatches": bn_bad}


def offline_replay(refs):
    """``--mode crypto``: the HLO validator runs on the chip's compiled
    programs before first dispatch, and raises on any violation."""
    from repro.launch.serve import serve_crypto
    results, n_ops, dt = serve_crypto(duration_s=0.05, validate=True)
    pairs = [(r, res.outputs[r.tenant_id])
             for res in results for r in res.batch.requests]
    c = refs.check(pairs)
    if (c["dilithium_mismatches"] or c["bn254_mismatches"]
            or len(pairs) != n_ops):
        raise AssertionError(f"offline replay: {c}, {len(pairs)}/{n_ops} rows")
    return (f"{n_ops} tenant ops in {len(results)} batches, HLO-validated; "
            f"{c}; serving {dt:.1f}s")


def online(refs, label, config):
    """``--mode crypto-online`` on the §7.4 trace: every admitted request
    served, every row equal to its host reference."""
    from repro.launch.serve import serve_crypto_online
    load, snap, dt = serve_crypto_online(**TRACE, **config)
    admitted = [h for h in load.handles if not h.rejected]
    unserved = sum(not h.done() for h in admitted)
    c = refs.check([(h.request, h.result()) for h in admitted
                    if h.done()])
    if (unserved or c["dilithium_mismatches"] or c["bn254_mismatches"]
            or c["bn254_rows"] < 32):
        raise AssertionError(f"{label}: {unserved} unserved, {c}")
    return (f"{label}: served {len(admitted) - unserved}/{len(admitted)} "
            f"admitted ({len(load.rejected)} rejected), "
            f"{snap['dispatch']['dispatches']} launches; {c}; "
            f"serving {dt:.1f}s, compiles included")


def four_chips(refs):
    """The unpinned co-scheduler (dilithium on devices 0-1, bn254 on 2-3,
    rows sharded) and the device-parallel fleet (one host slice per
    device), each bit-equal per tenant to a one-device run."""
    import jax
    from repro.core.scheduler.coscheduler import (SliceCoScheduler,
                                                  default_row_ladder)
    from repro.launch.serve import serve_crypto_cluster, serve_crypto_online

    devs = jax.devices()
    # One ladder rung: every device set compiles its own programs, so the
    # rung count multiplies the compile set-up by the number of sets.
    config = dict(V5E, row_ladder_max=8, validate=False)

    def cos(devices=None):
        return SliceCoScheduler(
            devices=devices, accum=config["accum"],
            reduction_by_workload=config["reduction_by_workload"],
            d_tile=config["d_tile"], row_ladder=default_row_ladder(8),
            donate=config["donate"])

    one_cos, groups_cos = cos(devices=[devs[0].id]), cos()
    one, _, _ = serve_crypto_online(**TRACE, **config, coscheduler=one_cos)
    c = refs.check([(h.request, h.result()) for h in one.handles
                    if h.done() and not h.rejected])
    if c["dilithium_mismatches"] or c["bn254_mismatches"]:
        raise AssertionError(f"one device: {c}")
    groups, _, _ = serve_crypto_online(**TRACE, **config,
                                       coscheduler=groups_cos)
    fleet, fsnap, _ = serve_crypto_cluster(hosts=4, device_parallel=True,
                                           **TRACE, **config)

    want_groups = {"dilithium": (0, 1), "bn254": (2, 3)}
    have_groups = {w: groups_cos.device_ids(w) for w in want_groups}
    if have_groups != {w: tuple(devs[i].id for i in ids)
                       for w, ids in want_groups.items()}:
        raise AssertionError(f"device groups {have_groups}")
    for (w, _), planes in groups_cos._planes.items():
        for leaf in jax.tree_util.tree_leaves(planes):
            if leaf.devices() != set(groups_cos._meshes[w].devices.flat):
                raise AssertionError(f"{w} planes on {leaf.devices()}")
    if fsnap["devices"]["distinct"] != 4:
        raise AssertionError(f"fleet devices {fsnap['devices']}")

    out = []
    for label, run in (("device groups", groups), ("fleet", fleet)):
        if set(run.outputs) != set(one.outputs):
            raise AssertionError(f"{label}: tenant sets differ")
        bad = sum(not np.array_equal(run.outputs[t], one.outputs[t])
                  for t in one.outputs)
        if bad:
            raise AssertionError(f"{label}: {bad} tenants differ")
        out.append(f"{label}: {len(run.outputs)} tenants bit-equal")
    return (f"one device {c}; " + "; ".join(out)
            + f"; fleet devices {fsnap['devices']['per_host']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-device paths")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {len(devs)} "
              f"{devs[0].platform} device(s) ({kind!r})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devs)}", file=sys.stderr)
        return 2
    print(f"[PASS] device: platform={devs[0].platform} kind={kind} "
          f"count={len(devs)}", flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.serve import enable_compilation_cache
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    failed: list = []
    refs = References()
    if args.chips == 4:
        run_phase("four chips", lambda: four_chips(refs), failed)
    else:
        run_phase("kernels", kernels, failed)
        run_phase("offline replay", lambda: offline_replay(refs), failed)
        run_phase("online, CLI defaults",
                  lambda: online(refs, "fp32_mantissa/eager/sync",
                                 CLI_DEFAULTS), failed)
        run_phase("online, v5e configuration",
                  lambda: online(refs, "int32_native/lazy dilithium/async",
                                 V5E), failed)
    print(f"total compile set-up {_compile_s[0]:.1f}s (smoke timing)",
          flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
