"""Closed-loop dispatch: adaptive occupancy controller, λ-priced merge
holdback, depth-k launch ring, ladder validation, perf-report diffing, and
the persistent compile cache.

The acceptance obligations of the closed-loop PR live here: the controller
must recover M occupancy above the static floor under a drifting arrival
rate, a held batch must never breach the admission-visible SLO, a depth-k
drain must retire every in-flight launch group (cluster barrier included),
and the whole control plane must stay bit-for-bit equal to the static
offline replay.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax

from repro.core import field as F
from repro.core.scheduler import TenantRequest
from repro.core.scheduler.coscheduler import (MIN_ROW_TILE, SliceCoScheduler,
                                              validate_row_ladder)
from repro.launch.serve import (serve_crypto, serve_crypto_cluster,
                                serve_crypto_online)
from repro.serve import (CryptoServer, LoadGenerator, ServeConfig,
                         compilation_cache_dir, enable_compilation_cache)
from repro.serve.controller import AdaptiveController

RNG = np.random.default_rng(31)

LADDER = (4, 8, 16)      # small rungs keep the CPU compile budget low

# One laddered co-scheduler for the whole module: every server (and the
# offline replays) reuses its compiled-program cache, so this suite pays
# for each (workload, d_bucket, rung) program once.
COS = SliceCoScheduler(merge=True, row_ladder=LADDER)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dil_request(tid, d=64, t=0.0):
    coeffs = np.asarray(RNG.integers(0, F.DILITHIUM_Q, d, dtype=np.uint64),
                        np.uint32)
    return TenantRequest(tid, "dilithium", d, t, coeffs)


def _cfg(**kw):
    kw.setdefault("validate", False)
    kw.setdefault("n_c", 4)
    kw.setdefault("max_age_s", 0.002)
    kw.setdefault("merge_dispatch", True)
    kw.setdefault("row_ladder_max", LADDER[-1])
    return ServeConfig(**kw)


def _run_trace(trace, **kw):
    server = CryptoServer(_cfg(**kw), coscheduler=COS)
    load = LoadGenerator(trace, attach=False).run(server)
    assert not load.rejected
    return server, load


# --- satellite: row-ladder construction validation ------------------------------

def test_row_ladder_rejects_non_monotonic():
    with pytest.raises(ValueError, match="strictly increasing"):
        SliceCoScheduler(row_ladder=(16, 8, 32))
    with pytest.raises(ValueError, match="strictly increasing"):
        validate_row_ladder((8, 4))


def test_row_ladder_rejects_duplicates():
    with pytest.raises(ValueError, match="duplicate rung 8"):
        SliceCoScheduler(row_ladder=(4, 8, 8, 16))


def test_row_ladder_rejects_sub_tile_rungs():
    with pytest.raises(ValueError, match="minimum M-tile"):
        SliceCoScheduler(row_ladder=(1, 8, 16))
    with pytest.raises(ValueError, match="minimum M-tile"):
        validate_row_ladder((0,))
    with pytest.raises(ValueError, match="at least one rung"):
        validate_row_ladder(())
    assert validate_row_ladder((MIN_ROW_TILE, 8)) == (MIN_ROW_TILE, 8)


# --- config validation ----------------------------------------------------------

def test_serve_config_cross_field_validation():
    with pytest.raises(ValueError, match="inflight_depth"):
        CryptoServer(_cfg(inflight_depth=0))
    with pytest.raises(ValueError, match="async_pipeline"):
        CryptoServer(_cfg(inflight_depth=2))          # ring needs async
    with pytest.raises(ValueError, match="controller"):
        CryptoServer(_cfg(holdback_lambda=1.0))       # pricing needs the model
    with pytest.raises(ValueError, match="merge_dispatch"):
        CryptoServer(_cfg(holdback_lambda=1.0, controller=True,
                          merge_dispatch=False))
    with pytest.raises(ValueError, match="holdback_lambda"):
        CryptoServer(_cfg(holdback_lambda=-0.5, controller=True))


def test_controller_parameter_validation():
    kw = dict(ladder=LADDER, n_c=4, max_age_s=0.002)
    with pytest.raises(ValueError, match="alpha"):
        AdaptiveController(alpha=0.0, **kw)
    with pytest.raises(ValueError, match="gain"):
        AdaptiveController(gain=0.0, **kw)
    with pytest.raises(ValueError, match="ladder"):
        AdaptiveController(ladder=(), n_c=4, max_age_s=0.002)


# --- controller unit behaviour --------------------------------------------------

def test_controller_bounds_and_rung_snap():
    ctl = AdaptiveController(ladder=LADDER, n_c=4, max_age_s=0.002,
                             slo_deadline_s=0.05, holdback_slo_fraction=0.5)
    key = ("dilithium", 64)
    assert ctl.target_rows(key) == 4          # floor = n_c
    assert ctl.max_age_s(key) == 0.002        # initial = static value
    # age ceiling is SLO-capped: ≤ fraction × deadline
    assert ctl.max_age_ceil_s <= 0.5 * 0.05 + 1e-12
    # rung snapping clamps to [n_c, ladder top]
    assert ctl._snap_rung(1) == 4
    assert ctl._snap_rung(9) == 16
    assert ctl._snap_rung(1000) == 16


def test_controller_starving_raises_age_overload_lowers_it():
    ctl = AdaptiveController(ladder=LADDER, n_c=4, max_age_s=0.002,
                             gain=0.5, alpha=1.0)
    key = ("dilithium", 64)
    # low fill, shallow queue → starving → age grows toward the ceiling
    ctl.observe_dispatch(key, live_rows=4, queue_depth=0, now=0.0)
    assert ctl.max_age_s(key) == pytest.approx(0.003)
    # deep backlog → overloaded → age shrinks toward the floor, and the
    # backlog itself raises the target rung
    ctl.observe_dispatch(key, live_rows=4, queue_depth=200, now=0.01)
    assert ctl.max_age_s(key) < 0.003
    assert ctl.target_rows(key) == LADDER[-1]
    # cluster depth folds into the setpoint even when the local queue is
    # shallow (gossip says merge partners are en route)
    ctl2 = AdaptiveController(ladder=LADDER, n_c=4, max_age_s=0.002,
                              alpha=1.0)
    ctl2.observe_dispatch(key, live_rows=4, queue_depth=0, now=0.0,
                          cluster_depth=64.0)
    assert ctl2.target_rows(key) == LADDER[-1]
    assert ctl2.snapshot()["cluster_depth_max"] == 64.0


# --- tentpole: convergence under a drifting rate --------------------------------

def _drifting_requests():
    """Deterministic two-phase stream: sparse (400 req/s) then dense
    (8,000 req/s) — the drift that mistunes any static close policy."""
    reqs, t, tid = [], 0.0, 0
    for _ in range(30):                       # phase A: gap 2.5 ms
        reqs.append(_dil_request(tid, 64, t))
        tid += 1
        t += 0.0025
    for _ in range(370):                      # phase B: gap 0.125 ms
        reqs.append(_dil_request(tid, 64, t))
        tid += 1
        t += 0.000125
    return reqs


def test_controller_converges_above_static_m_occupancy_floor():
    """Acceptance: under a drifting Poisson-like rate the m-fill EWMA
    recovers above the static floor (n_c / N_c_max) — the controller grows
    the target rung and age window until launches are tall again."""
    trace = _drifting_requests()       # one trace, byte-identical both runs
    static_srv, static_load = _run_trace(trace, async_pipeline=True)
    adaptive_srv, adaptive_load = _run_trace(trace, async_pipeline=True,
                                             controller=True)
    static_snap = static_srv.telemetry.snapshot()
    adaptive_snap = adaptive_srv.telemetry.snapshot()
    floor = 4 / 128                           # n_c / n_c_max
    cls = adaptive_snap["controller"]["classes"]["dilithium/64"]
    assert cls["target_rows"] == LADDER[-1]   # rung climbed off the floor
    assert cls["max_age_s"] > 0.002           # age grew to fill the window
    assert cls["m_occupancy_ewma"] > 1.5 * floor
    # the static path stays pinned at the floor the paper measures
    assert static_snap["dispatch"]["m_occupancy_mean"] == pytest.approx(
        floor, rel=0.35)
    assert (adaptive_snap["dispatch"]["m_occupancy_mean"]
            > 1.5 * static_snap["dispatch"]["m_occupancy_mean"])
    # fewer, taller launches — same rows
    assert (adaptive_snap["dispatch"]["dispatches"]
            < static_snap["dispatch"]["dispatches"])
    # and bit-for-bit the same per-tenant results
    assert set(adaptive_load.outputs) == set(static_load.outputs)
    for tid, row in static_load.outputs.items():
        np.testing.assert_array_equal(adaptive_load.outputs[tid], row)


# --- tentpole: holdback SLO safety ----------------------------------------------

def _bursty_requests():
    """2-row bursts every 4 ms (each closes by age below target) with two
    long 30 ms silences that strand a held batch past its priced window."""
    reqs, t, tid = [], 0.0, 0
    for burst in range(40):
        reqs.append(_dil_request(tid, 64, t))
        reqs.append(_dil_request(tid + 1, 64, t + 0.0002))
        tid += 2
        t += 0.030 if burst in (15, 31) else 0.004
    return reqs


def test_holdback_audited_and_never_breaches_slo():
    """Acceptance: λ-holdback trades p50 for M fill but the SLO gate's
    deadline survives — no held batch may push the admission-visible
    queue-wait p99 past the deadline, and every hold is audited as exactly
    one win, loss, or drain flush."""
    slo = 0.05
    server, load = _run_trace(
        _bursty_requests(), async_pipeline=True, controller=True,
        holdback_lambda=5.0, slo_deadline_s=slo, holdback_slo_fraction=0.5)
    snap = server.telemetry.snapshot()
    hb = snap["holdback"]
    assert hb["held"] >= 3, hb
    assert hb["wins"] >= 1, hb
    assert hb["losses"] >= 1, hb
    assert hb["wins"] + hb["losses"] + hb["flushed"] == hb["held"], hb
    # pricing bound: no realised hold may exceed its SLO share
    assert hb["hold_s_max"] <= 0.5 * slo + 1e-9, hb
    # the admission-visible p99 (queue wait, virtual clock) survives
    assert snap["queue_wait"]["p99_s"] <= slo, snap["queue_wait"]
    assert all(h.done() and not h.rejected for h in load.handles)


def test_holdback_win_merges_partner_into_one_launch():
    """A predicted partner arriving inside the window merges with the held
    batch into one tall launch (the M-fill win the holdback pays p50 for)."""
    server, _ = _run_trace(_bursty_requests(), async_pipeline=True,
                           controller=True, holdback_lambda=5.0,
                           slo_deadline_s=0.05)
    snap = server.telemetry.snapshot()
    assert snap["holdback"]["wins"] >= 1
    assert snap["dispatch"]["merged_dispatches"] >= 1
    assert any(r.n_batches > 1 for r in server.telemetry.dispatches)


# --- tentpole: depth-k launch ring ----------------------------------------------

def test_ring_holds_k_flights_and_drain_retires_all():
    """inflight_depth = 3 with every submit closing a batch: the ring fills
    to exactly k outstanding launch groups, and drain retires them all."""
    server = CryptoServer(_cfg(n_c=1, async_pipeline=True, inflight_depth=3),
                          coscheduler=COS)
    handles = [server.submit(_dil_request(i, 64, i * 1e-4), now=i * 1e-4)
               for i in range(6)]
    # every submit launched a 1-row batch; the ring holds the newest 3
    assert server.inflight_groups == 3
    assert sum(h.done() for h in handles) == 3     # oldest 3 gathered
    server.drain(0.01)
    assert server.inflight_groups == 0
    assert all(h.done() for h in handles)
    eng = server.cos.engine_for("dilithium", 64)
    for h in handles:
        iso = np.zeros((1, 64), np.uint32)
        iso[0] = h.request.coeffs
        np.testing.assert_array_equal(h.result(), eng.oracle_np(iso)[0])


def test_ring_splits_per_class_and_quiesce_retires_cluster_wide():
    """Bursty multi-class closes ride the ring concurrently (one flight per
    workload class), and the cluster drain barrier leaves zero in-flight
    groups on any host."""
    server = CryptoServer(_cfg(async_pipeline=True, inflight_depth=2,
                               max_age_s=0.002), coscheduler=COS)
    now = 0.0
    for i in range(3):                        # 3 rows in each of 2 classes
        server.submit(_dil_request(10 + i, 64, now), now=now)
        server.submit(_dil_request(20 + i, 100, now), now=now)
    server.pump(0.002)                        # age-close both classes at once
    assert server.inflight_groups == 2        # one flight per class in flight
    server.drain(0.003)
    assert server.inflight_groups == 0

    # cluster barrier: every host's ring must be empty after drain
    trace = [_dil_request(i, 64, i * 0.0002) for i in range(40)]
    load, snap, _ = serve_crypto_cluster(
        hosts=2, trace=trace, validate=False, n_c=4, max_age_s=0.002,
        merge_dispatch=True, row_ladder_max=LADDER[-1], async_pipeline=True,
        inflight_depth=2, controller=True,
        coscheduler_factory=lambda h: COS)
    bar = snap["drain_barrier"]
    assert bar["complete"] and bar["inflight_groups"] == 0
    assert all(h.done() and not h.rejected for h in load.handles)


def test_ring_busy_class_cannot_starve_quiet_class():
    """A class that keeps launching must not pin another class's in-flight
    results in the ring: the quiet class's oldest flight is materialised at
    the next serving event it doesn't launch into."""
    server = CryptoServer(_cfg(n_c=1, async_pipeline=True, inflight_depth=2),
                          coscheduler=COS)
    hb = server.submit(_dil_request(0, 100, 0.0), now=0.0)   # class (dil, 128)
    assert not hb.done()                   # in flight, ring not over depth
    ha = [server.submit(_dil_request(1 + i, 64, 1e-4 * (i + 1)),
                        now=1e-4 * (i + 1)) for i in range(4)]
    # every submit launched class (dil, 64); the (dil, 128) flight was
    # gathered at the first event it sat out — no drain needed
    assert hb.done()
    server.drain(0.01)
    assert server.inflight_groups == 0
    assert all(h.done() for h in ha)


def test_controller_consumes_class_local_depth_not_global():
    """The controller's queue model must see the class's own backlog — a
    busy neighbour class's pending rows must not inflate the depth EWMA
    (which would snap the idle class's target rung to the ladder top)."""
    server = CryptoServer(_cfg(controller=True), coscheduler=COS)
    for i in range(3):                     # 3 rows pile up in (dil, 64)
        server.submit(_dil_request(i, 64, 0.0), now=0.0)
    for i in range(4):                     # (dil, 128) closes full → dispatch
        server.submit(_dil_request(10 + i, 100, 0.0), now=0.0)
    assert server.batcher.depth == 3       # the neighbour backlog is global…
    cls = server.telemetry.snapshot()["controller"]["classes"]["dilithium/128"]
    assert cls["updates"] == 1
    assert cls["depth_ewma"] == 0.0        # …but this class saw its own: 0
    server.drain(0.01)


# --- tentpole: replay parity (single host + N=2 cluster) ------------------------

def _parity_kw(seed):
    return dict(duration_s=0.01, rate_hz=1024, seed=seed, d_uniform=256)


def test_closed_loop_serving_matches_offline_replay_bitforbit():
    """Acceptance: controller + holdback + depth-k ring through the full
    online runtime equals the static-config offline replay bit-for-bit —
    single host and a 2-host cluster with the distributed drain barrier."""
    kw = _parity_kw(29)
    offline_results, n_ops, _ = serve_crypto(validate=False, coscheduler=COS,
                                             **kw)
    offline = {}
    for res in offline_results:
        offline.update(res.outputs)
    COS.drain_dispatch_log()      # keep replay launches out of serve telemetry

    load, snap, _ = serve_crypto_online(
        max_age_s=0.002, validate=False, merge_dispatch=True,
        row_ladder_max=LADDER[-1], async_pipeline=True, controller=True,
        holdback_lambda=1.5, inflight_depth=2, coscheduler=COS, **kw)
    assert set(load.outputs) == set(offline) and n_ops == len(offline)
    for tid, row in offline.items():
        np.testing.assert_array_equal(load.outputs[tid], row)
    assert snap["controller"]["updates"] > 0
    COS.drain_dispatch_log()

    cload, csnap, _ = serve_crypto_cluster(
        hosts=2, max_age_s=0.002, validate=False, merge_dispatch=True,
        row_ladder_max=LADDER[-1], async_pipeline=True, controller=True,
        holdback_lambda=1.5, inflight_depth=2,
        coscheduler_factory=lambda h: COS, **kw)
    assert set(cload.outputs) == set(offline)
    for tid, row in offline.items():
        np.testing.assert_array_equal(cload.outputs[tid], row)
    m = csnap["merged"]
    assert m["requests_served"] == n_ops
    assert "holdback" in m and "controller" in m
    assert m["controller"]["hosts"] == 2
    assert csnap["drain_barrier"]["inflight_groups"] == 0


# --- satellite: persistent compile cache ----------------------------------------

@pytest.fixture
def cache_config():
    """Restore JAX's persistent-cache settings after a test turns it on."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_compilation_cache_dir_configures_jax(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = enable_compilation_cache()
    assert cache_dir == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert os.path.isdir(cache_dir)
    server = CryptoServer(_cfg(n_c=2), coscheduler=COS)
    h1 = server.submit(_dil_request(0, 64), now=0.0)
    h2 = server.submit(_dil_request(1, 64), now=0.0)
    assert h1.done() and h2.done()
    eng = server.cos.engine_for("dilithium", 64)
    iso = np.zeros((1, 64), np.uint32)
    iso[0] = h1.request.coeffs
    np.testing.assert_array_equal(h1.result(), eng.oracle_np(iso)[0])


def test_compilation_cache_dir_follows_environment(cache_config, monkeypatch,
                                                   tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins when set, and compiles land
    there; unset, the cache is the checkout's git-ignored ``.jax_cache/``."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compilation_cache_dir() == str(tmp_path)
    cc.reset_cache()
    assert enable_compilation_cache() == str(tmp_path)
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 11)(np.arange(5)))
    assert os.listdir(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compilation_cache_dir() == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# --- satellite: perf-report BENCH diffing ---------------------------------------

def _perf_report():
    spec = importlib.util.spec_from_file_location(
        "perf_report", os.path.join(ROOT, "scripts", "perf_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _record(configs, env=None):
    base_env = {"backend": "cpu", "device_count": 1, "jax": "0.4.37",
                "platform": "test", "python": "3.10"}
    base_env.update(env or {})
    return {"bench": "dispatch", "schema": 1, "env": base_env,
            "points": [{"config": c, "rows_per_s": r}
                       for c, r in configs.items()]}


def test_perf_report_flags_regressions_past_threshold():
    pr = _perf_report()
    base = _record({"a": 1000.0, "b": 1000.0, "gone": 500.0})
    cand = _record({"a": 850.0, "b": 700.0, "fresh": 123.0})
    rep = pr.diff_records(base, cand, threshold=0.2)
    assert not rep["env_mismatch"]
    by = {r["config"]: r for r in rep["per_config"]}
    assert by["a"]["status"] == "ok"          # −15 % is inside the threshold
    assert by["b"]["status"] == "regression"  # −30 % fails
    assert by["b"]["delta"] == pytest.approx(-0.3)
    assert by["gone"]["status"] == "missing-in-candidate"
    assert by["fresh"]["status"] == "new-in-candidate"
    assert [r["config"] for r in rep["regressions"]] == ["b"]


def test_perf_report_env_mismatch_is_warning_not_signal():
    pr = _perf_report()
    base = _record({"a": 1000.0})
    cand = _record({"a": 100.0}, env={"jax": "0.5.0"})
    rep = pr.diff_records(base, cand, threshold=0.2)
    assert rep["env_mismatch"] == {"jax": ("0.4.37", "0.5.0")}
    assert rep["regressions"]                 # detected…
    # …but the CLI downgrades it (exercised via run_bench_diff exit codes in
    # CI; here we assert the mismatch is reported for the caller to act on)


def test_perf_report_missing_baseline_path_is_clean(tmp_path):
    """An absent --baseline file exits 0 under --dry-run and 2 otherwise —
    never an unhandled traceback."""
    import types
    pr = _perf_report()
    cand = tmp_path / "cand.json"
    cand.write_text(__import__("json").dumps(_record({"a": 1.0})))
    args = dict(bench="dispatch", candidate=str(cand),
                baseline=str(tmp_path / "absent.json"), baseline_rev="HEAD",
                fail_threshold=0.2)
    assert pr.run_bench_diff(types.SimpleNamespace(**args, dry_run=True)) == 0
    assert pr.run_bench_diff(types.SimpleNamespace(**args, dry_run=False)) == 2


def test_perf_report_rejects_mismatched_benches_and_bad_schema():
    pr = _perf_report()
    with pytest.raises(ValueError, match="different benches"):
        pr.diff_records(_record({"a": 1.0}),
                        {**_record({"a": 1.0}), "bench": "serve"})
    with pytest.raises(ValueError, match="missing 'env'"):
        pr.check_record({"bench": "x", "schema": 1, "points": []}, "t")
