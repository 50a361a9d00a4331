"""Continuous metrics, SLO burn-rate alerting, and the controller flight
recorder.

Everything runs on the deterministic virtual clock.  The acceptance
contract exercised here:

- the registry scrapes on a fixed serving-clock cadence into bounded rings
  and exposes valid OpenMetrics text (gzip-transparent on ``.gz`` paths);
- burn rates match the closed form on synthetic counter series, and the
  alert engine walks pending → firing → resolved (with cancellation);
- an induced-overload serve run fires AND resolves the admission SLO
  burn-rate alert, with the firing instant visible in the exported
  Perfetto trace;
- two identical runs — single host and a 2-host cluster — produce
  bit-identical scrape series and alert logs under
  ``deterministic_timing``;
- every controller setpoint change lands in the flight-recorder ring and
  as a ``setpoint`` instant on the trace.
"""
import gzip
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.cluster import ClusterConfig, ClusterServer
from repro.core import field as F
from repro.core.scheduler import TenantRequest
from repro.core.scheduler.coscheduler import SliceCoScheduler
from repro.obs import (chrome_trace, read_text, validate_chrome_trace,
                       validate_openmetrics, write_text)
from repro.obs.alerts import (AlertEngine, BurnRateRule, ThresholdRule,
                              default_cluster_rules, default_serve_rules,
                              merge_alert_sections)
from repro.obs.metrics import MetricsRegistry, expose_registries
from repro.serve import CryptoServer, ServeConfig

RNG = np.random.default_rng(41)

# Shared compiled-program cache (engines are lru-cached process-wide, so
# this reuses the other serving suites' work).
COS = SliceCoScheduler()


def _dil_request(tid, d, t=0.0):
    coeffs = np.asarray(RNG.integers(0, F.DILITHIUM_Q, d, dtype=np.uint64),
                        np.uint32)
    return TenantRequest(tid, "dilithium", d, t, coeffs)


def _cfg(**kw):
    kw.setdefault("validate", False)
    kw.setdefault("n_c", 4)
    kw.setdefault("max_age_s", 0.005)
    kw.setdefault("metrics", True)
    kw.setdefault("metrics_period_s", 0.001)
    kw.setdefault("deterministic_timing", True)
    return ServeConfig(**kw)


# --- registry ------------------------------------------------------------------

def test_registry_cadence_and_monotone_timestamps():
    r = MetricsRegistry(period_s=0.01, capacity=16)
    ticks = []
    r.add_collector(lambda now: ticks.append(now) or [("g", (), 1.0)])
    assert r.scrape(0.0)
    assert not r.maybe_scrape(0.005)          # inside the period: gated
    assert r.maybe_scrape(0.0199999)          # >= period elapsed
    assert not r.scrape(0.0199999)            # same instant: no double sample
    assert not r.scrape(0.01)                 # going backwards: refused
    assert r.scrapes == 2 and len(ticks) == 2
    assert [ts for ts, _ in r.series("g")] == [0.0, 0.0199999]


def test_registry_ring_bounds_and_dropped_points():
    r = MetricsRegistry(period_s=0.001, capacity=4)
    for i in range(9):
        r.observe("c", (), float(i), float(i))
    assert len(r.series("c")) == 4
    assert r.dropped_points == 5
    assert r.series("c")[0] == (5.0, 5.0)     # oldest retained
    snap = r.snapshot()
    assert snap["samples"] == 4 and snap["dropped_points"] == 5


def test_window_delta_clamps_to_oldest_and_needs_two_samples():
    r = MetricsRegistry(period_s=0.001, capacity=16)
    r.observe("c", (), 0.0, 10.0)
    assert r.window_delta("c", (), 0.0, 1.0) is None
    for i in range(1, 5):
        r.observe("c", (), float(i), 10.0 + 2.0 * i)
    assert r.window_delta("c", (), 4.0, 2.0) == (4.0, 2.0)
    # window wider than the ring span: clamped to the oldest point
    assert r.window_delta("c", (), 4.0, 100.0) == (8.0, 4.0)


def test_exposition_is_valid_openmetrics_and_hosts_are_labelled():
    a = MetricsRegistry(period_s=0.001, host=0)
    b = MetricsRegistry(period_s=0.001, host=1)
    for reg, base in ((a, 1.0), (b, 2.0)):
        reg.describe("repro_x_total", kind="counter", help_text="an x")
        for i in range(3):
            reg.observe("repro_x_total", (), float(i), base * i)
    text = expose_registries([a, b])
    stats = validate_openmetrics(text)
    assert stats == {"families": 1, "series": 2, "samples": 6}
    assert text.count("# TYPE repro_x_total counter") == 1
    assert 'host="0"' in text and 'host="1"' in text
    assert text.endswith("# EOF\n")


def test_validate_openmetrics_rejects_bad_documents():
    with pytest.raises(ValueError):
        validate_openmetrics("# TYPE x counter\nx 1 0\n")   # missing EOF
    with pytest.raises(ValueError):                         # counter decrease
        validate_openmetrics("# TYPE x counter\nx 2 0\nx 1 1\n# EOF\n")
    with pytest.raises(ValueError):                         # ts not increasing
        validate_openmetrics("# TYPE x gauge\nx 1 5\nx 2 5\n# EOF\n")


# --- burn-rate math vs closed form ---------------------------------------------

def test_burn_rate_matches_closed_form():
    r = MetricsRegistry(period_s=1.0, capacity=256)
    miss_rate, budget = 0.3, 0.05
    for i in range(61):
        r.observe("den", (), float(i), float(i))
        r.observe("num", (), float(i), miss_rate * i)
    rule = BurnRateRule(name="b", num=("num", ()), den=("den", ()),
                        budget=budget, windows=((30.0, 5.0, 2.0),))
    for w in (5.0, 30.0):
        assert rule.burn(r, 60.0, w) == pytest.approx(miss_rate / budget)
    hit, worst = rule.condition(r, 60.0)
    assert hit and worst == pytest.approx(miss_rate / budget)
    # below the factor on both windows: no hit, worst still reported
    calm = BurnRateRule(name="c", num=("num", ()), den=("den", ()),
                        budget=budget, windows=((30.0, 5.0, 10.0),))
    hit, worst = calm.condition(r, 60.0)
    assert not hit and worst == pytest.approx(miss_rate / budget)


def test_burn_rate_pair_demands_both_windows():
    r = MetricsRegistry(period_s=1.0, capacity=256)
    # heavy historic burn that stopped 10 ticks ago: long window still hot,
    # short window clean — the pair must NOT fire (not burning *now*)
    for i in range(51):
        r.observe("den", (), float(i), float(i))
        r.observe("num", (), float(i), float(min(i, 40)))
    rule = BurnRateRule(name="b", num=("num", ()), den=("den", ()),
                        budget=0.05, windows=((40.0, 5.0, 2.0),))
    assert rule.burn(r, 50.0, 40.0) > 2.0
    assert rule.burn(r, 50.0, 5.0) == 0.0
    hit, _ = rule.condition(r, 50.0)
    assert not hit


# --- alert state machine -------------------------------------------------------

def test_alert_transitions_pending_firing_resolved_and_cancelled():
    r = MetricsRegistry(period_s=0.01, capacity=64)
    rule = ThresholdRule(name="hot", series=("g", ()), op=">", value=5.0,
                         for_s=0.02)
    eng = AlertEngine(r, (rule,))
    # missing series: undefined signal stays inactive
    eng.evaluate(0.0)
    assert eng.state("hot") == "inactive"
    # a blip shorter than for_s: pending then cancelled, never firing
    r.observe("g", (), 0.01, 9.0)
    eng.evaluate(0.01)
    assert eng.state("hot") == "pending"
    r.observe("g", (), 0.02, 1.0)
    eng.evaluate(0.02)
    assert eng.state("hot") == "inactive"
    # sustained breach: pending at onset, firing once for_s has elapsed
    for t in (0.03, 0.04, 0.05, 0.06):
        r.observe("g", (), t, 9.0)
        eng.evaluate(t)
    assert eng.state("hot") == "firing"
    r.observe("g", (), 0.07, 1.0)
    eng.evaluate(0.07)
    assert eng.state("hot") == "inactive"
    kinds = [e["transition"] for e in eng.log]
    assert kinds == ["pending", "cancelled", "pending", "firing", "resolved"]
    snap = eng.snapshot()
    assert snap["rules"]["hot"]["fired"] == 1
    assert snap["rules"]["hot"]["resolved"] == 1
    assert snap["events_total"] == 5


def test_alert_engine_rejects_duplicate_rule_names():
    r = MetricsRegistry(period_s=0.01)
    dup = ThresholdRule(name="x", series=("g", ()), op=">", value=0.0)
    with pytest.raises(ValueError):
        AlertEngine(r, (dup, dup))


def test_default_rule_sets_cover_the_contracted_signals():
    serve = {r.name for r in default_serve_rules(max_age_s=0.005,
                                                 slo_deadline_s=0.01)}
    assert serve == {"slo_burn", "p99_latency", "m_occupancy_floor",
                     "arithmetic_stall_share"}
    cluster = {r.name for r in default_cluster_rules(staleness_bound_s=0.004)}
    assert cluster == {"gossip_silence", "gossip_staleness", "failover_shed"}


def test_merge_alert_sections_counts_firing_hosts():
    mk = lambda state, fired: {"rules": {"slo_burn": {
        "state": state, "fired": fired, "resolved": 0, "severity": "page"}},
        "events_total": fired}
    merged = merge_alert_sections([mk("firing", 2), mk("inactive", 1), None])
    assert merged["hosts"] == 2
    assert merged["rules"]["slo_burn"]["fired"] == 3
    assert merged["rules"]["slo_burn"]["hosts_firing"] == 1
    assert merged["events_total"] == 3
    assert merge_alert_sections([None, {}]) == {}


# --- induced overload: fire AND resolve on a real serve run --------------------

def _overload_rules():
    """One tight window pair so a ~20 ms virtual run can both fire and
    resolve the admission burn alert."""
    return (BurnRateRule(
        name="slo_burn",
        num=("repro_admission_slo_miss_total", ()),
        den=("repro_admission_decisions_total", ()),
        budget=0.05, windows=((0.01, 0.004, 1.0),)),)


def _run_overload(tmp_path=None):
    # n_c far above the offered burst and a long age trigger: admitted
    # requests pool in the open batch, so the SLO gate's predicted wait
    # (pending / service-rate, init 1024 rows/s) crosses the 2 ms deadline
    # after a couple of admits and every later decision is a miss.
    cfg = _cfg(n_c=64, max_age_s=0.05, slo_deadline_s=0.002,
               tracing=True, alert_rules=_overload_rules())
    srv = CryptoServer(cfg, coscheduler=COS)
    t = 0.0
    handles = []
    for i in range(40):
        t = i * 0.0005
        handles.append(srv.submit(_dil_request(i, 64, t), now=t))
    rejected = sum(1 for h in handles if h.rejected)
    # offered load stops; keep the serving clock ticking so scrapes continue,
    # the age trigger flushes the pooled batch, and the alert can resolve
    for k in range(1, 41):
        srv.pump(0.02 + 0.002 * k)
    srv.drain(0.11)
    return srv, rejected


def test_induced_overload_fires_and_resolves_slo_burn():
    srv, rejected = _run_overload()
    assert rejected > 10                      # the overload actually rejected
    snap = srv.alerts.snapshot()
    rule = snap["rules"]["slo_burn"]
    assert rule["fired"] >= 1
    assert rule["resolved"] >= 1
    assert rule["state"] == "inactive"        # resolved by the end
    kinds = [e["transition"] for e in srv.alerts.log]
    assert kinds.index("firing") < kinds.index("resolved")
    # the firing instant is on the Perfetto timeline, on the alerts track
    trace = chrome_trace(srv.trace_events())
    validate_chrome_trace(trace)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "i"}
    assert "alert_firing:slo_burn" in names
    assert "alert_resolved:slo_burn" in names
    # and the telemetry snapshot carries both sections
    tsnap = srv.telemetry.snapshot()
    assert tsnap["metrics"]["scrapes"] == srv.metrics.scrapes
    assert tsnap["alerts"]["rules"]["slo_burn"]["fired"] == rule["fired"]


# --- virtual-clock determinism -------------------------------------------------

def _deterministic_run(seed=5):
    rng = np.random.default_rng(seed)
    reqs = [(i, TenantRequest(
        i, "dilithium", 64, i * 0.0008,
        np.asarray(rng.integers(0, F.DILITHIUM_Q, 64, dtype=np.uint64),
                   np.uint32))) for i in range(48)]
    cfg = _cfg(controller=True, row_ladder_max=32, slo_deadline_s=0.01,
               max_pending=64)
    srv = CryptoServer(cfg, coscheduler=COS)
    for i, req in reqs:
        srv.submit(req, now=req.arrival_time)
    srv.drain(0.06)
    return srv


def test_two_runs_scrape_bit_identical_series_and_alert_logs():
    a, b = _deterministic_run(), _deterministic_run()
    assert a.metrics.scrapes > 5
    assert a.metrics_text() == b.metrics_text()
    assert list(a.alerts.log) == list(b.alerts.log)
    assert json.dumps(a.alerts.snapshot(), sort_keys=True) == \
        json.dumps(b.alerts.snapshot(), sort_keys=True)


def _deterministic_cluster_run(seed=9):
    rng = np.random.default_rng(seed)
    serve = _cfg(n_c=4, max_age_s=0.004, slo_deadline_s=0.02)
    cluster = ClusterServer(
        ClusterConfig(n_hosts=2, gossip_period_s=0.002, serve=serve),
        coscheduler_factory=lambda h: COS)
    for i in range(48):
        t = i * 0.0008
        coeffs = np.asarray(rng.integers(0, F.DILITHIUM_Q, 64,
                                         dtype=np.uint64), np.uint32)
        cluster.submit(TenantRequest(i, "dilithium", 64, t, coeffs), now=t)
    cluster.drain(0.06)
    return cluster


def test_cluster_scrape_and_alert_logs_bit_identical_across_runs():
    a, b = _deterministic_cluster_run(), _deterministic_cluster_run()
    assert a.metrics is not None and a.metrics.scrapes > 0
    assert a.metrics_text() == b.metrics_text()
    assert list(a.alerts.log) == list(b.alerts.log)
    for ha, hb in zip(a.hosts, b.hosts):
        assert list(ha.alerts.log) == list(hb.alerts.log)
    stats = validate_openmetrics(a.metrics_text())
    assert stats["samples"] > 0
    # gossip sensing series are present at the fleet level
    assert a.metrics.latest("repro_gossip_silence_seconds_max") is not None
    # merged telemetry carries the fleet alert/metrics roll-ups
    merged = a.snapshot()["merged"]
    assert merged["metrics"]["hosts"] == 2
    assert set(merged["alerts"]["rules"]) == {
        r.name for r in default_serve_rules(max_age_s=0.004,
                                            slo_deadline_s=0.02)}


def test_gossip_silence_alert_senses_a_dead_host():
    serve = _cfg(n_c=4, max_age_s=0.004)
    cluster = ClusterServer(
        ClusterConfig(n_hosts=2, gossip_period_s=0.002, serve=serve),
        coscheduler_factory=lambda h: COS)
    # the in-process event loop publishes for every host it still drives, so
    # a dead host is simulated at the bus: both publish once, then host 1
    # goes silent while host 0 keeps its digests fresh
    cluster.gossip.publish(0, 3, 0.0)
    cluster.gossip.publish(1, 3, 0.0)
    bound = cluster.gossip.staleness_bound_s
    for k in range(1, 10):
        t = 0.002 * k
        cluster.gossip.maybe_publish(0, 3, t)
        assert cluster.metrics.scrape(t)
        cluster.alerts.evaluate(t)
        if t <= bound:                     # within the bound: not dead yet
            assert cluster.alerts.state("gossip_silence") == "inactive"
    assert cluster.alerts.state("gossip_silence") == "firing"
    assert cluster.metrics.latest("repro_gossip_silence_seconds_max") > bound
    # the dying host's per-peer silence series carries the evidence
    assert cluster.metrics.latest("repro_gossip_silence_seconds",
                                  (("peer", "1"),)) > bound
    # host 1 resumes publishing: the alert resolves on the next scrape
    cluster.gossip.publish(1, 3, 0.02)
    cluster.metrics.scrape(0.0205)
    cluster.alerts.evaluate(0.0205)
    assert cluster.alerts.state("gossip_silence") == "inactive"
    assert cluster.alerts.snapshot()["rules"]["gossip_silence"]["resolved"] == 1


def test_silence_survives_digest_prune_until_republish():
    """Regression: ``cluster_view``'s staleness prune drops a dead host's
    *digest*, but its publish silence must keep growing — ``gossip_silence``
    stays firing after the prune and resolves only on an actual republish.
    (The bug mode: pruning ``_last_pub`` alongside ``_digests`` would make a
    cordoned host read as healthy one GC later.)"""
    serve = _cfg(n_c=4, max_age_s=0.004)
    cluster = ClusterServer(
        ClusterConfig(n_hosts=2, gossip_period_s=0.002, serve=serve),
        coscheduler_factory=lambda h: COS)
    bus = cluster.gossip
    bus.publish(0, 3, 0.0)
    bus.publish(1, 3, 0.0)
    bound = bus.staleness_bound_s
    # age host 1's digest past the bound and force the prune via a view read
    t = bound + 0.001
    bus.publish(0, 3, t)
    bus.cluster_view(0, 3, t)
    assert bus.pruned_digests == 1
    assert 1 not in bus._digests                  # digest gone...
    assert bus.silence_s(t)[1] == pytest.approx(t)   # ...silence intact
    cluster.metrics.scrape(t)
    cluster.alerts.evaluate(t)
    assert cluster.alerts.state("gossip_silence") == "firing"
    # silence keeps growing across later scrapes — still firing, long after
    # the digest was garbage-collected
    for k in (2.0, 4.0, 8.0):
        tk = bound * k + 0.001
        bus.maybe_publish(0, 3, tk)
        cluster.metrics.scrape(tk)
        cluster.alerts.evaluate(tk)
        assert cluster.alerts.state("gossip_silence") == "firing"
        assert bus.silence_s(tk)[1] == pytest.approx(tk)
    # an actual republish (the rejoin announce) is what resolves it
    t_back = bound * 8.0 + 0.002
    bus.publish(1, 3, t_back)
    assert bus.revives == 1                       # pruned → publishing again
    cluster.metrics.scrape(t_back + 0.001)
    cluster.alerts.evaluate(t_back + 0.001)
    assert cluster.alerts.state("gossip_silence") == "inactive"
    assert cluster.alerts.snapshot()[
        "rules"]["gossip_silence"]["resolved"] == 1
    assert bus.snapshot()["revives"] == 1


# --- controller flight recorder ------------------------------------------------

def test_flight_recorder_captures_setpoint_changes():
    cfg = _cfg(controller=True, row_ladder_max=64, n_c=8, max_age_s=0.002,
               tracing=True, max_pending=4096)
    srv = CryptoServer(cfg, coscheduler=COS)
    # a hard burst then starvation: the controller must move the target
    # rung at least once in each direction
    t = 0.0
    for i in range(120):
        t = i * 0.0001
        srv.submit(_dil_request(i, 64, t), now=t)
    for k in range(1, 30):
        srv.pump(t + 0.002 * k)
    srv.drain(t + 0.08)
    ctl = srv.controller
    assert ctl.decisions >= 1
    assert len(ctl.flight) == min(ctl.decisions, ctl.flight.maxlen)
    for rec in ctl.flight:
        assert rec.reason in ("starving", "overloaded", "queue_model")
        assert (rec.target_rows, rec.max_age_s, rec.occupancy_close) != \
            (rec.target_rows_from, rec.max_age_from_s, rec.occupancy_from)
    fr = ctl.snapshot()["flight_recorder"]
    assert fr["decisions"] == ctl.decisions
    assert len(fr["records"]) == len(ctl.flight)
    assert fr["records"][-1]["ts"] >= fr["records"][0]["ts"]
    # every recorded decision also landed as a setpoint instant on the trace
    trace = chrome_trace(srv.trace_events())
    setpoints = [e for e in trace["traceEvents"]
                 if e["ph"] == "i" and e["name"] == "setpoint"]
    assert len(setpoints) == ctl.decisions
    assert setpoints[0]["args"]["reason"] in ("starving", "overloaded",
                                              "queue_model")


def test_flight_recorder_ring_is_bounded():
    from repro.serve.controller import AdaptiveController
    ctl = AdaptiveController(ladder=(8, 16, 32), n_c=8, max_age_s=0.002,
                             recorder_capacity=4)
    for i in range(12):
        # alternate starvation and overload so every observation moves a
        # setpoint (the age lever oscillates) and appends a record
        depth = 0 if i % 2 == 0 else 10_000
        ctl.observe_dispatch(("dilithium", 64), now=0.01 * (i + 1),
                             live_rows=2, queue_depth=depth)
    assert ctl.decisions > 4
    assert len(ctl.flight) == 4               # ring stays bounded
    assert ctl.snapshot()["flight_recorder"]["capacity"] == 4


# --- gzip transparency ---------------------------------------------------------

def test_trace_and_metrics_gzip_roundtrip(tmp_path):
    srv, _ = _run_overload()
    tpath = str(tmp_path / "trace.json.gz")
    mpath = str(tmp_path / "metrics.om.gz")
    srv.write_trace(tpath)
    srv.write_metrics(mpath)
    with gzip.open(tpath, "rt") as f:      # really gzip on disk
        json.load(f)
    stats = validate_chrome_trace(tpath)   # validator reads .gz directly
    assert stats["requests"] > 0
    mstats = validate_openmetrics(mpath)
    assert mstats["samples"] > 0
    assert read_text(mpath) == srv.metrics_text()
    # phase calls and per-class waits are exported; phase seconds are
    # wall-clock, withheld under deterministic timing
    series = {line.split("{")[0] for line in srv.metrics_text().splitlines()
              if line and not line.startswith("#")}
    assert {"repro_phase_calls_total", "repro_wait_seconds_total",
            "repro_wait_requests_total",
            "repro_wait_longest_seconds"} <= series
    assert "repro_phase_seconds_total" not in series
    assert 'stage="to_close"' in srv.metrics_text()
    # plain-path round trip through the same helpers
    plain = str(tmp_path / "metrics.om")
    write_text(plain, srv.metrics_text())
    assert validate_openmetrics(plain) == mstats


def test_phase_seconds_exported_on_the_wall_clock():
    srv = CryptoServer(_cfg(deterministic_timing=False), coscheduler=COS)
    for i in range(12):
        srv.submit(_dil_request(i, 64, i * 0.001), now=i * 0.001)
    srv.drain(0.02)
    text = srv.metrics_text()
    assert validate_openmetrics(text)["samples"] > 0
    assert 'repro_phase_seconds_total{phase="stage"}' in text
    assert 'repro_phase_calls_total{phase="resolve"}' in text
    assert 'repro_wait_longest_seconds{class="dilithium",stage="to_close"}' \
        in text
    assert 'repro_wait_requests_total{class="dilithium",stage="to_resolve"}' \
        in text


# --- perf_report penalty-share drift -------------------------------------------

def _load_perf_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "perf_report.py")
    spec = importlib.util.spec_from_file_location("perf_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_report_warns_on_penalty_share_drift_without_failing():
    pr = _load_perf_report()
    env = {k: "same" for k in pr.ENV_KEYS}
    mk = lambda shares: {
        "bench": "serve", "schema": 1, "env": env,
        "points": [{"config": "rate512", "rows_per_s": 1000.0,
                    "penalty": {"dilithium": {"shares": shares}}}]}
    base = mk({"mxu_productive": 0.50, "arithmetic_stall": 0.30,
               "spatial_pad": 0.15, "host_gap": 0.05})
    cand = mk({"mxu_productive": 0.42, "arithmetic_stall": 0.38,
               "spatial_pad": 0.15, "host_gap": 0.05})
    report = pr.diff_records(base, cand)
    drift = report["per_config"][0]["penalty_drift"]
    assert {d["bin"] for d in drift} == {"mxu_productive",
                                         "arithmetic_stall"}
    assert not report["regressions"]          # drift is warning-only
    # identical shares (and drift within the band): no warning rows
    same = pr.diff_records(base, base)
    assert "penalty_drift" not in same["per_config"][0]
    small = mk({"mxu_productive": 0.47, "arithmetic_stall": 0.33,
                "spatial_pad": 0.15, "host_gap": 0.05})
    assert "penalty_drift" not in pr.diff_records(
        base, small)["per_config"][0]
