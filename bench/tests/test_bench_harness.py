"""The harness's pure parts: traffic from the seed, latency from the due
time, retries of a full queue, the percentile / rate / roofline
arithmetic, and the refusal to run without a TPU."""
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness as H  # noqa: E402
from bench import roofline as RF  # noqa: E402
from bench import stats as ST  # noqa: E402
from bench import traffic as TR  # noqa: E402

MIXED = [{"workload": "dilithium", "share": 0.5, "degree_low": 64,
          "degree_high": 512},
         {"workload": "bn254_full", "share": 0.5, "degree_low": 64,
          "degree_high": 512}]
OPEN = {"loop": "open", "request_rate_hz": 2000, "group_size": 1}


def schedule(seed, mix=OPEN, classes=MIXED, seconds=2.0):
    return TR.open_schedule(mix, classes, seconds,
                            np.random.default_rng(seed))


@pytest.mark.parametrize("seed", [0, 3000000000])
def test_traffic_is_identical_for_a_seed(seed):
    a, b = schedule(seed), schedule(seed)
    assert a.workloads == b.workloads
    np.testing.assert_array_equal(a.degrees, b.degrees)
    np.testing.assert_array_equal(a.due, b.due)


def test_traffic_differs_across_seeds_in_order_only():
    a, b = schedule(1), schedule(2)
    assert a.workloads != b.workloads
    assert not np.array_equal(a.due, b.due)
    # the same amount of work: the same multiset of sizes and gaps
    assert sorted(zip(a.workloads, a.degrees.tolist())) == sorted(
        zip(b.workloads, b.degrees.tolist()))
    ga = TR.exp_gaps(500, 1e-3, np.random.default_rng(1))
    gb = TR.exp_gaps(500, 1e-3, np.random.default_rng(2))
    assert not np.array_equal(ga, gb)
    np.testing.assert_array_equal(np.sort(ga), np.sort(gb))


def test_open_schedule_law():
    s = schedule(5, seconds=4.0)
    assert len(s.workloads) == 8000
    assert s.due[0] == 0.0 and s.due[-1] < 4.0
    assert np.all(np.diff(s.due) >= 0)
    assert s.workloads.count("dilithium") == 4000
    assert s.degrees.min() == 64 and s.degrees.max() == 512
    gaps = np.diff(s.due)
    # exponential gaps: mean 1/R, and standard deviation close to the mean
    assert abs(gaps.mean() * 2000 - 1) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05


def test_groups_arrive_together():
    mix = {"loop": "open", "request_rate_hz": 1000, "group_size": 5}
    s = schedule(9, mix=mix, classes=MIXED[:1], seconds=1.0)
    assert len(s.workloads) == 1000
    assert np.all(s.due.reshape(-1, 5) == s.due.reshape(-1, 5)[:, :1])
    with pytest.raises(ValueError):
        schedule(9, mix=mix)


def test_percentile_rate_and_spread():
    v = np.arange(1, 101, dtype=float)
    assert ST.percentile(v, 50) == 50.5
    assert ST.percentile(v, 99) == pytest.approx(99.01)
    # statistics.quantiles (exclusive): q1 = 3.25, q3 = 9.75 of 1..12
    assert ST.spread(range(1, 13)) == pytest.approx((9.75 - 3.25) / 6.5)
    with pytest.raises(ValueError):
        ST.percentile([], 50)


def test_roofline_counts_by_hand():
    # dilithium d = 256, d_tile 171: passes of 171 and 85 columns,
    # La = 3, n_diag = 5, M = 1280, 8 rows, one channel
    c = RF.launch_cost("dilithium", 256, 8, 171)
    ks = (171 * 3, 85 * 3)
    assert c["macs"] == sum(8 * k * 1280 for k in ks)
    assert c["bytes"] == sum(8 * k + k * 1280 + 4 * 8 * 1280 for k in ks)
    full = RF.launch_cost("bn254_full", 512, 64, 171)
    assert full["macs"] == 18 * 64 * (512 * 4) * (512 * 7)
    peak = {"int8_ops_per_s": 400e12, "hbm_bytes_per_s": 800e9}
    assert RF.bound_s(10 ** 12, 10 ** 6, peak) == (5e-3, "compute")
    assert RF.bound_s(10 ** 6, 8 * 10 ** 9, peak) == (1e-2, "memory")
    with pytest.raises(KeyError):
        RF.peaks("cpu")
    assert RF.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12


class _Req:
    def __init__(self, i):
        self.tenant_id = i


class _Handle:
    def __init__(self):
        self.rejected, self.resolved = False, False

    def done(self):
        return self.resolved

    def result(self):
        return "row"


class _FakeServer:
    """Resolves every submitted request at the next pump; the first
    submission stalls, as a slow launch would."""

    def __init__(self, probes, stall_s):
        self.probes, self.stall_s, self.queued = probes, stall_s, []
        self.inflight_groups = 0

    def submit_many(self, reqs, now):
        if self.stall_s:
            time.sleep(self.stall_s)
            self.stall_s = 0.0
        hs = [_Handle() for _ in reqs]
        self.queued += zip(reqs, hs)
        self.inflight_groups = 1
        return hs

    def pump(self, now):
        for r, h in self.queued:
            h.resolved = True
            self.probes.resolved.append(r)
        self.queued, self.inflight_groups = [], 0

    def next_deadline(self):
        return None


class _Probes(H.Probes):
    def __init__(self):                      # no co-scheduler to wrap
        self.annotate, self.resolved, self.launches = False, [], []
        self.launch_s = self.gather_s = 0.0


def test_latency_is_taken_from_the_due_time():
    """A stall at the first submission makes every request due during it
    late; their latency counts the wait, not just their own service."""
    probes = _Probes()
    server = _FakeServer(probes, stall_s=0.2)
    due = np.array([0.0, 0.05, 0.1, 0.15, 0.3])
    win = H.run_open(server, [_Req(i) for i in range(5)], due, 0.4, probes)
    lat = win.done - win.due
    late = win.submitted - win.due
    assert np.all(lat >= late)
    assert lat[1] >= 0.14 and lat[3] >= 0.04     # waited behind the stall
    assert late[4] < 0.05 and lat[4] < 0.05       # due after it: on time
    assert np.all(~np.isnan(win.done))
    assert win.rows == ["row"] * 5 and not win.rejected.any()


class _BoundedServer(_FakeServer):
    """Admits while fewer than ``cap`` rows are pending and refuses the
    rest for ``reason``; every pump resolves what is pending."""

    def __init__(self, probes, cap, reason):
        super().__init__(probes, stall_s=0.0)
        self.reason = reason
        self.admission = types.SimpleNamespace(max_pending=cap)

    @property
    def pending_load(self):
        return len(self.queued)

    def submit_many(self, reqs, now):
        hs = []
        for r in reqs:
            h = _Handle()
            if self.pending_load < self.admission.max_pending:
                self.queued.append((r, h))
            else:
                h.rejected = True
                h.decision = types.SimpleNamespace(reason=self.reason)
            hs.append(h)
        self.inflight_groups = int(bool(self.queued))
        return hs


@pytest.mark.parametrize("reason,answered", [("queue_full", 6),
                                             ("draining", 2)])
def test_open_loop_retries_a_full_queue_only(reason, answered):
    """Refusals for a full queue are held and resubmitted, oldest first,
    until answered; any other refusal is final and counts as rejected."""
    probes = _Probes()
    server = _BoundedServer(probes, cap=2, reason=reason)
    due = np.zeros(6)
    win = H.run_open(server, [_Req(i) for i in range(6)], due, 0.1, probes)
    got = [r is not None for r in win.rows]
    assert sum(got) == answered and got[:2] == [True, True]
    assert win.rejected.sum() == 6 - answered
    retried = reason == "queue_full"
    assert win.refused.tolist() == [False] * 2 + [retried] * 4
    if retried:
        assert np.all(np.diff(win.done) >= 0)    # served in due order
        assert np.all(win.submitted == win.submitted[0])  # first attempt


class _Fleet:
    """Host slices behind a router that sends even tenants to host 0 and
    odd ones to host 1; ``submit_many`` and ``pump`` go to every host."""

    def __init__(self, hosts):
        self.hosts = hosts
        self.router = types.SimpleNamespace(host_for=lambda t: t % 2)

    def submit_many(self, reqs, now):
        out = [None] * len(reqs)
        for h, host in enumerate(self.hosts):
            pos = [p for p, r in enumerate(reqs) if r.tenant_id % 2 == h]
            for p, handle in zip(pos, host.submit_many(
                    [reqs[p] for p in pos], now)):
                out[p] = handle
        return out

    def pump(self, now):
        for host in self.hosts:
            host.pump(now)

    def next_deadline(self):
        return None


class _CountingServer(_BoundedServer):
    """Also counts the submissions it refused."""

    def __init__(self, probes, cap):
        super().__init__(probes, cap, "queue_full")
        self.refusals = 0

    def submit_many(self, reqs, now):
        hs = super().submit_many(reqs, now)
        self.refusals += sum(h.rejected for h in hs)
        return hs


def test_open_loop_resubmits_only_into_the_owner_host():
    """A request refused at its owner host's full queue is held until that
    host has room, whatever room the other host has; each host serves its
    own requests in due order."""
    probes = _Probes()
    full, roomy = _CountingServer(probes, cap=1), _CountingServer(probes,
                                                                   cap=10)
    fleet = _Fleet([full, roomy])
    due = np.zeros(8)
    win = H.run_open(fleet, [_Req(i) for i in range(8)], due, 0.1, probes)
    assert all(r is not None for r in win.rows)
    assert not win.rejected.any()
    assert win.refused.tolist() == [False, False] + [True, False] * 3
    # 2, 4 and 6 were refused once each, when all arrived together, and
    # then each was submitted again only once host 0 had room for it
    assert full.refusals == 3 and roomy.refusals == 0
    assert np.all(np.diff(win.done[0::2]) > 0)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "mldsa65.poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def window_out(failed=0, correct=True, done=1000, attempted=1000,
               trend=(7.0, 7.5), retried=0):
    return {"failed": failed, "correct": correct, "attempted": attempted,
            "metrics": {"ops_per_s": {"value": done / 1.0}},
            "latency_trend_ms": trend, "drain_ms": 1.0, "retried": retried}


@pytest.mark.parametrize("kw,held", [
    ({}, True), ({"failed": 1}, False), ({"correct": False}, False),
    ({"retried": 1}, False),
    ({"done": 960}, False), ({"done": 970}, True),
    ({"trend": (7.0, 12.6)}, False), ({"trend": (7.0, 12.4)}, True)])
def test_sweep_window_held(kw, held):
    from bench import sweep as SW
    assert SW.window_held(window_out(**kw), 1.0) is held


@pytest.mark.parametrize("verdicts,knee", [
    ([(1, True), (2, True), (3, False), (4, True)], 2),
    ([(1, False), (2, True)], None),
    ([(1, True), (2, True)], 2)])
def test_sweep_knee_needs_every_lower_rate_held(verdicts, knee):
    from bench import sweep as SW
    assert SW.knee_of(verdicts) == knee


def test_sweep_takes_the_majority_of_repeats(monkeypatch):
    """A rate holds when most of its windows hold; the sweep stops after
    STOP_AFTER rates in a row that did not."""
    from bench import sweep as SW
    fails = {(2, 0), (3, 0), (3, 1), (4, 1), (4, 2)}   # (rate, repeat)
    calls = []

    def fake_window(sess, seed, seconds, trace, mix):
        rate, j = mix["request_rate_hz"], (seed - 100) % SW.REPEATS
        calls.append((rate, seed))
        return window_out(failed=int((rate, j) in fails))
    monkeypatch.setattr(SW.R, "window", fake_window)
    lines = []
    verdicts = SW.sweep(None, OPEN, [1, 2, 3, 4, 5, 6], 1.0, 100,
                        emit=lines.append)
    assert verdicts == [(1, True), (2, True), (3, False), (4, False)]
    assert SW.knee_of(verdicts) == 2
    assert len(calls) == 4 * SW.REPEATS
    assert len({seed for _, seed in calls}) == len(calls)
    assert len(lines) == 4 * (SW.REPEATS + 1)
