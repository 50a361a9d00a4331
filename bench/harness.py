"""The server under test, built from a configuration, and the loops that
drive it.

The window drives ``CryptoServer.submit_many`` / ``pump``: admission, the
continuous batcher, the co-scheduler and the engines.  Timers and profiler
spans sit around those calls and around the co-scheduler's
``launch_mixed`` / ``gather``, installed from here on the server's objects;
nothing in the program is edited.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import numpy as np

clock = time.perf_counter
QUEUE_FULL = "queue_full"       # the admission gate's reason at max_pending


class Probes:
    """Host timers and records around the co-scheduler's two calls.

    ``resolved`` collects the requests whose results a ``gather`` returned;
    the loops empty it after every server call and date those completions
    at the moment the call returned, which is when a caller sees them."""

    def __init__(self, cos, annotate: bool):
        import jax
        self.annotate = annotate
        self._annotation = jax.profiler.TraceAnnotation
        self.launch_s = self.gather_s = 0.0
        self.launches: list = []     # (workload, d_bucket, rows, live_rows)
        self.resolved: list = []
        launch_mixed, gather = cos.launch_mixed, cos.gather

        def timed_launch(batches):
            t = clock()
            with self.span("launch"):
                flight = launch_mixed(batches)
            self.launch_s += clock() - t
            for g, _, _ in flight.groups:
                self.launches.append((g.workload, g.d_bucket,
                                      cos.launch_rows(g.operand_rows),
                                      g.live_rows))
            return flight

        def timed_gather(flight):
            t = clock()
            with self.span("gather"):
                results = gather(flight)
            self.gather_s += clock() - t
            for dr in results:
                self.resolved.extend(dr.batch.requests)
            return results

        cos.launch_mixed, cos.gather = timed_launch, timed_gather

    def span(self, name: str):
        return (self._annotation(name) if self.annotate
                else contextlib.nullcontext())

    def reset(self):
        self.launch_s = self.gather_s = 0.0
        self.launches.clear()
        self.resolved.clear()


def build_server(cfg: dict, device):
    """A ``CryptoServer`` on one device, with a ``SliceCoScheduler`` made
    from the configuration's serving settings and classes."""
    from repro.core.scheduler.coscheduler import (SliceCoScheduler,
                                                  default_row_ladder)
    from repro.serve.server import CryptoServer, ServeConfig
    s = cfg["serving"]
    classes = [c["workload"] for c in cfg["classes"]]
    folds = {w: cfg["guarantees"]["fold"][w] for w in classes}
    cos = SliceCoScheduler(
        assignment={w: [device] for w in classes}, accum=s["accum"],
        reduction="eager", reduction_by_workload=folds, d_tile=s["d_tile"],
        merge=s["merge_dispatch"],
        row_ladder=default_row_ladder(s["row_ladder_max"]),
        donate=s["donate"])
    scfg = ServeConfig(
        n_c=s["n_c"], max_age_s=s["max_age_s"], validate=s["validate"],
        accum=s["accum"], max_pending=s["max_pending"],
        reduction_by_workload=folds, d_tile=s["d_tile"],
        merge_dispatch=s["merge_dispatch"],
        row_ladder_max=s["row_ladder_max"], donate=s["donate"],
        async_pipeline=s["async_pipeline"])
    return CryptoServer(scfg, coscheduler=cos), cos


def moduli_for(cos):
    """A BN254 class's RNS channel moduli: the server's input encoding."""
    def get(workload):
        return cos.engine_for(workload, 64).chain.moduli
    return get


def buckets(server, cls: dict) -> list:
    return sorted({server.batcher.bucket_for(d)
                   for d in range(cls["degree_low"], cls["degree_high"] + 1)})


def warm_up(server, cos, cfg: dict, request_type) -> int:
    """One launch of every (class, bucket, ladder rung) the traffic can
    produce, through the server's own path: a ``submit_many`` of one rung's
    worth of full rows closes rung / n_c batches that merge into one launch
    of that height, and the first of each (class, bucket) runs the HLO
    validator.  Returns the launches made."""
    mods = moduli_for(cos)
    made = 0
    for cls in cfg["classes"]:
        w = cls["workload"]
        for b in buckets(server, cls):
            shape = (b,) if w == "dilithium" else (b, len(mods(w)))
            for rung in cos.row_ladder:
                reqs = [request_type(tenant_id=-1, workload=w, degree=b,
                                     arrival_time=0.0,
                                     coeffs=np.zeros(shape, np.uint32))
                        for _ in range(rung)]
                server.submit_many(reqs, now=clock())
                server.pump(clock())
                made += 1
    while server.inflight_groups:
        server.pump(clock())
    return made


@dataclasses.dataclass
class WindowResult:
    rows: list               # served row per request; None if unanswered
    rejected: np.ndarray     # bool per request, never admitted
    refused: np.ndarray      # bool per request, refused for a full queue
                             # at least once and held for a retry
    due: np.ndarray          # s from the window's start (closed: submit)
    submitted: np.ndarray    # s, when the harness submitted it
    done: np.ndarray         # s, when the harness saw it resolved (nan)
    pool_index: np.ndarray   # payload index per request
    window_s: float
    host_call_s: float       # time inside submit_many / pump calls
    calls: int


def _wait_until(t_target: float, t0: float, probes: Probes):
    """Sleep (or, within 1 ms, spin) until ``t0 + t_target``."""
    with probes.span("wait_arrival"):
        while True:
            left = t0 + t_target - clock()
            if left <= 0:
                return
            if left > 2e-3:
                time.sleep(left - 1e-3)


class _Loop:
    """Shared bookkeeping of both loops: timed server calls, completions.

    A handle is held only while its request is pending; once resolved, its
    row is kept and the handle dropped, so the harness adds no long-lived
    objects for the garbage collector to walk during the window."""

    def __init__(self, server, probes: Probes, n_max: int):
        self.server, self.probes = server, probes
        self.pending: dict = {}          # tenant_id -> ResponseHandle
        self.rows: list = [None] * n_max
        self.rejected = np.zeros(n_max, bool)
        self.refused = np.zeros(n_max, bool)
        self.due = np.full(n_max, np.nan)
        self.submitted = np.full(n_max, np.nan)
        self.done = np.full(n_max, np.nan)
        self.pool = np.zeros(n_max, np.int64)
        self.host_s = 0.0
        self.calls = 0
        self.t0 = 0.0

    def call(self, name: str, fn, *args, **kw):
        t = clock()
        with self.probes.span(name):
            out = fn(*args, **kw)
        t_ret = clock()
        self.host_s += t_ret - t
        self.calls += 1
        return out, t_ret

    def collect(self, t_ret: float):
        """Date every completion the last call made at its return."""
        resolved = self.probes.resolved
        if not resolved:
            return
        now = t_ret - self.t0
        for r in resolved:
            h = self.pending.pop(r.tenant_id, None)
            if h is not None and h.done() and not h.rejected:
                self.rows[r.tenant_id] = h.result()
                self.done[r.tenant_id] = now
        resolved.clear()

    def submit(self, reqs, now_abs: float, retry: bool = False) -> list:
        """One ``submit_many``.  With ``retry``, returns the tenant ids the
        server refused for a full queue, for the caller to submit again;
        every other refusal, and without ``retry`` that one too, is final.
        ``submitted`` keeps a request's first submission."""
        hs, t_ret = self.call("submit", self.server.submit_many, reqs,
                              now=now_abs)
        t = now_abs - self.t0
        full = []
        for r, h in zip(reqs, hs):
            tid = r.tenant_id
            if np.isnan(self.submitted[tid]):
                self.submitted[tid] = t
            if not h.rejected:
                self.pending[tid] = h
            elif retry and h.decision.reason == QUEUE_FULL:
                self.refused[tid] = True
                full.append(tid)
            else:
                self.rejected[tid] = True
        self.collect(t_ret)
        return full

    def service(self, now_abs: float) -> bool:
        """Pump an expired age deadline, or gather a launch in flight when
        nothing else is due.  Returns whether a call was made."""
        dl = self.server.next_deadline()
        if (dl is not None and dl <= now_abs) or self.server.inflight_groups:
            _, t_ret = self.call("pump", self.server.pump, now_abs)
            self.collect(t_ret)
            return True
        return False

    @property
    def open_count(self) -> int:
        return len(self.pending)

    def result(self, n: int, window_s: float) -> WindowResult:
        return WindowResult(rows=self.rows[:n], rejected=self.rejected[:n],
                            refused=self.refused[:n], due=self.due[:n], submitted=self.submitted[:n],
                            done=self.done[:n], pool_index=self.pool[:n],
                            window_s=window_s, host_call_s=self.host_s,
                            calls=self.calls)


def _resubmit(lp: _Loop, requests: list, held: collections.deque,
              now_abs: float) -> bool:
    """Submit held requests, oldest first, as far as the server's queue has
    room; returns whether any was admitted."""
    server = lp.server
    room = min(server.admission.max_pending - server.pending_load, len(held))
    if room <= 0:
        return False
    take = [held.popleft() for _ in range(room)]
    full = lp.submit([requests[k] for k in take], now_abs, retry=True)
    held.extendleft(reversed(full))
    return len(full) < len(take)


def run_open(server, requests: list, due: np.ndarray, seconds: float,
             probes: Probes, grace_s: float = 60.0) -> WindowResult:
    """Open loop: each group is submitted when due (all that are due in one
    ``submit_many``), whatever the server's backlog; between arrivals the
    loop pumps age deadlines and gathers launches in flight.

    A request the server refuses for a full queue is held, as a client
    that honours the refusal holds it, and submitted again as the queue
    makes room, oldest first, with every later arrival queued behind it;
    its latency still runs from its due time.  After the last arrival the
    loop keeps serving until every request has resolved, at most
    ``grace_s`` past the window; one still held then counts as rejected.
    Request ``i`` carries tenant id ``i``."""
    n = len(requests)
    lp = _Loop(server, probes, n)
    lp.due[:] = due
    lp.pool[:] = np.arange(n)
    held: collections.deque = collections.deque()
    i = 0
    lp.t0 = t0 = clock()
    while True:
        now_abs = clock()
        now = now_abs - t0
        if i < n and due[i] <= now:
            j = int(np.searchsorted(due, now, side="right"))
            if held:
                held.extend(range(i, j))
            else:
                held.extend(lp.submit(requests[i:j], now_abs, retry=True))
            i = j
            continue
        if held and _resubmit(lp, requests, held, now_abs):
            continue
        if lp.service(now_abs):
            continue
        if i >= n and lp.open_count <= 0 and not held:
            break
        if now > seconds + grace_s:
            break
        dl = server.next_deadline()
        targets = [due[i]] if i < n else []
        if dl is not None:
            targets.append(dl - t0)
        if targets:
            _wait_until(min(targets), t0, probes)
    lp.rejected[list(held)] = True
    return lp.result(n, seconds)


def run_closed(server, pool: list, in_flight: int, seconds: float,
               probes: Probes, request_type, grace_s: float = 60.0,
               n_max: int = 1 << 20) -> WindowResult:
    """Closed loop: ``in_flight`` requests outstanding; every completion
    the harness sees is replaced in the next ``submit_many``, cycling
    through the payload pool, until the window closes.  Then no more are
    submitted and the loop serves what is left."""
    lp = _Loop(server, probes, n_max)
    k = 0

    def fresh(count: int):
        nonlocal k
        out = []
        for _ in range(count):
            src = pool[k % len(pool)]
            out.append(request_type(tenant_id=k, workload=src.workload,
                                    degree=src.degree, arrival_time=0.0,
                                    coeffs=src.coeffs))
            lp.pool[k] = k % len(pool)
            k += 1
        return out

    lp.t0 = t0 = clock()
    lp.submit(fresh(in_flight), t0)
    lp.due[:k] = 0.0
    while True:
        now_abs = clock()
        now = now_abs - t0
        if now < seconds:
            short = in_flight - lp.open_count
            if short > 0 and k + short <= n_max:
                lo = k
                lp.submit(fresh(short), now_abs)
                lp.due[lo:k] = now
                continue
        elif lp.open_count <= 0 or now > seconds + grace_s:
            break
        if lp.service(now_abs):
            continue
        dl = server.next_deadline()
        if dl is not None:
            _wait_until(min(dl - t0, seconds), t0, probes)
        elif now >= seconds:
            break
    return lp.result(k, seconds)
