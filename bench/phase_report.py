#!/usr/bin/env python3
"""Where the host time of a cell's windows went, by the program's own
account: its leaf phases, its per-class waits, and in a traced window the
device-idle time each phase covers.

    python bench/phase_report.py \
        --runs mixed74.poisson:11:0,mixed74.backlog:12:1 [--seconds 10]

Each run is ``cell:seed:trace``.  The cells of one call share one
configuration, and one set-up serves every window; each window is the one
``bench/run.py`` drives.  Around each window the server's running phase and
wait counters are read, and their longest occurrences restarted, so each
window reports its own: on standard error, every phase's seconds, calls and
longest call, every class's mean and longest wait per stage, in a traced
window the device-idle seconds by phase and the phases' coverage of the
harness's own host timers; on standard output, one JSON line a window.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import phase_trace as PT  # noqa: E402
from bench import run as R  # noqa: E402
from bench import trace_reduce as TRR  # noqa: E402

SERVER = ("admit", "enqueue", "validate", "account", "resolve")
COSCHED = ("stage", "call", "d2h")


def _counters(server) -> dict:
    live = server.telemetry.live
    return copy.deepcopy({"phases": live["phases"], "waits": live["waits"]})


def _delta(before: dict, after: dict) -> tuple:
    """Window phases and waits: sums and counts subtracted, the longest
    (restarted at the window's start) as read at its end."""
    phases = {}
    for name, (s, n, longest) in sorted(after["phases"].items()):
        s0, n0, _ = before["phases"].get(name, (0.0, 0, 0.0))
        if n > n0:
            phases[name] = {"seconds": s - s0, "calls": n - n0,
                            "longest_s": longest}
    waits = {}
    for w, by_stage in sorted(after["waits"].items()):
        for stage, (s, n, longest) in by_stage.items():
            s0, n0, _ = before["waits"].get(w, {}).get(stage, (0.0, 0, 0.0))
            if n > n0:
                waits.setdefault(w, {})[stage] = {
                    "mean_s": (s - s0) / (n - n0), "requests": n - n0,
                    "longest_s": longest}
    return phases, waits


def report(sess, cell: str, seed: int, seconds: float, trace: bool) -> dict:
    """One window of ``cell`` on a built session, with the program's phase
    and wait account of it."""
    bench, wl, _, mix = R.load_spec(cell)
    run = dataclasses.replace(sess, bench=bench, wl=wl, mix=mix)
    server = sess.server
    sess.probes.annotate = trace
    server.telemetry.reset_longest()
    before = _counters(server)
    out = R.window(run, seed, seconds, trace)
    phases, waits = _delta(before, _counters(server))
    launches = len(sess.probes.launches)
    line = {"cell": cell, "seed": seed, "trace": int(trace),
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "retried": out["retried"],
            "launches": launches, "phases": phases, "waits": waits,
            "metrics": {n: v["value"] for n, v in out["metrics"].items()}}
    R.log(f"phases of {cell} seed {seed} ({out['attempted']} requests, "
          f"{launches} launches; window and drain):")
    for name, p in phases.items():
        R.log(f"  {name:9s} {p['seconds']:9.4f} s in {p['calls']:7d} calls,"
              f" longest {p['longest_s'] * 1e3:9.3f} ms")
    for w, by_stage in waits.items():
        R.log(f"  waits {w}: " + ", ".join(
            f"{stage} mean {x['mean_s'] * 1e3:.3f} ms longest "
            f"{x['longest_s'] * 1e3:.3f} ms" for stage, x in by_stage.items()))
    if trace:
        path = TRR.find_xplane(PT.TRACE_DIR)
        summary = TRR.load(path)
        spans = PT.window_phases({"trace": summary}) or []
        by = PT.idle_by_phase(summary, spans)
        idle = sum(by.values())
        busy = TRR.busy_s(summary)
        line["idle_by_phase"] = by
        line["idle_s"], line["window_s"] = idle, summary.window_s
        R.log(f"  device idle {idle:.4f} s of {summary.window_s:.4f} s "
              f"(busy {busy:.4f} s; idle + busy - window "
              f"{(idle + busy - summary.window_s) * 1e3:.3f} ms): "
              + ", ".join(f"{k} {v:.4f} s" for k, v in
                          sorted(by.items(), key=lambda kv: -kv[1])))
        line["coverage"] = coverage(phases, out, launches)
        for k, v in line["coverage"].items():
            R.log(f"  coverage {k}: {v}")
    return line


def coverage(phases: dict, out: dict, launches: int) -> dict:
    """The phases against the harness's timers of the same window, where
    the cell reports those: server phases per request against
    ``ingress.host_us_per_req``, co-scheduler phases per launch against
    ``cosched.host_ms_per_launch``."""
    m = {n: v["value"] for n, v in out["metrics"].items()}
    got = {}
    if "ingress.host_us_per_req" in m and out["attempted"]:
        own = sum(phases.get(p, {}).get("seconds", 0.0) for p in SERVER)
        us = own / out["attempted"] * 1e6
        got["server_us_per_req"] = [us, m["ingress.host_us_per_req"],
                                    us / m["ingress.host_us_per_req"]]
    if "cosched.host_ms_per_launch" in m and launches:
        own = sum(phases.get(p, {}).get("seconds", 0.0) for p in COSCHED)
        ms = own / launches * 1e3
        got["cosched_ms_per_launch"] = [ms, m["cosched.host_ms_per_launch"],
                                        ms / m["cosched.host_ms_per_launch"]]
    return got


def parse_runs(text: str) -> list:
    runs = []
    for item in text.split(","):
        cell, seed, trace = item.split(":")
        runs.append((cell, int(seed), trace == "1"))
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", required=True,
                    help="comma-separated cell:seed:trace windows")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    runs = parse_runs(args.runs)
    specs = {cell: R.load_spec(cell) for cell, _, _ in runs}
    if len({spec[1]["config"] for spec in specs.values()}) != 1:
        raise SystemExit("the cells of one call must share a configuration")
    try:
        sess = R.setup(*specs[runs[0][0]], trace=True)
    except R.NoChip as e:
        R.log(f"bench/phase_report.py: {e}")
        return 2
    for cell, seed, trace in runs:
        print(json.dumps(report(sess, cell, seed, args.seconds, trace)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
