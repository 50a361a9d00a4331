#!/usr/bin/env python3
"""Find an open cell's knee: one set-up, then windows at each offered rate.

    python bench/sweep.py --workload <cell> --rates 2048,4096,8192 \
        [--seconds 5] [--seed 1]

Each rate runs ``REPEATS`` windows, each on its own seed.  A window holds
when nothing is rejected or refused for a full queue (and retried), every
row is correct, its completions are within
3 % of the offered rate, and no backlog grows across it (the median latency
of the last fifth of requests at most 1.5 times that of the first fifth,
plus 2 ms).  A rate holds when most of its windows hold, so that one window
hit by a host stall does not decide it.  Prints one JSON line per window,
one per rate and, last, the knee: the highest rate that held with every
lower rate holding too.  The sweep stops after ``STOP_AFTER`` rates in a
row that did not hold.  The server is reused throughout; each window's
traffic is drawn afresh.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402

REPEATS = 3
STOP_AFTER = 2


def window_held(out: dict, seconds: float) -> bool:
    first, last = out["latency_trend_ms"]
    return (out["failed"] == 0 and out["retried"] == 0 and out["correct"]
            and out["metrics"]["ops_per_s"]["value"]
            >= 0.97 * out["attempted"] / seconds
            and last <= 1.5 * first + 2.0)


def knee_of(held_by_rate: list) -> float | None:
    """The highest rate, in the order swept, with every rate up to it
    held."""
    knee = None
    for rate, held in held_by_rate:
        if not held:
            break
        knee = rate
    return knee


def sweep(sess, mix: dict, rates, seconds: float, seed: int, emit=print):
    """Run the sweep on a built session; returns ``[(rate, held), ...]``."""
    verdicts, misses = [], 0
    for k, rate in enumerate(rates):
        wins = []
        for j in range(REPEATS):
            seed_j = seed + k * REPEATS + j
            out = R.window(sess, seed_j, seconds, False,
                           mix=dict(mix, request_rate_hz=rate))
            held = window_held(out, seconds)
            wins.append(held)
            emit(json.dumps({"rate": rate, "seed": seed_j,
                             "held": held, "correct": out["correct"],
                             "failed": out["failed"],
                             "retried": out["retried"],
                             "attempted": out["attempted"],
                             "drain_ms": out["drain_ms"],
                             "latency_trend_ms": out["latency_trend_ms"],
                             **{n: v["value"]
                                for n, v in out["metrics"].items()}}))
        held = 2 * sum(wins) > len(wins)
        verdicts.append((rate, held))
        emit(json.dumps({"rate": rate, "windows_held": sum(wins),
                         "windows": len(wins), "held": held}))
        misses = 0 if held else misses + 1
        if misses >= STOP_AFTER:
            break
    return verdicts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered request rates, req/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    bench, wl, cfg, mix = R.load_spec(args.workload)
    if mix["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    try:
        sess = R.setup(bench, wl, cfg, mix, trace=False)
    except R.NoChip as e:
        R.log(f"bench/sweep.py: {e}")
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    verdicts = sweep(sess, mix, rates, args.seconds, args.seed,
                     emit=lambda s: print(s, flush=True))
    print(json.dumps({"knee": knee_of(verdicts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
