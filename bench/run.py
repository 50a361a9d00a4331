#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip JAX finds, and print one JSON
line of results as the last line of standard output.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``) in ``BENCHMARK.json``.  The run
builds the server from the configuration, warms up every shape the traffic
can produce, drives the traffic for ``--seconds`` through the server's
``submit_many`` / ``pump`` entry, then compares the served rows with the
plain references in ``bench/reference``.  With ``--trace 0`` it reports the
cell's end-to-end metrics; with ``--trace 1`` it records a profiler trace of
the window and reports the cell's per-layer metrics, read by
``bench/metrics/<metric>.py``.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, "bench_out")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check as CHK  # noqa: E402
from bench import harness as H  # noqa: E402
from bench import payloads as PL  # noqa: E402
from bench import stats as ST  # noqa: E402
from bench import traffic as TR  # noqa: E402

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_spec(cell: str) -> tuple:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise SystemExit(f"unknown workload {cell!r}; known: {sorted(cells)}")
    wl = cells[cell]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return bench, wl, cfg, TR.load_mix(wl["traffic"])


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def apply_control(cfg: dict) -> dict:
    """The configuration with its control switched on: a class swapped for
    another of the program's own classes."""
    ctl = cfg["control"]
    cfg = json.loads(json.dumps(cfg))
    if ctl["kind"] == "class_swap":
        for c in cfg["classes"]:
            if c["workload"] == ctl["from"]:
                c["workload"] = ctl["to"]
        folds = cfg["guarantees"]["fold"]
        folds[ctl["to"]] = folds.pop(ctl["from"])
    return cfg


def install_f32_control(cos, workload: str):
    """Put the plain NTT, computed in float32, in the program's place for
    one class: the control of a configuration whose program has no lower-
    precision path of its own."""
    import jax
    import jax.numpy as jnp
    from bench.reference import dilithium as DIL
    jitted_for = cos.jitted_for
    cache: dict = {}

    def control_for(w, d):
        if w != workload:
            return jitted_for(w, d)
        if d not in cache:
            mat = jnp.asarray(DIL.ntt_matrix(d).astype(np.float32))

            def f32_ntt(operand, planes):
                y = jnp.dot(operand.astype(jnp.float32), mat,
                            precision=jax.lax.Precision.HIGHEST)
                return jnp.mod(y, jnp.float32(DIL.Q)).astype(jnp.uint32)
            cache[d] = jax.jit(f32_ntt)
        return cache[d]

    cos.jitted_for = control_for


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run of the cell named in ``BENCHMARK.json``."""
    sess = setup(*load_spec(cell), trace=trace, **kw)
    return window(sess, seed, seconds, trace)


@dataclasses.dataclass
class Session:
    """A built, warmed server and what its set-up recorded."""
    bench: dict
    wl: dict
    cfg: dict
    mix: dict
    devices: list
    server: object
    probes: object
    request_type: type
    compile_s: dict
    validate_s: list
    cache_dir: str
    marks: dict


def _time_validation(srv, validate_s: list, lock):
    """Add the seconds of each of the server's HLO validations to
    ``validate_s[0]``."""
    validate_once = srv._validate_once

    def timed_validate(batch):
        t = time.perf_counter()
        validate_once(batch)
        with lock:
            validate_s[0] += time.perf_counter() - t
    srv._validate_once = timed_validate


def setup(bench: dict, wl: dict, cfg: dict, mix: dict, *, trace: bool,
          require_tpu: bool = True, control: bool = False,
          setup_hook=None, cache: bool = True) -> Session:
    """Build the server of a cell's configuration on the first ``chips``
    devices and warm up every shape its traffic can produce.

    ``setup_hook(server, cos)`` runs after the server is built, once for
    each host slice's server and co-scheduler (the tests plant faults
    through it); ``control`` runs the configuration's control in the
    program's place; ``cache`` keeps compiled programs in the checkout's
    ``.jax_cache/``."""
    if control and cfg["control"]["kind"] == "class_swap":
        cfg = apply_control(cfg)
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < wl["chips"]):
        raise NoChip(f"needs {wl['chips']} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind!r})")
    from repro.core.scheduler.queue import TenantRequest
    cache_dir = None
    if cache:
        # Fixed inside the checkout, so only a cell's first run there
        # compiles; the program's cache follows this variable.
        from repro.serve import enable_compilation_cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        cache_dir = enable_compilation_cache()
        # No cap on its size: a cap (JAX_COMPILATION_CACHE_MAX_SIZE) below
        # a cell's programs evicts them, least recently used first, so
        # every run compiles them all again.
        jax.config.update("jax_compilation_cache_max_size", -1)
    marks = {"init": time.perf_counter()}

    compile_s: dict = {}
    lock = threading.Lock()      # the warm-up compiles on several threads

    def on_duration(event, secs, **_):
        key = COMPILE_EVENTS.get(event)
        if key:
            with lock:
                compile_s[key] = compile_s.get(key, 0.0) + secs
                compile_s["n_" + key] = compile_s.get("n_" + key, 0) + 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devs = devs[:wl["chips"]]
    server = H.build_server(cfg, devs)
    validate_s = [0.0]
    for srv in H.hosts_of(server):
        _time_validation(srv, validate_s, lock)
        if control and cfg["control"]["kind"] == "reference_f32":
            install_f32_control(srv.cos, cfg["control"]["workload"])
        if setup_hook is not None:
            setup_hook(srv, srv.cos)
    probes = H.Probes(H.coschedulers(server), annotate=trace)
    marks["built"] = time.perf_counter()
    marks["warm_launches"] = H.warm_up(server, cfg, TenantRequest, marks)
    marks["warm"] = time.perf_counter()
    return Session(bench=bench, wl=wl, cfg=cfg, mix=mix, devices=devs,
                   server=server, probes=probes,
                   request_type=TenantRequest, compile_s=compile_s,
                   validate_s=validate_s, cache_dir=cache_dir, marks=marks)


TELEMETRY = ("dispatches", "live_rows", "launched_rows", "requests_served")


def _traces(coss) -> int:
    return sum(sum(cos.trace_counts.values()) for cos in coss)


def _telemetry(hosts) -> dict:
    """The servers' dispatch counters, summed over host slices."""
    return {k: sum(srv.telemetry.live[k] for srv in hosts) for k in TELEMETRY}


def _admissions(hosts) -> dict:
    out: dict = {}
    for srv in hosts:
        for k, v in srv.telemetry.admission_counts.items():
            out[k] = out.get(k, 0) + v
    return out


def window(sess: Session, seed: int, seconds: float, trace: bool,
           mix: dict | None = None) -> dict:
    """Draw the cell's traffic from ``seed``, drive it for ``seconds``,
    compare the served rows with the references, and return the result
    line's object.  ``mix`` overrides the cell's mix (for sweeps)."""
    import jax
    mix = mix or sess.mix
    cfg, server, probes = sess.cfg, sess.server, sess.probes
    cell, devs, compile_s = sess.wl["name"], sess.devices, sess.compile_s
    hosts, coss = H.hosts_of(server), H.coschedulers(server)
    t_pay = time.perf_counter()
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        sched = TR.open_schedule(mix, cfg["classes"], seconds, rng)
    else:
        sched = TR.closed_pool(cfg["classes"], TR.pool_size(mix), rng)
    served, truth = PL.make_payloads(sched.workloads, sched.degrees, rng,
                                     H.moduli_for(server))
    requests = [sess.request_type(tenant_id=i, workload=w, degree=int(d),
                                  arrival_time=0.0, coeffs=c)
                for i, (w, d, c) in enumerate(zip(sched.workloads,
                                                  sched.degrees, served))]
    payload_s = time.perf_counter() - t_pay
    # The traffic made ahead of the window is the harness's, not the
    # server's: move it out of the collector's reach, so that a full
    # collection inside the window walks only what the server allocates.
    gc.collect()
    gc.freeze()
    traces_before = _traces(coss)
    compiles_before = compile_s.get("n_backend_compile", 0)
    live0 = _telemetry(hosts)
    adm0 = _admissions(hosts)
    probes.reset()

    trace_dir = os.path.join(OUT_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc_pauses: list = []                 # (generation, seconds)
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            gc_pauses.append((info["generation"],
                              time.perf_counter() - gc_start[0]))
    gc.callbacks.append(on_gc)
    setup_s = time.perf_counter() - T_START
    with probes.span("window"):
        if mix["loop"] == "open":
            win = H.run_open(server, requests, sched.due, seconds, probes)
        else:
            win = H.run_closed(server, requests, int(mix["in_flight"]),
                               seconds, probes, sess.request_type)
    gc.callbacks.remove(on_gc)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    traces_in_window = _traces(coss) - traces_before
    compiles_in_window = (compile_s.get("n_backend_compile", 0)
                          - compiles_before)
    live1 = _telemetry(hosts)
    adm1 = _admissions(hosts)

    # --- what the window did ---------------------------------------------
    n = len(win.rows)
    rejected = win.rejected
    answered = np.array([r is not None for r in win.rows], bool)
    seen = answered & ~np.isnan(win.done)
    latency = (win.done - win.due)[seen]
    if not len(latency):
        raise RuntimeError(f"no request of {n} completed in the window")
    in_window = seen & (win.done <= win.window_s)
    lateness = win.submitted - win.due
    rejects = {k: adm1.get(k, 0) - adm0.get(k, 0) for k in adm1
               if k != "ok" and adm1.get(k, 0) - adm0.get(k, 0)}

    # --- correctness ------------------------------------------------------
    t_ref = time.perf_counter()
    records = []
    for i, row in enumerate(win.rows):
        if rejected[i]:
            continue
        j = int(win.pool_index[i])
        records.append((sched.workloads[j], int(sched.degrees[j]), truth[j],
                        None if row is None else np.asarray(row),
                        bool(answered[i])))
    chk = CHK.run_checks(records, np.random.default_rng([seed, 1]))
    if "cluster" in cfg:
        chk["checks"].update(CHK.check_delivery(
            ((h, [r.tenant_id for r in reqs]) for h, reqs in probes.answered),
            len(hosts)))
    ref_s = time.perf_counter() - t_ref
    correct = all(c["value"] <= c["limit"] for c in chk["checks"].values())

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    ctx = {"window": win, "probes": probes, "cfg": cfg,
           "telemetry": {k: live1[k] - live0[k] for k in TELEMETRY},
           "n_submitted": n, "trace": None, "busy_s": None,
           "device_kind": dev.device_kind}
    out = {"correct": correct, "attempted": n,
           "failed": int(rejected.sum()
                         + chk["checks"]["unanswered"]["value"])}
    metrics: dict = {}
    if trace:
        from bench import trace_reduce as TRR
        t_red = time.perf_counter()
        summ = TRR.load(TRR.find_xplane(trace_dir))
        by_chip = list(TRR.busy_by_device(summ).values())
        ctx.update(trace=summ, busy_s=sum(by_chip) / max(1, len(by_chip)))
        device.update(busy_s=ctx["busy_s"], window_s=summ.window_s)
        for m in metrics_for(sess.bench, cell, "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": TRR.top_ops(summ),
                            "idle_gaps": TRR.idle_gaps(summ)}
        log(f"trace: {len(summ.ops)} device ops on {summ.devices}, window "
            f"{summ.window_s:.3f}s, busy {ctx['busy_s']:.3f}s (by chip: "
            + ", ".join(f"{b:.3f}" for b in by_chip)
            + f"), reduced in {time.perf_counter() - t_red:.1f}s")
    else:
        e2e = {"p99_ms": ST.percentile(latency, 99) * 1e3,
               "p50_ms": ST.percentile(latency, 50) * 1e3,
               "ops_per_s": int(in_window.sum()) / win.window_s,
               "setup_s": setup_s}
        for m in metrics_for(sess.bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    tail = win.done[seen].max() - win.window_s
    fifth = max(1, len(latency) // 5)
    trend = (float(np.median(latency[:fifth])) * 1e3,
             float(np.median(latency[-fifth:])) * 1e3)
    out["drain_ms"], out["latency_trend_ms"] = tail * 1e3, trend
    out["retried"] = int(win.refused.sum())
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = chk["checks"]

    # --- the run's account, on standard error -----------------------------
    by_host = np.bincount([rec[4] for rec in probes.launches],
                          minlength=len(hosts)).tolist()
    mk = sess.marks
    log(f"cell {cell}: seed {seed}, {mix['loop']} loop, {n} requests "
        f"({len(set(sched.workloads))} classes), window {seconds}s"
        + (f", rate {mix['request_rate_hz']} req/s"
           if mix["loop"] == "open" else f", {mix['in_flight']} in flight"))
    log(f"compile cache: {sess.cache_dir}")
    log(f"set-up {setup_s:.2f}s: init {mk['init'] - T_START:.2f}s, build "
        f"{mk['built'] - mk['init']:.2f}s, warm-up {mk['warm'] - mk['built']:.2f}"
        f"s (compiled for every chip {mk['compiled'] - mk['built']:.2f}s, "
        f"other hosts {mk['warm'] - mk['compiled']:.2f}s; "
        f"{mk['warm_launches']} launches; trace "
        f"{compile_s.get('trace', 0):.2f}s, lower "
        f"{compile_s.get('lower', 0):.2f}s, backend compile or cache fetch "
        f"{compile_s.get('backend_compile', 0):.2f}s in "
        f"{compile_s.get('n_backend_compile', 0)} programs, validation "
        f"{sess.validate_s[0]:.2f}s), payloads {payload_s:.2f}s")
    log(f"in the window: {traces_in_window} retraces, {compiles_in_window} "
        f"backend compiles (both should be 0)")
    log(f"garbage collector in the window: {len(gc_pauses)} collections, "
        f"{sum(g == 2 for g, _ in gc_pauses)} of generation 2, longest "
        f"{max((t for _, t in gc_pauses), default=0.0) * 1e3:.1f} ms, total "
        f"{sum(t for _, t in gc_pauses) * 1e3:.1f} ms")
    log(f"generator lateness: p50 {ST.percentile(lateness, 50) * 1e3:.3f} ms,"
        f" p99 {ST.percentile(lateness, 99) * 1e3:.3f} ms")
    log(f"completed {int(answered.sum())}/{n} ({int(in_window.sum())} inside "
        f"the window, last {tail * 1e3:.1f} ms after it), rejected "
        f"{int(rejected.sum())}, held for a full queue and retried "
        f"{out['retried']} (refusals {rejects}), launches "
        f"{ctx['telemetry']['dispatches']} (by host {by_host}), host calls "
        f"{win.calls}")
    log(f"latency from due: p50 {ST.percentile(latency, 50) * 1e3:.3f} ms, "
        f"p99 {ST.percentile(latency, 99) * 1e3:.3f} ms, max "
        f"{latency.max() * 1e3:.3f} ms over {len(latency)} requests; median "
        f"of the first fifth {trend[0]:.3f} ms, of the last {trend[1]:.3f} "
        f"ms")
    log(f"reference: {chk['compared']} in {ref_s:.2f}s")
    for name, c in chk["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control in place of the "
                         "program (should come out not correct)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control)
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
