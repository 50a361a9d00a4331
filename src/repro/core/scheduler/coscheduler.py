"""Tier 2 — the Slice-Level Co-Scheduler (paper §4.1) + the dispatch fast path.

Maps workload-homogeneous stacked batches onto *disjoint device groups* of a
pod slice so heterogeneous cryptographic primitives (Dilithium next to BN254)
execute concurrently without sharing TensorCores.  Per-class jit programs are
dispatched with batch rows sharded across the group's devices; workload-zone
scopes (:mod:`repro.core.zones`) travel into the HLO for the post-hoc
validator.

The dispatch fast path (the hottest loop in the repo) adds three levers, all
bit-for-bit neutral:

* **M-axis super-batching** (``merge``) — ``dispatch_mixed`` coalesces
  same-``(workload, d_bucket, reduction)`` stacked batches into one tall
  operand before launch, recovering the M-dimension fill the paper measures
  collapsing to 6.25% on v4.  Row semantics (Property 5.1) make the merged
  launch equal to the per-batch launches row-for-row.
* **Row-ladder compile cache** (``row_ladder``) — batch heights are padded up
  to a small geometric ladder of rungs (e.g. 8→16→…→128) so ``trace_counts``
  per ``(workload, d_bucket)`` is bounded by the ladder size instead of by
  the number of distinct arrival counts; padded rows are all-zero and sliced
  off before tenant routing.  ``precompile`` warms every rung.
* **Zero-sync two-phase pipeline** — ``launch_mixed`` enqueues every program
  and starts the device→host copies asynchronously; ``gather`` materialises
  later, so a pump loop can launch batch *n+1* before batch *n*'s result
  crosses PCIe.  ``donate=True`` additionally donates the operand buffer to
  its e2e program (``donate_argnums``), and twiddle/fused planes are passed
  as device-resident jit arguments (uploaded once per engine) instead of
  being re-embedded as host constants at every trace.

On a 1-device CPU test rig every group degenerates to the same device;
multi-device behaviour runs under ``--xla_force_host_platform_device_count``
on the CPU and on a four-chip host (``chip_smoke.py --chips 4``).
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import limb_gemm as G
from repro.core import workloads as WK
from repro.core.scheduler.rectangular import StackedBatch, merge_operands
from repro.obs.tracing import Phases

# Bounded history of per-launch merge/padding records (the serving layer
# drains it into telemetry after every dispatch; non-serving callers just
# let old entries fall off).
DISPATCH_LOG_MAX = 4096

# Minimum legal row-ladder rung.  A rung below the systolic M-tile height
# compiles a program whose operand cannot be split across a device group
# (and on real slices wastes a full sublane tile per launch); historically a
# sub-tile or shuffled ladder only surfaced later as a confusing
# launch-shape error inside XLA — now it is rejected at construction.
MIN_ROW_TILE = 2


def resolve_devices(devices):
    """Normalise a ``devices=`` spec into a list of live ``jax.Device``s.

    ``None`` means "every device in the process" (today's behaviour).
    Entries may be integer device ids or ``jax.Device`` objects; anything
    out of range, unknown, or listed twice fails loudly here — a duplicate
    or phantom device in a host slice's pin list would otherwise surface
    as two hosts silently serialising on one queue (the exact failure mode
    device pinning exists to remove).
    """
    all_devs = list(jax.devices())
    if devices is None:
        return all_devs
    by_id = {d.id: d for d in all_devs}
    resolved, seen = [], set()
    for i, entry in enumerate(devices):
        if isinstance(entry, (int, np.integer)):
            dev = by_id.get(int(entry))
            if dev is None:
                raise ValueError(
                    f"devices[{i}] = {entry} is out of range: this process "
                    f"has {len(all_devs)} JAX device(s) (ids "
                    f"0..{len(all_devs) - 1}); on CPU, widen the slice with "
                    f"XLA_FLAGS --xla_force_host_platform_device_count=N "
                    f"before the first jax use")
        else:
            dev = by_id.get(getattr(entry, "id", None))
            if dev is None or dev is not entry:
                raise ValueError(
                    f"devices[{i}] = {entry!r} is not a device of this "
                    f"process (jax.devices() has ids "
                    f"0..{len(all_devs) - 1})")
        if dev.id in seen:
            raise ValueError(
                f"devices[{i}] names device {dev.id} twice: a host slice "
                f"pinned to a repeated device would share a launch queue "
                f"with itself — each pin must be distinct")
        seen.add(dev.id)
        resolved.append(dev)
    if not resolved:
        raise ValueError("devices= must name at least one device "
                         "(use None for the whole process)")
    return resolved


def partition_devices(n_parts: int, devices=None) -> list[list]:
    """Split the process's devices into ``n_parts`` host slices.

    With D ≥ n_parts devices each slice gets a contiguous near-even chunk
    (first ``D mod n_parts`` slices get the extra device); with D <
    n_parts, slices wrap round-robin onto single devices — hosts then
    share queues, which the dispatch-overlap audit makes visible rather
    than hiding.  The cluster layer uses this when ``device_parallel`` is
    on; benches/tests call it directly to build per-device co-schedulers.
    """
    if n_parts < 1:
        raise ValueError(f"partition_devices needs n_parts >= 1, "
                         f"got {n_parts}")
    devs = resolve_devices(devices)
    if len(devs) >= n_parts:
        base, extra = divmod(len(devs), n_parts)
        out, lo = [], 0
        for i in range(n_parts):
            hi = lo + base + (1 if i < extra else 0)
            out.append(devs[lo:hi])
            lo = hi
        return out
    return [[devs[i % len(devs)]] for i in range(n_parts)]


def validate_row_ladder(row_ladder) -> tuple[int, ...]:
    """Validate a compile-cache rung ladder at construction time.

    Rungs must be unique, strictly increasing, and at least
    ``MIN_ROW_TILE`` tall; anything else raises a ``ValueError`` naming the
    offending rung instead of letting a mis-shaped ladder reach dispatch.
    """
    ladder = tuple(int(r) for r in row_ladder)
    if not ladder:
        raise ValueError("row_ladder must name at least one rung")
    low = [r for r in ladder if r < MIN_ROW_TILE]
    if low:
        raise ValueError(
            f"row_ladder rungs must be ≥ {MIN_ROW_TILE} (the minimum M-tile "
            f"height): got {low} in {ladder}")
    for prev, cur in zip(ladder, ladder[1:]):
        if cur == prev:
            raise ValueError(
                f"row_ladder has a duplicate rung {cur} in {ladder}: each "
                f"rung is one compiled program — duplicates would double-"
                f"count the trace budget")
        if cur < prev:
            raise ValueError(
                f"row_ladder must be strictly increasing, got {cur} after "
                f"{prev} in {ladder}: launch_rows snaps a height to the "
                f"first rung that fits, so a shuffled ladder launches at "
                f"the wrong height")
    return ladder


def default_row_ladder(n_max: int, n_min: int = 8) -> tuple[int, ...]:
    """Geometric rung set ``n_min, 2·n_min, … ≥ n_max`` (the compile-cache
    ladder).  ``len(default_row_ladder(128)) == 5`` — and so is the bound on
    ``trace_counts`` per program class."""
    if n_max < 1 or n_min < 1:
        raise ValueError(f"row ladder needs positive bounds "
                         f"(got n_min={n_min}, n_max={n_max})")
    rungs, r = [], n_min
    while r < n_max:
        rungs.append(r)
        r *= 2
    rungs.append(n_max)     # top rung is exactly n_max (the merge cap)
    return tuple(rungs)


@dataclasses.dataclass
class DispatchResult:
    batch: StackedBatch
    outputs: dict          # tenant_id -> result rows (numpy; last-wins if a
                           # tenant has several rows — use `rows` to route by
                           # position)
    stats: dict
    rows: object = None    # (n_rows, ...) result array, batch row order


@dataclasses.dataclass
class _LaunchGroup:
    """One compiled-program launch: ≥1 same-class batches stacked along M."""
    workload: str
    d_bucket: int
    members: list          # (input index, StackedBatch, row_lo, row_hi)
    operand_rows: int = 0  # stacked operand height before ladder padding
    live_rows: int = 0     # tenant rows only (excludes batcher zero-pad rows)
    lid: int = 0           # causal launch ID (0 when tracing is off)


@dataclasses.dataclass
class InflightDispatch:
    """launch_mixed → gather handle: device results with D2H copies already
    streaming; gathering materialises without re-synchronising launches."""
    groups: list           # (_LaunchGroup, engine, device result)
    n_batches: int


class SliceCoScheduler:
    """Static workload → device-group assignment over a pod slice.

    ``reduction`` sets the default fold discipline; ``reduction_by_workload``
    overrides it per workload class, so lazy (κ-amortised) tenants can share
    the slice with strictly-eager tenants — each class keeps its own engines,
    compiled programs, and device group, so the disciplines never mix inside
    one program (paper §7.2.1).  Mode strings are validated here: a typo must
    fail construction, not silently trace the eager path — and so must an
    all-eager config carrying κ>1, which used to construct silently and only
    blow up (or record a bogus κ) deep in dispatch.
    """

    def __init__(self, assignment: dict[str, list] | None = None,
                 *, accum: str = "fp32_mantissa", reduction: str = "eager",
                 reduction_by_workload: dict[str, str] | None = None,
                 kappa: int | None = None, d_tile: int | None = None,
                 merge: bool = True, row_ladder: tuple | None = None,
                 merge_rows_max: int = 128, donate: bool = False,
                 host: int | None = None, devices=None):
        # devices= pins this co-scheduler to an explicit device sub-slice
        # (ints or jax.Device objects; validated by resolve_devices).  The
        # pin is what makes a cluster host slice's launches land on *its*
        # device instead of the process default: operands are committed via
        # _shard, and the engine's cached twiddle planes are re-homed per
        # co-scheduler (device_planes_for) because make_engine is shared
        # process-wide.  devices=None keeps today's behaviour bit-for-bit.
        self._pinned = devices is not None
        pinned = resolve_devices(devices)
        if assignment is None:
            # default: split the slice evenly across workload classes
            assignment = {"dilithium": pinned[: max(1, len(pinned) // 2)],
                          "bn254": pinned[max(1, len(pinned) // 2):] or pinned}
            self.devices = pinned
        else:
            ordered: dict[int, object] = {}
            for devs in assignment.values():
                for d in devs:
                    ordered.setdefault(d.id, d)
            self.devices = list(ordered.values())
        self.assignment = assignment
        self.accum = accum
        self.reduction = G.check_reduction(reduction)
        self.reduction_by_workload = dict(reduction_by_workload or {})
        for w, mode in self.reduction_by_workload.items():
            if w not in WK.CLASSES:
                raise ValueError(f"unknown workload class {w!r} in "
                                 f"reduction_by_workload")
            G.check_reduction(mode)
        # κ only means something under lazy folding: if no class is lazy,
        # reject the deferral depth at construction time.
        modes = {self.reduction} | set(self.reduction_by_workload.values())
        if "lazy" not in modes:
            G.check_reduction(self.reduction, kappa)
        self.kappa = kappa
        self.d_tile = d_tile
        self.merge = merge
        if row_ladder is not None:
            row_ladder = validate_row_ladder(row_ladder)
        self.row_ladder = row_ladder
        self.merge_rows_max = (row_ladder[-1] if row_ladder
                               else merge_rows_max)
        self.donate = donate
        # Cluster mode runs one co-scheduler per host slice; the owning host id
        # travels into per-host telemetry so compiled-program caches and trace
        # counters stay attributable after snapshots are merged.
        self.host = host
        self._meshes = {
            w: Mesh(np.asarray(devs), ("rows",))
            for w, devs in assignment.items()
        }
        # (workload, split rows?) -> the operand sharding, built once.
        self._shardings: dict = {}
        # Operand device_put calls (launches, warm-up, validation); each
        # dispatch_log record carries its launch's share.
        self.placements = 0
        self._engines: dict = {}
        self._jitted: dict = {}
        # (workload, d_bucket) -> device-resident twiddle/fused planes.
        # Engines (make_engine) are an lru-cached *process-wide* resource
        # whose device_planes() upload lands on the default device; a pinned
        # co-scheduler re-homes the planes onto its own mesh exactly once
        # here, so N host slices never share one host's plane buffers.
        self._planes: dict = {}
        # (workload, d_bucket) -> number of times XLA retraced the program.
        # Incremented inside the traced body, so cached executions leave it
        # untouched; with a row ladder the count is bounded by the ladder
        # size (one trace per rung), asserted by the retrace-guard tests.
        self.trace_counts: dict = {}
        # One record per launched program (merge width, live vs launched
        # rows) — the serving telemetry's per-dispatch M-occupancy source.
        self.dispatch_log: collections.deque = collections.deque(
            maxlen=DISPATCH_LOG_MAX)
        # Observability hook (repro.obs.Tracer), installed by the serving
        # layer when tracing is on: launches then emit device-track spans on
        # the anchored serving clock and dispatch_log entries carry a causal
        # launch ID ("lid") linking them to batch/request spans.
        self.tracer = None
        # Leaf phases of each launch group (stage, call, d2h); the serving
        # layer rebinds this to its own record.
        self.phases = Phases()

    def reduction_for(self, workload: str) -> str:
        """The fold discipline this slice applies to a workload class."""
        return self.reduction_by_workload.get(workload, self.reduction)

    def device_ids(self, workload: str | None = None) -> tuple[int, ...]:
        """Device ids this co-scheduler launches on — the whole slice, or
        one workload class's group (telemetry / placement assertions)."""
        if workload is None:
            return tuple(d.id for d in self.devices)
        return tuple(d.id for d in self._meshes[workload].devices.flat)

    def device_planes_for(self, workload: str, d: int):
        """The engine's device-resident planes, placed once on this
        co-scheduler's device group.  Only an unpinned one-device slice
        passes the engine's default-device upload through (re-uploading
        there would double memory); on a pinned slice, or a workload group
        of a multi-device slice, planes left on the default device would be
        copied onto the group's devices at every launch."""
        key = (workload, d)
        planes = self._planes.get(key)
        if planes is None:
            planes = self.engine_for(workload, d).device_planes()
            if self._pinned or len(self.devices) > 1:
                sharding = NamedSharding(self._meshes[workload], P())
                planes = jax.device_put(planes, sharding)
            self._planes[key] = planes
        return planes

    def engine_for(self, workload: str, d: int):
        key = (workload, d)
        if key not in self._engines:
            mode = self.reduction_for(workload)
            # κ belongs to the lazy classes only: an eager engine carrying a
            # deferral depth would refuse to trace (check_reduction) — and
            # recording one that never happened would corrupt bench records.
            self._engines[key] = WK.make_engine(
                workload, d, accum=self.accum, reduction=mode,
                kappa=self.kappa if mode == "lazy" else None,
                d_tile=self.d_tile)
        return self._engines[key]

    def jitted_for(self, workload: str, d: int):
        """One compiled e2e program per (workload, d_bucket), reused across
        dispatches — rebuilding ``jax.jit(eng.e2e)`` per dispatch discards the
        executable cache and recompiles every batch.  The twiddle planes are
        jit *arguments* (device-resident, uploaded once per engine), so a
        ladder retrace at a new batch height re-embeds no host constants; with
        ``donate`` the operand buffer is donated to the program."""
        key = (workload, d)
        if key not in self._jitted:
            eng = self.engine_for(workload, d)

            def _e2e(operand, planes, _eng=eng, _key=key):
                self.trace_counts[_key] = self.trace_counts.get(_key, 0) + 1
                return _eng.e2e(operand, planes=planes)

            self._jitted[key] = jax.jit(
                _e2e, donate_argnums=(0,) if self.donate else ())
        return self._jitted[key]

    def launch_rows(self, n_rows: int) -> int:
        """Launched operand height for ``n_rows`` live rows: the smallest
        ladder rung ≥ n_rows, or n_rows itself without a ladder (or beyond
        the top rung — oversize batches launch at natural height)."""
        if self.row_ladder is not None:
            for rung in self.row_ladder:
                if rung >= n_rows:
                    return rung
        return n_rows

    def operand_shape(self, workload: str, d: int, n_c: int) -> tuple:
        """Device operand shape of one ``n_c``-live-row launch — the jit
        cache key (ladder-padded when a row ladder is configured)."""
        rows = self.launch_rows(n_c)
        if workload == "dilithium":
            return (rows, d)
        return (rows, d, self.engine_for(workload, d).n_channels)

    def precompile(self, programs, n_c: int) -> int:
        """Warm-start the compiled-program cache: trace + compile the known
        ``(workload, d_bucket)`` set before first dispatch, so cold-start p99
        is not dominated by XLA compilation.  Without a row ladder one
        ``n_c``-row shape per program is warmed; with a ladder every rung is
        (live heights then always hit a warm rung).  Returns the number of
        fresh traces this triggered; a later dispatch of any warmed program
        at a warmed shape must trigger zero more (asserted via
        ``trace_counts`` in the serving tests)."""
        rungs = list(self.row_ladder) if self.row_ladder else [n_c]
        n_new = 0
        for workload, d in programs:
            key = (workload, d)
            planes = self.device_planes_for(workload, d)
            before = self.trace_counts.get(key, 0)
            for rung in rungs:
                operand = np.zeros(self.operand_shape(workload, d, rung),
                                   np.uint32)
                out = self.jitted_for(workload, d)(
                    self._shard(workload, operand), planes)
                jax.block_until_ready(out)
            n_new += self.trace_counts.get(key, 0) - before
        return n_new

    def _sharding(self, workload: str, rows: int) -> NamedSharding:
        """Operand sharding of a ``rows``-tall launch on ``workload``'s
        device group: rows split over the group (``P("rows")``) when they
        divide evenly over more than one device, else whole on each."""
        mesh = self._meshes[workload]
        split = mesh.devices.size > 1 and rows % mesh.devices.size == 0
        sharding = self._shardings.get((workload, split))
        if sharding is None:
            sharding = NamedSharding(mesh, P("rows") if split else P())
            self._shardings[(workload, split)] = sharding
        return sharding

    def _shard(self, workload: str, operand):
        """Place an operand on its workload's device group with one
        ``device_put``.  A host (numpy) operand goes straight to each of
        its shards; launches, warm-up and validation all come through
        here, so their operands share one committed sharding and hence
        one executable."""
        self.placements += 1
        return jax.device_put(operand,
                              self._sharding(workload, operand.shape[0]))

    # --- group planning + launch ----------------------------------------------

    def _plan_groups(self, batches: list[StackedBatch]) -> list[_LaunchGroup]:
        """Cut a dispatch set into launch groups: same-(workload, d_bucket,
        reduction) batches coalesce along M (``merge``) up to the top ladder
        rung / ``merge_rows_max``; groups keep first-appearance launch order
        and members remember their input index for order-preserving gather."""
        groups: list[_LaunchGroup] = []
        open_group: dict[tuple, _LaunchGroup] = {}
        for i, b in enumerate(batches):
            rows = b.operand.shape[0] if b.operand is not None else b.n_c
            key = (b.workload, b.d_bucket, self.reduction_for(b.workload))
            g = open_group.get(key) if self.merge else None
            if g is None or g.operand_rows + rows > self.merge_rows_max:
                g = _LaunchGroup(workload=b.workload, d_bucket=b.d_bucket,
                                 members=[])
                groups.append(g)
                if self.merge:
                    open_group[key] = g
            g.members.append((i, b, g.operand_rows, g.operand_rows + rows))
            g.operand_rows += rows
            g.live_rows += b.n_c
        return groups

    def _member_operand(self, batch: StackedBatch, eng) -> np.ndarray:
        if batch.workload == "dilithium":
            return np.asarray(batch.operand, np.uint32)    # (N, d)
        if batch.operand.ndim == 2:                        # raw words → residues
            return np.asarray(eng.ingest(batch.operand.astype(object)))
        return np.asarray(batch.operand)                   # (N, d, C)

    def _launch(self, group: _LaunchGroup):
        """Enqueue one launch group on its workload's device group and return
        the in-flight device result without materialising it."""
        with self.phases.stage:
            eng = self.engine_for(group.workload, group.d_bucket)
            members = [self._member_operand(b, eng)
                       for _, b, _, _ in group.members]
            rows = self.launch_rows(group.operand_rows)
            if len(members) == 1 and members[0].shape[0] == rows:
                operand_np = members[0]    # singleton at a rung: no host copy
            else:
                operand_np = merge_operands(members, n_rows=rows)
            placed = self.placements
            operand = self._shard(group.workload, operand_np)
            program = self.jitted_for(group.workload, group.d_bucket)
            planes = self.device_planes_for(group.workload, group.d_bucket)
        with self.phases.call:
            out = program(operand, planes)
        tr = self.tracer
        if tr is not None:
            group.lid = tr.next_id()
            tr.begin("launch", group.lid,
                     f"launch:{group.workload}/d{group.d_bucket}",
                     tr.wall_now(), track="device",
                     args={"live_rows": group.live_rows,
                           "launched_rows": int(operand_np.shape[0]),
                           "n_batches": len(group.members)})
        # live_rows counts tenant rows only — batcher zero-pad rows inside a
        # member operand are dead M just like ladder padding, so they must
        # not inflate the achieved-fill telemetry.
        self.dispatch_log.append({
            "workload": group.workload, "d_bucket": group.d_bucket,
            "n_batches": len(group.members), "live_rows": group.live_rows,
            "launched_rows": int(operand_np.shape[0]),
            "donated": self.donate, "lid": group.lid,
            "devices": self.device_ids(group.workload),
            "staged_bytes": int(operand_np.nbytes),
            "placements": self.placements - placed,
            "transform_stages": eng.transform_stages,
            "field_reductions": eng.field_reductions})
        return group, eng, out

    def _materialise(self, group: _LaunchGroup, eng, out):
        """Gather one group's device result and split it back into one
        :class:`DispatchResult` per member batch (ladder-pad rows dropped,
        rows routed by position within each member's slice)."""
        with self.phases.d2h:
            res = np.asarray(out)
        tr = self.tracer
        if tr is not None:
            tr.end("launch", group.lid,
                   f"launch:{group.workload}/d{group.d_bucket}",
                   tr.wall_now(), track="device")
        # last_stats is trace-time state (one channel's staged_transform);
        # fold_profile is the static whole-program census — deterministic per
        # (workload, d_bucket) and what the serve telemetry aggregates.
        stats = dict(getattr(eng, "last_stats", {}) or {})
        stats.update(eng.fold_profile)
        results = []
        for idx, batch, lo, hi in group.members:
            rows = res[lo:hi]
            outputs = {r.tenant_id: rows[i]
                       for i, r in enumerate(batch.requests)}
            results.append((idx, DispatchResult(
                batch=batch, outputs=outputs, stats=dict(stats), rows=rows)))
        return results

    @staticmethod
    def _start_transfer(out):
        """Begin the device→host copy without blocking (phase 2 of the
        zero-sync pipeline; ``np.asarray`` in gather then finds the bytes
        already on their way)."""
        for leaf in jax.tree_util.tree_leaves(out):
            copy = getattr(leaf, "copy_to_host_async", None)
            if copy is not None:
                copy()

    # --- public dispatch surface ----------------------------------------------

    def launch_mixed(self, batches: list[StackedBatch]) -> InflightDispatch:
        """Phase 1+2 of a dispatch: enqueue every launch group (all launches
        before any host transfer — materialising between launches would
        serialise the device groups behind a blocking ``np.asarray``), then
        start every device→host copy asynchronously."""
        inflight = [self._launch(g) for g in self._plan_groups(batches)]
        for _, _, out in inflight:
            self._start_transfer(out)
        return InflightDispatch(groups=inflight, n_batches=len(batches))

    def gather(self, flight: InflightDispatch) -> list[DispatchResult]:
        """Phase 3: materialise an in-flight dispatch, input batch order."""
        results: list = [None] * flight.n_batches
        for f in flight.groups:
            for idx, dr in self._materialise(*f):
                results[idx] = dr
        return results

    def dispatch(self, batch: StackedBatch) -> DispatchResult:
        """Execute one stacked batch on its workload's device group."""
        return self.dispatch_mixed([batch])[0]

    def dispatch_mixed(self, batches: list[StackedBatch]) -> list[DispatchResult]:
        """Concurrent heterogeneous dispatch: per-class programs launched
        back-to-back; XLA queues them on disjoint device groups so Dilithium
        and BN254 batches overlap on real multi-device slices, while
        same-class batches coalesce into tall super-batches (``merge``)."""
        return self.gather(self.launch_mixed(batches))

    def drain_dispatch_log(self) -> list[dict]:
        """Hand the accumulated per-launch records to the caller (serving
        telemetry) and reset the log."""
        log = list(self.dispatch_log)
        self.dispatch_log.clear()
        return log
