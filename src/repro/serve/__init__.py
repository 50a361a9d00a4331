"""repro.serve — online multi-tenant serving runtime (the paper, productionised).

Converts the offline measurement pipeline (Poisson replay → Tier-1 stacking
→ Tier-2 dispatch) into a server with live ingress:

* :mod:`server`    — ``CryptoServer`` event loop: submit → handle, explicit-
  clock flush policy, graceful drain;
* :mod:`admission` — queue-bound / per-tenant token-bucket / SLO gates with
  backpressure signalling;
* :mod:`batcher`   — continuous rectangular batcher (close on N_c-full, age
  timeout, or occupancy threshold);
* :mod:`telemetry` — K/M occupancy, queue depth, p50/p95/p99 latency,
  eager-vs-deferred reduction-stall counters, JSON export for ``BENCH_*``
  tracking;
* :mod:`client`    — synthetic load generator (virtual or real-time pacing);
* :mod:`controller` — adaptive occupancy controller: EWMA feedback over the
  dispatch telemetry drives the per-class close policy (target ladder rung,
  max_age, occupancy threshold) and prices the λ-controlled merge holdback
  against the SLO gate.

``ServeConfig.reduction_by_workload`` selects the fold discipline per
workload class (paper §7.2.1): lazy (κ-amortised deferred Montgomery
reduction) classes batch and dispatch next to strictly-eager classes, each
with its own compiled programs and HLO validation mode (eager V1–V5; lazy
adds the one-fold-per-window checks V6/V7).
"""
from repro.serve.admission import (AdmissionController, AdmissionDecision,
                                   BatchDecisions, TenantInterner,
                                   TokenBucket)
from repro.serve.batcher import ContinuousBatcher, ClosedBatch
from repro.serve.client import LoadGenerator, LoadResult, attach_payloads
from repro.serve.controller import AdaptiveController
from repro.serve.server import (CryptoServer, RejectedError, ResponseHandle,
                                ServeConfig, compilation_cache_dir,
                                enable_compilation_cache)
from repro.serve.telemetry import BatchRecord, LatencyHistogram, Telemetry
