"""Streaming subsystem: online subspace tracking + dynamic-batching serving.

Turns the one-shot DeEPCA solver into a continuously-serving system, built
entirely on the PR-3 step/driver seam (no new iteration loops):

* :mod:`repro.streaming.stream` — deterministic drifting-problem
  generators (slow subspace rotation, abrupt eigengap shifts, per-agent
  sample-arrival covariance updates);
* :mod:`repro.streaming.tracker` — :class:`StreamingDeEPCA`, warm-start
  online tracking over a stream via the resumable ``(S, W, G_prev,
  offset)`` state contract, with drift monitoring, adaptive iteration
  escalation, and tracker restarts through the fault-tolerance path;
* :mod:`repro.streaming.service` — :class:`PCAService`, a request-queue
  front-end with shape bucketing + dynamic batching so ragged one-shot
  PCA requests ride :meth:`~repro.core.driver.IterationDriver.run_batch`'s
  compiled-program cache;
* :mod:`repro.streaming.fleet` — :class:`TrackerFleet`, the multi-tenant
  version of the tracker: N drifting streams vmapped through one compiled
  window program per padded-shape bucket, with per-tenant drift policy as
  masked in-batch selects and join/leave as slot scatters (zero
  steady-state retraces, pinned by the ``fleet-warm`` contract).

Entry points: ``python -m repro.launch.serve --workload pca-stream`` /
``--workload pca-fleet`` and ``benchmarks/bench_streaming.py``.
"""
from .stream import (DriftingStream, EigengapShiftStream, HostTick,
                     SampleArrivalStream, SlowRotationStream, StreamTick,
                     ragged_requests)
from .tracker import (DriftPolicy, StreamingDeEPCA, TickReport,
                      concat_traces)
from .service import AdmissionPolicy, PCAResponse, PCAService
from .fleet import (FleetTickReport, TenantReport, TrackerFleet,
                    scatter_carry, select_carry)

__all__ = [
    "DriftingStream", "SlowRotationStream", "EigengapShiftStream",
    "SampleArrivalStream", "StreamTick", "HostTick", "ragged_requests",
    "StreamingDeEPCA", "DriftPolicy", "TickReport", "concat_traces",
    "PCAService", "AdmissionPolicy", "PCAResponse",
    "TrackerFleet", "FleetTickReport", "TenantReport",
    "select_carry", "scatter_carry",
]
