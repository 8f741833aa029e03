"""repro.obs — the zero-sync telemetry plane.

Bohm's design keeps reads bookkeeping-free and writers off contended
shared state; instrumentation must honor the same contract or it
perturbs exactly what it measures. The layers:

``registry``   ``MetricsRegistry``: typed counters / gauges with
               device-side array accumulation on the hot path (lazy adds
               folded onto the jitted phases' metric outputs — no host
               sync, no per-batch Python arithmetic on device values) and
               ONE host transfer at ``snapshot()``. The engine's and
               schedulers' legacy stats surfaces are views onto it.
``trace``      ``PhaseTracer``: host spans around the engine's phase
               dispatches (``engine/*``) and the scheduler's admission,
               epoch formation, dispatches and joins (``service/*``).
               Spans are unfenced host intervals — tracing never waits
               on the device, on or off (tested). With ``annotate`` they
               are ``jax.profiler.TraceAnnotation``s, on the profiler's
               clock beside the device's programs, which are named after
               their functions (``jit_commit_phase``) and carry
               ``jax.named_scope`` stages (``commit/spill``) in their
               ops' metadata: device time is read there. ``enabled``
               adds a bounded ring exported as Chrome ``trace_event``
               JSON (Perfetto-loadable).
``flight``     ``FlightRecorder``: per-ticket lifecycle records through
               the out-of-order scheduler (submit → dispatch → exec →
               commit → visible), telescoping latency breakdowns,
               conflict attribution with footprint witnesses, per-class
               quantile digests, Chrome async-lane export stitched into
               the tracer's (OFF = one attribute test per hook).
``quantiles``  ``LogHistogram``: fixed-bucket log histogram — streaming
               p50/p99 with bounded relative error, no sample retention.
``health``     derived MVCC gauges computed from store state on demand:
               watermark lag, pin ages, ring/slab/spill saturation,
               pressure percentiles, flight SLO quantiles —
               ``BohmEngine.health()`` / ``TxnService.health()`` /
               ``BohmScheduler.health()``.
``lifecycle``  ``LifecycleAuditor``: every version transition (committed,
               overwritten, spilled, page-dropped, gc-reclaimed) into
               per-state device counters + a bounded host audit ring,
               harvested only at sweep/snapshot boundaries (zero fences
               on or off); the ``inspect_record`` time-travel inspector
               and the GC delay/pin-certification audit.
``monitor``    ``HealthMonitor``: fixed-cadence ``health()`` sampling
               into bounded ring-buffer series, EWMA anomaly alerts
               (warn/crit JSONL event log), Chrome counter-track export
               stitched into the phase/flight trace.

``ewma`` (shared anomaly baselines) and ``meta`` (``run_metadata()``
provenance stamping for benchmark artifacts) ride along.
"""
from repro.obs.ewma import Ewma, EwmaAnomaly
from repro.obs.flight import (NULL_FLIGHT, FlightRecorder, TicketFlight,
                              stitch_chrome_trace)
from repro.obs.health import (engine_health, scheduler_health,
                              service_health)
from repro.obs.lifecycle import (NULL_AUDIT, AuditEvent, LifecycleAuditor,
                                 RecordTimeline)
from repro.obs.meta import git_sha, run_metadata
from repro.obs.monitor import NULL_MONITOR, HealthMonitor
from repro.obs.quantiles import LogHistogram
from repro.obs.registry import MetricsRegistry, MetricsView
from repro.obs.trace import (NULL_SPAN, PhaseTracer, validate_chrome_trace)

__all__ = [
    "AuditEvent", "Ewma", "EwmaAnomaly", "FlightRecorder",
    "HealthMonitor", "LifecycleAuditor", "LogHistogram",
    "MetricsRegistry", "MetricsView", "NULL_AUDIT", "NULL_FLIGHT",
    "NULL_MONITOR", "NULL_SPAN", "PhaseTracer", "RecordTimeline",
    "TicketFlight", "engine_health", "git_sha", "run_metadata",
    "scheduler_health", "service_health", "stitch_chrome_trace",
    "validate_chrome_trace",
]
