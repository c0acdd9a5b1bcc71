"""Telemetry subsystem: metrics registry + /metrics + JSONL snapshots.

Answers "what is this fleet doing right now" without grepping stdout
(HashKitty's central-monitoring lesson, PAPERS.md): coordinator,
dispatcher, worker, RPC, and bench all publish into a process-wide
registry; the coordinator serves it as a Prometheus ``/metrics``
endpoint on the RPC port and journals periodic JSONL snapshots next to
the session file.

Metric names (all prefixed ``dprf_``; see README "Observability"):

  dprf_candidates_hashed_total{engine,device}   keyspace swept
  dprf_units_leased_total / _completed_total / _reissued_total{reason}
  dprf_hits_total / dprf_hits_rejected_total    oracle-verified cracks
  dprf_unit_seconds                             unit latency histogram
  dprf_compile_seconds{engine,cache}            step warmup compiles
                                                (cache: hit|miss|off)
  dprf_compile_cache_hits_total{engine}         persistent-compile-
  dprf_compile_cache_misses_total{engine}         cache behavior
  dprf_keyspace_total / dprf_keyspace_covered   sweep progress gauges
  dprf_targets_total / dprf_targets_found
  dprf_workers_quarantined / dprf_worker_last_seen_timestamp{worker}
  dprf_bench_rate_hs{engine,impl,device,mode}   bench results
  dprf_tuned_batch{engine,device,attack}        tuning-subsystem batch
  dprf_unit_target_seconds / dprf_unit_size     adaptive unit sizing
  dprf_units_poisoned_total                     retry-cap parking events
  dprf_units_parked                             currently-parked gauge
                                                (0 after retry-parked)
  dprf_trace_spans_total                        flight-recorder spans
                                                (telemetry/trace.py)
  dprf_worker_pipeline_depth                    remote worker submit-
                                                ahead depth (1=serial)
  dprf_worker_idle_seconds                      seconds a worker held
                                                no submitted unit
                                                (device idle)
  dprf_phase_seconds{phase,engine,job}          every hit batch's
                                                host verify (perf.py)
  dprf_device_busy_fraction{worker}             live sliding-window
                                                sweep coverage
  dprf_roofline_frac{engine}                    EWMA throughput / the
                                                int32 roofline ceiling
  dprf_per_chip_rate_hs / dprf_scaling_efficiency{engine}
                                                multichip scaling bench
  dprf_jobs_gc_total                            age-based job reaps
  dprf_worker_health_state{worker}              health state machine
                                                (telemetry/health.py)
  dprf_worker_straggler / dprf_worker_rate_hs{worker}
                                                straggler detection
  dprf_job_eta_seconds / dprf_job_ttfh_seconds / dprf_job_stalled{job}
                                                per-job SLOs
  dprf_job_lease_wait_seconds{job}              fair-share latency
  dprf_alerts_firing{rule} / dprf_alerts_fired_total{rule}
                                                alert engine
                                                (telemetry/alerts.py)
  dprf_trace_spans_dropped_total                dropped/lost spans
  dprf_hbm_bytes_in_use/_limit/_peak{device}    device allocator
                                                memory (devstats.py)
  dprf_program_peak_bytes{engine,attack}        analyzed per-dispatch
                                                footprint (programs.py)
  dprf_roofline_model_divergence{engine}        analyzed-vs-hand op
                                                model cross-check

Alongside metrics, telemetry/trace.py records per-unit lifecycle SPANS
(the flight recorder): trace ids assigned at split time, context
propagated over the RPC messages, ``dprf top`` live view, and ``dprf
trace export`` to Perfetto -- see its module docstring.
"""

from __future__ import annotations

import socket
from typing import Optional

from dprf_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                         MetricsRegistry)
from dprf_tpu.telemetry.snapshot import (TelemetrySnapshotter,
                                         load_snapshots,
                                         snapshot_interval,
                                         telemetry_path)

# NOTE: dprf_tpu.telemetry.trace is imported lazily by its users (it
# imports get_registry from this package at recorder construction);
# `from dprf_tpu.telemetry.trace import get_tracer` is the entrypoint.

#: process-wide registry: library code with no registry threaded
#: through publishes here (the utils/logging.DEFAULT pattern); the
#: coordinator serves THIS registry unless handed another.
DEFAULT = MetricsRegistry()


def get_registry(registry: Optional[MetricsRegistry] = None
                 ) -> MetricsRegistry:
    return registry if registry is not None else DEFAULT


def declare_job_metrics(m: MetricsRegistry) -> dict:
    """The job-progress metric surface shared by the local Coordinator
    and the distributed CoordinatorState -- ONE declaration site, so
    the two runtimes' names/labels/help can never drift."""
    return {
        "hits": m.counter("dprf_hits_total", "oracle-accepted cracks"),
        "rejects": m.counter(
            "dprf_hits_rejected_total",
            "device hits the CPU oracle refused to verify"),
        "cands": m.counter(
            "dprf_candidates_hashed_total", "keyspace indices swept",
            labelnames=("engine", "device")),
        "targets": m.gauge("dprf_targets_total", "targets in the job"),
        "found": m.gauge("dprf_targets_found",
                         "targets cracked so far"),
        "unit_seconds": m.histogram(
            "dprf_unit_seconds",
            "per-unit wall cost: submit-to-resolve, or the "
            "inter-completion interval once a worker pipeline is "
            "primed (queue wait behind the stream excluded)"),
    }


def scrape_metrics(host: str, port: int, timeout: float = 10.0,
                   path: str = "/metrics") -> str:
    """Plain-socket HTTP GET of a coordinator's metrics endpoint (the
    ``dprf metrics`` subcommand; no HTTP client dependency).  Returns
    the response body; raises OSError/ValueError on failure."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(f"GET {path} HTTP/1.0\r\n"
                  f"Host: {host}\r\n\r\n".encode())
        chunks = []
        while True:
            b = s.recv(65536)
            if not b:
                break
            chunks.append(b)
    raw = b"".join(chunks)
    head, _, body = raw.partition(b"\r\n\r\n")
    status = head.split(b"\r\n", 1)[0].split()
    if len(status) < 2 or status[1] != b"200":
        raise ValueError(
            f"metrics endpoint answered {head.splitlines()[0]!r}")
    return body.decode("utf-8", "replace")


__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "TelemetrySnapshotter", "DEFAULT", "declare_job_metrics",
           "get_registry", "load_snapshots", "scrape_metrics",
           "snapshot_interval", "telemetry_path"]
