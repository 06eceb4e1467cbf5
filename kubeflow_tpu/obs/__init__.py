"""Observability plane: metrics, heartbeats, profiling, structured logs.

SURVEY.md §5.1/§5.5 equivalents, TPU-first: Prometheus-style exposition on
every process, XLA profiler capture endpoints, worker heartbeat liveness
feeding the elastic supervisor (§5.3).
"""

from kubeflow_tpu.obs.heartbeat import (
    Heartbeat,
    HeartbeatWriter,
    heartbeat_path,
    heartbeat_path_from_env,
    is_stale,
    read_heartbeat,
)
from kubeflow_tpu.obs.jsonlog import JsonFormatter, configure_json_logging
from kubeflow_tpu.obs.profiler import ObsServer, capture_trace
from kubeflow_tpu.obs.prom import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
)

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Heartbeat",
    "HeartbeatWriter",
    "Histogram",
    "JsonFormatter",
    "ObsServer",
    "Registry",
    "capture_trace",
    "configure_json_logging",
    "heartbeat_path",
    "heartbeat_path_from_env",
    "is_stale",
    "read_heartbeat",
]
