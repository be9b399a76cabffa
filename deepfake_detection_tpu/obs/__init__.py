"""Training observability subsystem.

The cross-cutting layer every scaling PR reports through: a per-step
time-breakdown tracker riding the trainer's drain cadence with zero extra
device syncs (telemetry.py), a schema-versioned JSONL event/metrics log in
the run dir (events.py), an optional stdlib ``--metrics-port`` Prometheus
endpoint sharing the serving renderer (server.py + utils/prometheus.py),
and on-demand bounded profiler capture on a live job via SIGUSR2 or a
``PROFILE`` trigger file (profiler.py).

The jax-touching modules (telemetry pulls utils.metrics → jnp; profiler
traces) are imported LAZILY (PEP 562, the data/ package idiom):
tools/obs_report.py reads telemetry logs through ``events`` without
dragging jax into a reporting subprocess.
"""

from .events import SCHEMA_VERSION, EventLog, iter_records, read_records

# lazily-resolved (jax-importing) attributes: name -> submodule
_LAZY = {
    "TrainTelemetry": "telemetry", "forward_flops_per_sample": "telemetry",
    "loader_collector": "telemetry", "native_warp_collector": "telemetry",
    "peak_flops": "telemetry", "resilience_collector": "telemetry",
    "MetricsServer": "server", "start_metrics_server": "server",
    "ProfilerCapture": "profiler", "TRIGGER_FILENAME": "profiler",
    "start_trace": "profiler",
}

__all__ = ["SCHEMA_VERSION", "EventLog", "iter_records", "read_records",
           *sorted(_LAZY)]


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        value = getattr(mod, name)
        globals()[name] = value        # cache: __getattr__ runs once per name
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
