"""Device-resident serving telemetry (see DESIGN.md §13).

The hot-path half lives INSIDE the scheduler's compiled while-loop
carry: fixed-size event rings, per-iteration sample rings and counter
arrays written with masked scatter updates, so the loop still syncs the
host exactly once per workload.  The host half turns the harvested
rings into typed spans / histograms (`rings.harvest_obs`), maintains a
pull-style metrics registry with Prometheus-text and JSON exporters
(`metrics`), emits structured JSON-lines span traces for the serving
drivers (`trace`), and counts the process's XLA backend compiles
(`compiles`).

Telemetry is a STATIC flag: a scheduler built with ``obs=None``
compiles to an executable byte-identical to the pre-telemetry one
(gated by the HLO fingerprint check in benchmarks/serve_bench.py), and
a metrics-on scheduler emits bit-identical tokens -- rings only ever
read values the loop already computes.
"""
from .compiles import backend_compiles
from .fingerprint import hlo_fingerprint, scheduler_fingerprint
from .hostinfo import BENCH_SCHEMA_VERSION, host_fingerprint, host_matches
from .metrics import REGISTRY, MetricsRegistry
from .rings import (EV_ADMIT, EV_FINISH, EV_FIRST, ObsConfig, ObsSnapshot,
                    harvest_obs, init_obs_state)
from .trace import SpanTracer, get_tracer, set_trace_path, span

__all__ = [
    "ObsConfig", "ObsSnapshot", "init_obs_state", "harvest_obs",
    "EV_ADMIT", "EV_FIRST", "EV_FINISH",
    "MetricsRegistry", "REGISTRY",
    "SpanTracer", "get_tracer", "set_trace_path", "span",
    "backend_compiles",
    "host_fingerprint", "host_matches", "BENCH_SCHEMA_VERSION",
    "hlo_fingerprint", "scheduler_fingerprint",
]
