"""repro.obs — determinism-safe tracing, metrics, and trace export.

The observability layer for the whole repo: span tracing with a
preallocated ring buffer (:mod:`~repro.obs.trace`), a counters/gauges/
histograms metrics registry (:mod:`~repro.obs.metrics`), a JAX-profiler
adapter (:mod:`~repro.obs.jaxprof`), and a CLI
(``python -m repro.obs report|export|tail``).

Everything is **off by default** and strictly observational: enabling
tracing changes no stored sweep byte and no ``TickReport`` field (tested
— see ``tests/test_obs.py``). Opt in with::

    from repro import obs
    obs.enable()                      # or REPRO_OBS=1 in the environment
    with obs.span("tick.place"):
        ...
    obs.save("trace.json")            # raw artifact; export via the CLI

Instrumented hot paths: :mod:`repro.serving.horizon` (per-tick
materialize/place/route/execute spans, queue-depth + realized-QoS
gauges, per-request latency histograms), :mod:`repro.sweeps`
(per-chunk spans with their pad/dispatch/wait phases, items/s, store I/O
timing), the sparse placement tick :func:`repro.workloads.evaluate_sparse`
(``placement.*`` spans and the greedy's iteration and re-score counters),
and :mod:`repro.fleet` (worker telemetry files behind ``fleet status``
rate/ETA). Device time is attributed by ``jax.named_scope`` tags in the
programs themselves (the kernels', the sparse greedy's ``greedy.*``
phases, ``sigma``), which the JAX profiler's trace carries.
"""
from .metrics import (METRICS_SCHEMA_VERSION, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .trace import (DEFAULT_CAPACITY, OBS_SCHEMA_VERSION,
                    READABLE_OBS_SCHEMAS, Tracer, count, disable, enable,
                    enable_from_env, enabled, get_tracer, last_tracer,
                    load_artifact, sample, save, span, to_chrome_trace,
                    validate_chrome_trace)
from .jaxprof import (have_jax_profiler, kernel_span, named_scope,
                      profile_trace)
from .stream import (STREAM_SCHEMA_VERSION, StreamPublisher, disable_stream,
                     enable_stream, enable_stream_from_env, get_publisher,
                     publish, read_stream, stream_active)
from .aggregate import (rollup_counters, rollup_metrics, stitch_fleet,
                        stitch_traces)
from .slo import (DEFAULT_SLOS, SLO, SLO_SCHEMA_VERSION, SLOReport,
                  compare_bench, evaluate_slos, load_slos)
from .reqtrace import (REQTRACE_SCHEMA_VERSION, RequestTracer,
                       disable_request_tracing, enable_request_tracing,
                       enable_reqtrace_from_env, explain_uid,
                       get_request_tracer, load_reqtrace)
from .ledger import (LEDGER_SCHEMA_VERSION, DecisionLedger, disable_ledger,
                     enable_ledger, enable_ledger_from_env, get_ledger,
                     ingest_sparse_trace, load_ledger, why_text)

__all__ = [
    "OBS_SCHEMA_VERSION", "METRICS_SCHEMA_VERSION", "DEFAULT_CAPACITY",
    "READABLE_OBS_SCHEMAS", "STREAM_SCHEMA_VERSION", "SLO_SCHEMA_VERSION",
    "Tracer", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "enable", "disable", "enabled", "get_tracer", "last_tracer",
    "enable_from_env",
    "span", "count", "sample", "save",
    "load_artifact", "to_chrome_trace", "validate_chrome_trace",
    "kernel_span", "named_scope", "profile_trace", "have_jax_profiler",
    "StreamPublisher", "enable_stream", "disable_stream", "stream_active",
    "get_publisher", "publish", "read_stream", "enable_stream_from_env",
    "stitch_traces", "stitch_fleet", "rollup_metrics", "rollup_counters",
    "SLO", "SLOReport", "DEFAULT_SLOS", "load_slos", "evaluate_slos",
    "compare_bench",
    "REQTRACE_SCHEMA_VERSION", "RequestTracer", "enable_request_tracing",
    "disable_request_tracing", "get_request_tracer",
    "enable_reqtrace_from_env", "load_reqtrace", "explain_uid",
    "LEDGER_SCHEMA_VERSION", "DecisionLedger", "enable_ledger",
    "disable_ledger", "get_ledger", "enable_ledger_from_env",
    "ingest_sparse_trace", "load_ledger", "why_text",
]
