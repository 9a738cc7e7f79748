"""Unified observability: span tracing, metrics, pump watchdog.

Three pieces (stdlib-only — importable from the client-side tools and the
dependency-light serving client path without pulling in jax):

  * `obs.trace` — a bounded-ring span tracer (request lifecycle on the
    serving pump, per-dispatch phases on the trainer), exportable as
    structured JSONL and Chrome `trace_event` JSON (Perfetto-loadable;
    `tools/trace_dump.py`).  `get_tracer()` is the process-global
    instance, disabled by default.  Its `span()` also enters a
    `jax.profiler.TraceAnnotation` (looked up only once the process has
    imported JAX), so the `pt.` phase spans land on a profiler trace's
    host plane beside the device's ops.
  * `obs.metrics` — a registry of counters/gauges/histograms with labels
    that unifies StatSet, BarrierTimer, and the serving engine's counters
    behind one Prometheus-style `render()` (the server's `metrics` frame)
    and a flat `snapshot()` (the trainer's `metrics.jsonl` sink).
    `CATALOG` pins every metric name; `tools/check_metrics_names.py`
    keeps it in lockstep with `docs/observability.md`.
  * the pump heartbeat watchdog lives with its thread in
    `serving/server.py` and exports through this registry
    (`pump_last_step_age_s`, `pump_alive`).
  * `obs.compile_watch` — per-signature jit compile events on a `compile`
    tracer lane with a recompile-storm detector (`get_compile_watch()`,
    always on — compiles are rare); every other backend compile is
    counted under the `pt.` span it happened in.
  * `obs.hbm` — device-memory accounting (KV pool / param / live-array
    bytes plus the backend's own stats, CPU-safe).
  * `obs.flight` — the flight recorder: a bounded structured-event ring
    that dumps atomic postmortem bundles on pump death / watchdog wedge /
    an operator `dump` RPC (`get_flight_recorder()`;
    `tools/postmortem.py` pretty-prints a bundle).
  * `obs.timeseries` — the health plane's storage: a bounded in-memory
    ring of downsampled samples per catalogued metric (counters as
    deltas, gauges as last-value), fed by a background `HistorySampler`
    and served over the `history` RPC (`tools/obs_top.py` renders it
    live).
  * `obs.slo` — declarative SLO specs + multi-window burn-rate alerting
    over the time-series; firing transitions emit `slo_fire`/`slo_clear`
    flight events, flip `obs_slo_firing`, and freeze one proactive
    postmortem bundle per episode.

See docs/observability.md for the span model, metric reference, the
trace_dump workflow, and the postmortem-bundle format.
"""

from paddle_tpu.obs.compile_watch import (CompileWatch,  # noqa: F401
                                          compile_collector,
                                          get_compile_watch)
from paddle_tpu.obs.flight import (FlightRecorder,  # noqa: F401
                                   flight_collector, get_flight_recorder,
                                   load_bundle)
from paddle_tpu.obs.hbm import hbm_collector, hbm_snapshot  # noqa: F401
from paddle_tpu.obs.metrics import (CATALOG, Counter,  # noqa: F401
                                    Gauge, Histogram, MetricsRegistry,
                                    barrier_collector, statset_collector,
                                    tracer_collector)
from paddle_tpu.obs.slo import (SloEvaluator, SloSpec,  # noqa: F401
                                default_pserver_slos, default_router_slos,
                                default_serving_slos)
from paddle_tpu.obs.timeseries import (HistorySampler,  # noqa: F401
                                       MetricHistory, history_collector,
                                       history_reply, merge_history,
                                       relabel_series_key)
from paddle_tpu.obs.trace import (Tracer, flush_trace_file,  # noqa: F401
                                  get_tracer, merge_chrome, new_span_id,
                                  new_trace_id, process_info,
                                  spans_to_chrome)

__all__ = ["Tracer", "get_tracer", "spans_to_chrome", "merge_chrome",
           "flush_trace_file",
           "new_trace_id", "new_span_id", "process_info", "MetricsRegistry",
           "Counter", "Gauge", "Histogram", "CATALOG", "statset_collector",
           "barrier_collector", "tracer_collector", "CompileWatch",
           "get_compile_watch", "compile_collector", "FlightRecorder",
           "get_flight_recorder", "flight_collector", "load_bundle",
           "hbm_collector", "hbm_snapshot", "MetricHistory",
           "HistorySampler", "history_collector", "history_reply",
           "merge_history", "relabel_series_key", "SloSpec",
           "SloEvaluator", "default_serving_slos", "default_router_slos",
           "default_pserver_slos"]
