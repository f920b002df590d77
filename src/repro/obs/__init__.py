"""repro.obs — unified observability: metrics, tracing, traffic (DESIGN.md §14).

Three host-side subsystems, off by default:

  * :mod:`repro.obs.metrics` — process-global counter/gauge/histogram
    registry (JSON snapshot + Prometheus text export);
  * :mod:`repro.obs.trace`   — structured span tracer: every span is a
    ``jax.profiler.TraceAnnotation`` (kept only while a profiler session
    runs), and enabled it also buffers Chrome-trace/Perfetto JSON;
  * :mod:`repro.obs.traffic` — measured memory-traffic accounting
    (compiler bytes-accessed vs the analytic plane-traffic model).

``enable_all()`` / ``disable_all()`` flip metrics and tracing together
(what ``launch/serve.py --metrics-out/--trace-out`` uses).  Instrumentation
never touches jax values — enabling it cannot move a bit of any computed
output.
"""
from repro.obs import metrics, trace, traffic

__all__ = ["metrics", "trace", "traffic", "enable_all", "disable_all"]


def enable_all() -> None:
    metrics.enable()
    trace.enable()


def disable_all() -> None:
    metrics.disable()
    trace.disable()
