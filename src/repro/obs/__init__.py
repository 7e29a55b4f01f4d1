"""repro.obs — lightweight observability for the MCA pipeline.

Four pieces, importable as ``from repro import obs``:

- metrics: ``obs.get_registry()`` returns the active :class:`Registry`
  (counters / gauges / histograms / timers); ``obs.scoped()`` isolates
  collection for a test or a benchmark run; ``obs.snapshot()`` snapshots
  the active registry and with ``aggregate="psum"`` sums additive leaves
  across SPMD processes.
- spans: ``obs.span(name, cat=..., track=..., hist=..., **args)`` is the
  one span API: it always writes a ``jax.profiler`` annotation (on the
  device trace's clock, args as stats), observes the body's seconds into
  histogram ``hist`` when given, and records a registry span while
  ``obs.enable_tracing()`` / ``obs.tracing()`` is on;
  ``obs.record_span`` draws spans after the fact on a request's track;
  ``obs.export_chrome_trace(path)`` writes Perfetto-loadable JSON.
- device telemetry: ``obs.devtel`` accumulates per-execution kernel
  launch / sampled-block counts delivered from the device
  (``kernels.<op>.device_launches`` — vs the dispatch-time
  ``kernel_calls`` which count traced call sites).
- sink: ``obs.JsonlSink(path)`` appends structured JSON-lines records
  (flushed per write; fsync on close).

Metric naming convention: dotted ``<area>.<metric>`` —
``kernels.flash_attention.kernel_calls``, ``train.flops_reduction``,
``serve.wave_seconds``.  See ROADMAP.md § Observability for the full list.
"""
from . import devtel
from .aggregate import snapshot
from .registry import (Counter, Gauge, Histogram, Registry, get_registry,
                       scoped)
from .sink import JsonlSink, read_jsonl
from .tracing import (enable_tracing, export_chrome_trace, mark, record_span,
                      span, tracing, tracing_enabled)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry", "scoped",
    "snapshot", "JsonlSink", "read_jsonl", "devtel",
    "enable_tracing", "tracing", "tracing_enabled", "span", "record_span",
    "mark", "export_chrome_trace",
]
