"""``repro.obs`` — dependency-free observability for the deployment stack.

* :class:`Recorder` / :func:`maybe_span` — structured tracing (spans,
  events, counters, gauges, histograms) with JSONL and Chrome-trace export;
  threaded through ``deploy_model(recorder=)`` and
  ``optimize_placement(recorder=)`` (nothing stored when detached). Every
  span, stored or not, is also a ``repro:<name>`` profiler annotation.
* :func:`flow_report` — per-link NoC load matrix of a placement with hotspot
  top-k, Gini/CoV imbalance indices, per-chip and inter-chip byte breakdowns,
  and an ASCII heatmap (``repro-deploy report``).
* :func:`bench_time` / :func:`bench_percentiles` / :func:`percentiles` —
  the shared timing primitives the benchmark suites build on.
"""
from .recorder import (NULL_RECORDER, TRACE_PREFIX, Recorder,  # noqa: F401
                       Span, bench_percentiles, bench_time, maybe_span,
                       percentiles, read_jsonl, timed)
from .flow import FlowReport, ascii_heatmap, cov, flow_report, gini  # noqa: F401
