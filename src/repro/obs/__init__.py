"""Fleet telemetry: typed metrics, logical-clock trace spans, and the
health/MTTR reporter.

``obs.metrics``  process-local Counter/Gauge/Histogram registry
                 (JSONL snapshots + Prometheus text format).
``obs.trace``    events keyed by the ``(step, origin, seq)`` logical
                 clock, whose merges are byte-identical under any arrival
                 interleaving (the FleetEvent-log contract); and timed
                 host spans kept in a bounded ring and marked in the
                 profiler's trace.
``obs.logging``  the one structured logger every layer logs through.
``obs.report``   renders a metrics+trace snapshot into the fleet-health
                 / capacity-vs-DegradationModel comparison the benches
                 consume.
"""
from repro.obs import logging, metrics, report, trace  # noqa: F401

__all__ = ["logging", "metrics", "report", "trace"]
