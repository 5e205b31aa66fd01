"""Observability: metrics registry + flight recorder + distributed
tracing (host-side only).

`obs.metrics` — thread-safe counters/gauges/histograms with Prometheus
v0.0.4 text exposition, a JSON snapshot, span-derived exemplars, and a
label-cardinality bound, served by the relay at GET /metrics and
GET /stats (server/relay.py).

`obs.flight` — bounded structured-event ring whose dump is attached to
exceptions crossing the worker/relay boundary.

`obs.ledger` — the conservation-ledger accounting plane (ISSUE 15):
typed flow stations with registered conservation equations, an
`audit()` that returns violated equations with per-station deltas
(empty == conserved), owner-scoped sub-ledgers behind the cardinality
cap, served by the relay at GET /ledger and asserted at the end of
every model-check episode.

`obs.trace` — W3C-traceparent-style distributed tracing: a bounded
per-process span ring, deterministic hash-based sampling, fan-in span
links, `GET /trace/<id>` span trees, and a Chrome-trace export
(ISSUE 10 — one mutation followed client → relay → batch → engine →
replica).

`obs.anatomy` — the stage primitive: `stage` times an interval on the
thread that does the work and posts the `evolu_stage_*` histograms and
totals in one registry acquisition (plus a profiler annotation and a
distributed-trace span); GET /stats reads its `stages` section from
the `evolu_stage_ms` histogram.

This package MUST NOT import jax (directly or transitively): metrics
and spans record host-side Python values the hot paths already hold —
never an extra device pull, never an op inside the fused jit pipeline.
The constraint is load-bearing (instrumentation overhead budget is
<=1% — metrics measured at 0.0015%, tracing gated by
benchmarks/trace_overhead.py) and mechanically enforced by
tests/test_import_hygiene.py and tests/test_bench_liveness.py.
"""

from evolu_tpu.obs import flight, ledger, metrics, trace
from evolu_tpu.obs import anatomy
from evolu_tpu.obs.flight import recorder
from evolu_tpu.obs.metrics import registry, set_enabled

__all__ = ["anatomy", "flight", "ledger", "metrics", "trace", "recorder",
           "registry", "set_enabled"]
