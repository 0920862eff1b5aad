"""repro.obs — observability for the serving stack (DESIGN.md §11, §13).

Core primitives, all stdlib + thread-safe, shared by `repro.serving`,
`repro.transport`, and `repro.online`:

  * :class:`LatencyHistogram` — fixed log-spaced buckets, constant
    memory, exact counts, mergeable across instances by bucket-wise
    addition (the property the old bounded-deque reservoir lacked:
    percentiles of a merged histogram equal percentiles of the merged
    observation stream, so per-model, per-replica, and cross-process
    metrics combine honestly).  ``state()``/``from_state()`` round-trip
    the exact buckets through JSON — the fleet-aggregation scrape form.
  * :class:`TraceBuffer` / :class:`RequestTrace` — per-request spans
    (queue → batch assembly → device step → response write) plus
    structured lifecycle events (watcher promotions, learner
    publishes) in one bounded in-process ring, exposed over
    ``GET /v1/traces`` and exportable as JSONL for offline analysis.
    :func:`adopt_request_id` sanitizes a client-minted
    ``x-hdc-request-id`` so one id names a request across hops.
  * :class:`MetricsWindow` / :class:`WindowSnapshot` — bounded window
    of timestamped cumulative snapshots deriving exact time series
    (request/shed rates, queue-depth trajectory + slope, SLO burn)
    from first-to-last deltas, never averaged rates.
  * :func:`render_prometheus` — Prometheus text exposition
    (``uhd_*`` counters/gauges/histograms) for ``GET /metrics`` with
    ``Accept: text/plain``; :func:`parse_exposition` is its strict
    inverse (duplicate HELP/TYPE and escaping are machine-checked).

Plus the profiling hooks, which `repro.core` uses too: :class:`span`
(a named stretch of host work on the ``jax.profiler`` clock that also
keeps its wall time for the stage histograms; names take the form ``hdc.<layer>.<what>``, e.g.
``hdc.engine.step``), :func:`install_gc_span` / :func:`remove_gc_span`
(``python.gc`` spans around garbage collections) and
:func:`profile_capture` (an opt-in ``jax.profiler`` trace window behind
``POST /v1/debug/profile``).  JAX is imported only when a span opens.

The fleet aggregation plane (`FleetAggregator`, `AggregatorServer`,
scrape targets) lives in ``repro.obs.aggregator`` and is **not**
imported here: it sits above `repro.transport` (which itself imports
these primitives), so an eager import would create a cycle.  Import
``repro.obs.aggregator`` explicitly.
"""

from repro.obs.histogram import LatencyHistogram  # noqa: F401
from repro.obs.profiler import (  # noqa: F401
    install_gc_span,
    profile_capture,
    remove_gc_span,
    span,
)
from repro.obs.prometheus import (  # noqa: F401
    parse_exposition,
    render_prometheus,
)
from repro.obs.trace import (  # noqa: F401
    OWNER_BATCHER,
    OWNER_TRANSPORT,
    RequestTrace,
    TraceBuffer,
    adopt_request_id,
    new_request_id,
)
from repro.obs.window import MetricsWindow, WindowSnapshot  # noqa: F401
