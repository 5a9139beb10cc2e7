"""paddle_tpu.observability — unified tracing, metrics, export
(DESIGN-OBSERVABILITY.md).

One subsystem answers "where did this step/request spend its time" on
a live system:

- :mod:`.trace`   — low-overhead span recorder (monotonic-clock ring
  buffer, thread-aware, ~zero cost when disabled; arm with
  ``PADDLE_TPU_TRACE=1`` or ``trace.enable()``); exports
  Chrome/Perfetto ``trace_event`` JSON and a compact summary.
- :mod:`.metrics` — process-wide registry of counters/gauges/
  histograms whose hot-path instruments accept lazy device scalars
  and defer the device→host sync to scrape time.
- :mod:`.export`  — JSON snapshot + Prometheus text dump.
- :mod:`.host_events` — what jax traces, lowers, compiles and reads
  from its cache, by function, and the collector's pauses: always-on
  counters, and spans beside the program's own.

Quickstart::

    import paddle_tpu as paddle
    paddle.observability.trace.enable()       # or PADDLE_TPU_TRACE=1
    model.fit(...)                            # spans record as it runs
    paddle.observability.trace.dump_chrome_trace("fit_trace.json")
    print(paddle.observability.scrape())      # all metrics, one dict

The training/serving hot loops are instrumented unconditionally —
dispatch spans, auto-K gauges, request lifecycle spans, checkpoint IO
— but record nothing until armed; step/dispatch wall-time histograms
and counters are ALWAYS on (host floats, no device syncs).
"""

from __future__ import annotations

from ..framework import env_knobs as _env_knobs
from . import trace  # noqa: F401
from . import metrics  # noqa: F401
from . import export  # noqa: F401
from . import events  # noqa: F401
from . import aggregate  # noqa: F401
from . import http  # noqa: F401
from . import host_events  # noqa: F401
from .metrics import registry  # noqa: F401

__all__ = ["trace", "metrics", "export", "events", "aggregate",
           "http", "host_events", "registry", "scrape",
           "scrape_prometheus"]


def scrape(materialize: bool = True):
    """ONE dict over every metric in the process-wide registry —
    dispatch, fit, mesh, serving, checkpoint.  ``materialize=True``
    pays the deferred device→host syncs of lazy-valued metrics here
    (the sanctioned sync point); the instrumented loops never sync."""
    return export.snapshot(materialize=materialize)


def scrape_prometheus() -> str:
    """The registry in Prometheus text exposition format."""
    return export.to_prometheus_text()


# PADDLE_TPU_TRACE=1 arms the span recorder at import — i.e. before
# any instrumented module dispatches — so "trace this run" is an env
# var, not a code change.  Capacity knob: PADDLE_TPU_TRACE_CAPACITY.
if _env_knobs.get_bool("PADDLE_TPU_TRACE"):
    # malformed capacity must not kill the import (get_int -> default)
    _cap = _env_knobs.get_int("PADDLE_TPU_TRACE_CAPACITY", 0)
    # nonpositive values (unset, 0, or e.g. -1) keep the default ring
    trace.enable(capacity=_cap if _cap > 0 else None)
    del _cap

# what jax builds and the collector's pauses: counted from here on,
# always (host_events.py: nothing on a steady step)
host_events.install()

# PADDLE_TPU_METRICS_PORT=<base> arms the per-process HTTP scrape
# endpoint the same way (DESIGN-OBSERVABILITY.md §Distributed plane):
# rank r serves base+1+r, a rank-less process serves base.  Unset/0
# creates NOTHING — no socket, no thread (zero-overhead contract,
# pinned in tests).  Parked spares arm at promotion instead
# (http.serve_for_rank).
http.maybe_serve_from_env()
