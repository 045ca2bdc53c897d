"""graftscope: end-to-end request tracing, flight recorder, SLO metrics.

The observability layer for the serving core (ISSUE 14). Public
surface:

- :func:`span` / :func:`request_context` / :func:`bind` /
  :func:`current_context` — the tracer (:mod:`.trace`): request-scoped
  span trees in bounded per-thread rings, no-op without a recorder.
- :func:`maybe_install` / :func:`install` / :func:`get_recorder` —
  lifecycle; the server installs the process recorder at boot
  (``BUCKETEER_TRACE`` gates it, default on).
- ``get_recorder().flight`` — the always-on flight recorder
  (:mod:`.flight`): ``GET /debug/flight``, auto-dumped on 5xx and SLO
  breach.
- :func:`chrome_trace` — per-request Chrome-trace/Perfetto export
  (:mod:`.export`): ``GET /debug/trace/{request_id}``.
- :class:`SloWatchdog` (:mod:`.slo`) — per-endpoint latency budgets
  feeding breach counters and flight dumps.
- :mod:`.logctx` — every log record gains ``request_id``.
- :func:`stage` — a span that also feeds its ``/metrics`` stage as it
  closes: the one seam for a timed layer boundary; :func:`waited` the
  same for a wait that began on another thread.

docs/observability.md is the operator-facing walkthrough.
"""
from __future__ import annotations

from . import export, flight, logctx, slo  # noqa: F401
from .slo import SloWatchdog  # noqa: F401
from .trace import (LAYER_SPANS, Recorder, bind,  # noqa: F401
                    current_context, current_request_id, get_recorder,
                    install, installed, maybe_install, request_context,
                    span, stage, use_context, waited)


def chrome_trace(request_id):
    """Chrome-trace document for one request from the installed
    recorder; None when tracing is disabled."""
    rec = get_recorder()
    if rec is None:
        return None
    return export.chrome_trace(rec, request_id)
