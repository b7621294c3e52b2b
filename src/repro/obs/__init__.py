"""repro.obs — unified telemetry: metrics registry + span tracing.

The ``Obs`` bundle is what instrumented code receives: a (always-on,
cheap) ``MetricsRegistry`` plus a ``Tracer`` that is either recording or
the no-op ``NULL_TRACER``. Construct with:

    obs = Obs.off()                  # default: metrics only, no tracing
    obs = Obs.on()                   # record spans too
    obs = Obs.on(clock=fake_clock)   # deterministic tests

and at the end of a traced run:

    obs.export("trace.json")         # Chrome trace + metric snapshot
    print(obs.tracer.timeline())     # plain-text per-track view
    print(obs.metrics.prometheus_text())
    obs.close()                      # stops recording GC passes

A recording ``Obs`` puts the engine's and the Trainer's phases, and each
GC pass (``host.gc``), into a jax.profiler capture as annotations of the
same names (obs/trace.py); names on those tracks begin with one of
``SPAN_PREFIXES``.

Metric names are dotted (``serve.prefills``, ``train.real_tokens``,
``data.prefetch_wait_ms``) — the catalogue lives in obs/README.md.
See obs/metrics.py and obs/trace.py for the pieces; obs/profile.py for
the ``--profile-dir`` capture; obs/check.py for the trace validator
that ``make obs-smoke`` runs.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Optional, Union

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      percentiles)
from .trace import (NULL_TRACER, PROFILED_TRACKS, SPAN_PREFIXES,
                    NullTracer, Tracer)
from .profile import profiler_session

__all__ = [
    "Obs", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "percentiles", "Tracer", "NullTracer", "NULL_TRACER",
    "PROFILED_TRACKS", "SPAN_PREFIXES", "profiler_session",
]


@dataclasses.dataclass
class Obs:
    """Telemetry bundle handed to ServeEngine / Trainer / loaders."""

    metrics: MetricsRegistry
    tracer: Union[Tracer, NullTracer]

    @classmethod
    def off(cls, clock: Callable[[], float] = time.time) -> "Obs":
        """Metrics only (tracing disabled — the default everywhere)."""
        return cls(metrics=MetricsRegistry(clock=clock), tracer=NULL_TRACER)

    @classmethod
    def on(cls, clock: Optional[Callable[[], float]] = None,
           span_clock: Optional[Callable[[], float]] = None,
           max_events: int = 1_000_000) -> "Obs":
        """Metrics + recording tracer. ``clock`` overrides both the
        registry stamp clock and the span clock (scripted-clock tests);
        ``span_clock`` overrides just the tracer's."""
        reg = MetricsRegistry(clock=clock or time.time)
        tr = Tracer(clock=span_clock or clock or time.perf_counter,
                    max_events=max_events)
        gc.callbacks.append(tr.gc_callback)
        return cls(metrics=reg, tracer=tr)

    def close(self) -> None:
        """Stop recording GC passes (the hook ``on()`` installed)."""
        if self.tracer.gc_callback in gc.callbacks:
            gc.callbacks.remove(self.tracer.gc_callback)

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled

    def export(self, path: str) -> str:
        """Dump the Chrome trace (with the metric snapshot embedded)."""
        return self.tracer.export(path, metrics=self.metrics.to_dict())
