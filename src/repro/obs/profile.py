"""jax.profiler integration.

``profile_region(obs, name)`` annotates a region so it shows up in an XLA
profile (``jax.profiler.TraceAnnotation``) AND as a host span in the obs
tracer. ``step_region`` is the per-train-step variant
(``StepTraceAnnotation`` carries ``step_num`` into the profile's step
view). With tracing off the host half is a NullTracer no-op and the
annotation costs nothing outside a capture — callers never branch.

``profiler_session(dir)`` wraps ``jax.profiler.start_trace/stop_trace``
for the ``--profile-dir`` flags on launch/serve.py and launch/train.py:
the captured TensorBoard-format profile lands under ``dir``. A capture that
cannot start raises; it never degrades to a run without a profile.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax.profiler as prof


@contextlib.contextmanager
def profile_region(obs, name: str, track: Optional[str] = None,
                   **attrs) -> Iterator[None]:
    """Host span (via ``obs.tracer``) + XLA TraceAnnotation."""
    with prof.TraceAnnotation(name), \
            obs.tracer.span(name, track=track, **attrs):
        yield


@contextlib.contextmanager
def step_region(obs, name: str, step: int,
                track: Optional[str] = None, **attrs) -> Iterator[None]:
    """Per-step profile_region: StepTraceAnnotation groups device ops under
    a step number in TensorBoard's profile step view."""
    with prof.StepTraceAnnotation(name, step_num=step), \
            obs.tracer.span(name, track=track, step=step, **attrs):
        yield


@contextlib.contextmanager
def profiler_session(profile_dir: Optional[str]) -> Iterator[bool]:
    """Capture an XLA profile into ``profile_dir`` for the duration of the
    block (the --profile-dir flag). Yields whether a capture is running:
    False only when no directory was asked for."""
    if not profile_dir:
        yield False
        return
    prof.start_trace(profile_dir)
    try:
        yield True
    finally:
        prof.stop_trace()
