"""jax.profiler capture.

The program's spans reach a capture through the recording ``Tracer``
(obs/trace.py), which enters a ``TraceAnnotation`` for each span on the
engine, train and host tracks.

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
