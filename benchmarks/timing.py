"""Shared wall-clock measurement discipline for the CPU benchmarks.

One helper, one protocol: *interleaved min-of-rounds*. All candidate cells
are warmed (compiled) first, then timed round-robin — each round times every
cell once — and each cell keeps its best round. Interleaving means a
machine-load drift hits every cell in the same round instead of biasing
whichever cell happened to own a contiguous timing block; min-of-rounds
discards the drifty rounds entirely. This is the protocol
``benchmarks/run.py`` fig2/serve always used.

``clock`` and ``sync`` are injectable so tests can drive the loop with a
fake clock and no real device work.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Sequence, Tuple


def _default_sync(x):
    import jax
    return jax.block_until_ready(x)


def interleaved_min_of_rounds(
        cells: Sequence[Tuple[str, Callable[[], object]]],
        rounds: int = 7, warmup: int = 1,
        clock: Callable[[], float] = time.perf_counter,
        sync: Callable[[object], object] = _default_sync,
) -> Tuple[Dict[str, float], Dict[str, object]]:
    """Time ``cells`` — (name, thunk) pairs — under the shared protocol.

    Each thunk runs one full measurement unit (e.g. one jitted call, one
    serve wave); ``sync`` blocks on its result before the clock stops.
    Returns (best_us, last_result): per-cell best round in microseconds and
    the last synced thunk result (benchmarks that need a derived quantity,
    e.g. generated-token counts, read it from there).
    """
    best: Dict[str, float] = {}
    last: Dict[str, object] = {}
    for name, thunk in cells:               # compile / cache warm-up
        for _ in range(warmup):
            last[name] = sync(thunk())
        best[name] = float("inf")
    for _ in range(rounds):
        for name, thunk in cells:
            t0 = clock()
            r = thunk()
            sync(r)
            best[name] = min(best[name], (clock() - t0) * 1e6)
            last[name] = r
    return best, last
