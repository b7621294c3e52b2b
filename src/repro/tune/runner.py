"""Measurement sweeps: time every candidate in an operator's tunable space
at one shape key, cache the winner.

The timing discipline is the benchmark harness's own (benchmarks/timing.py:
interleaved min-of-rounds) so tuner numbers and fig2 numbers are directly
comparable. What a sweep measures is the key's ``objective``: "fwd" times
the forward operator (the serving regime); "fwdbwd" times forward + full
VJP of a scalar loss (the training regime — the backward recomputes the
chunk bodies, so its cost structure, and therefore the winning schedule,
can differ from the forward's). Winners are cached under objective-tagged
keys and never served across objectives.

Pallas candidates are included only where their timings mean something:
on a TPU, where the kernels are compiled — elsewhere they run under the
Pallas interpreter (kernels/__init__.py), whose wall clock would "tune"
the emulator.

CLI — the bounded default sweep behind ``make bench-tune``:

    PYTHONPATH=src python -m repro.tune.runner --out TUNE_CACHE.json \
        [--rounds 3] [--grid small|fig2] [--force]

The ``fig2`` grid covers the benchmark matrix's shapes (both scan ops at
L ∈ {256…4096}, plus the wide-head dh ≫ T cell where the dual form wins);
``small`` is a seconds-scale smoke grid.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.tune.space import (ShapeKey, shape_key, space_for, candidate_name)
from repro.tune.cache import TuneCache, get_cache


def _timing():
    """Import the shared benchmark timing helper (repo-root package)."""
    try:
        from benchmarks.timing import interleaved_min_of_rounds
    except ImportError:    # src-only sys.path (e.g. installed layout)
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks.timing import interleaved_min_of_rounds
    return interleaved_min_of_rounds


# ---------------------------------------------------------------------------
# synthetic operands per shape key
# ---------------------------------------------------------------------------

def synth_positions(rng, B: int, L: int, resets: str):
    """Packed position ids matching a reset-density band (space.RESET_BANDS):
    segment length ≈ 1/density, boundaries straddling power-of-two chunks."""
    import jax.numpy as jnp
    if resets == "none":
        return jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    seg = {"sparse": 400, "mid": 100, "dense": 12}.get(resets, 100)
    seg = min(seg, L)
    lens = [seg] * (L // seg) + ([L % seg] if L % seg else [])
    row = np.concatenate([np.arange(n) for n in lens])
    return jnp.asarray(np.broadcast_to(row, (B, L)).copy(), jnp.int32)


def synth_args(key: ShapeKey, seed: int = 0) -> Tuple:
    """Operator inputs for one shape key (at the bucketed L)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    B, L, N = key.B, key.Lb, key.N
    dt_ = jnp.dtype(key.dtype)
    pos = synth_positions(rng, B, L, key.resets)
    Bm = jnp.asarray(rng.normal(size=(B, L, N)), dt_)
    Cm = jnp.asarray(rng.normal(size=(B, L, N)), dt_)
    if key.op == "selective_scan_heads":
        H, P = key.H, key.dh
        u = jnp.asarray(rng.normal(size=(B, L, H, P)), dt_)
        delta = jnp.asarray(rng.uniform(0.1, 0.5, (B, L, H)), dt_)
        A = -jnp.exp(jnp.asarray(rng.normal(size=(H,)), jnp.float32))
        Dk = jnp.ones((H,), jnp.float32)
    else:
        D = key.D
        u = jnp.asarray(rng.normal(size=(B, L, D)), dt_)
        delta = jnp.asarray(rng.uniform(0.1, 0.5, (B, L, D)), dt_)
        A = -jnp.exp(jnp.asarray(rng.normal(size=(D, key.N)), jnp.float32))
        Dk = jnp.ones((D,), jnp.float32)
    return u, delta, A, Bm, Cm, Dk, pos


def make_thunk(key: ShapeKey, knobs: Dict, args: Tuple):
    """A zero-arg jitted callable evaluating one candidate at this shape.

    ``key.objective == "fwdbwd"`` wraps the candidate in a value_and_grad
    of a scalar loss over every differentiable operand, so the sweep times
    the full training-step cost of the schedule (forward + VJP recompute),
    not just the forward."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    u, delta, A, Bm, Cm, Dk, pos = args
    heads = key.op == "selective_scan_heads"
    if knobs.get("backend") == "pallas":
        kw = dict(backend="pallas", chunk=knobs["pchunk"],
                  sub_t=knobs.get("sub_t"))
        if heads:
            kw["schedule"] = knobs.get("schedule", "blocked_heads")
            raw = lambda u, d, Bm, Cm, p: kops.selective_scan_heads(
                u, d, A, Bm, Cm, Dk, p, **kw)
        else:
            kw["schedule"] = knobs.get("schedule", "blocked")
            raw = lambda u, d, Bm, Cm, p: kops.selective_scan(
                u, d, A, Bm, Cm, Dk, p, **kw)
    else:
        from repro.core import ssm as core_ssm
        kw = dict(method=knobs.get("method", "blocked"))
        if "chunk" in knobs:
            kw["chunk"] = knobs["chunk"]
        if "intra" in knobs:
            kw["intra"] = knobs["intra"]
        f = core_ssm.selective_scan_heads if heads else core_ssm.selective_scan
        raw = lambda u, d, Bm, Cm, p, f=f: f(u, d, A, Bm, Cm, Dk, p, **kw)
    if key.objective == "fwdbwd":
        def scalar_loss(u, d, Bm, Cm, p):
            y = raw(u, d, Bm, Cm, p)
            return (y.astype(jnp.float32) ** 2).mean()
        fn = jax.jit(jax.value_and_grad(scalar_loss, argnums=(0, 1, 2, 3)))
    else:
        fn = jax.jit(raw)
    return lambda: fn(u, delta, Bm, Cm, pos)


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def tune_key(key: ShapeKey, cache: Optional[TuneCache] = None,
             rounds: int = 3, include_pallas: Optional[bool] = None,
             verbose: bool = False, obs=None) -> Dict:
    """Measure the candidate space at ``key``, cache and return the winner.

    Candidates that fail to build/compile are dropped (a knob combination
    can be invalid for a shape), each with a printed line and a
    ``tune.dropped`` count, so a kernel that fails to compile is never
    dropped unseen; at least the default-equivalent candidates always
    survive. ``obs`` (repro.obs.Obs) records one ``tune.sweep`` span per key
    with nested ``tune.candidate`` compile probes, plus ``tune.sweeps`` /
    ``tune.candidates`` / ``tune.dropped`` counters."""
    if obs is None:
        from repro.obs import Obs
        obs = Obs.off()
    tr = obs.tracer
    if include_pallas is None:
        import jax
        include_pallas = jax.default_backend() == "tpu"
    cands = space_for(key, include_pallas=include_pallas)
    args = synth_args(key)
    cells: List[Tuple[str, object]] = []
    by_name: Dict[str, Dict] = {}
    ssid = tr.start("tune.sweep", track="tune", key=key.encode(),
                    candidates=len(cands))
    for c in cands:
        name = candidate_name(c)
        try:
            with tr.span("tune.candidate", track="tune", cand=name):
                thunk = make_thunk(key, c, args)
                thunk()       # build + compile probe outside the timed loop
        except Exception as e:
            obs.metrics.counter("tune.dropped").inc()
            print(f"#   tune drop {key.encode()} {name}: "
                  f"{type(e).__name__}: {e}")
            continue
        cells.append((name, thunk))
        by_name[name] = c
    if not cells:
        tr.finish(ssid, viable=0)
        raise RuntimeError(f"no viable candidates for {key.encode()}")
    best_us, _ = _timing()(cells, rounds=rounds, warmup=1)
    win = min(best_us, key=best_us.get)
    obs.metrics.counter("tune.sweeps").inc()
    obs.metrics.counter("tune.candidates").inc(len(cells))
    tr.finish(ssid, viable=len(cells), winner=win,
              winner_us=best_us[win])
    if verbose:
        ranked = sorted(best_us.items(), key=lambda kv: kv[1])
        print(f"# tune {key.encode()}: " +
              "  ".join(f"{n}={us:.0f}us" for n, us in ranked[:4]) +
              (f"  (+{len(ranked) - 4} more)" if len(ranked) > 4 else ""))
    knobs = by_name[win]
    if cache is not None:
        cache.put(key, knobs, best_us[win], candidates=len(cells))
    return knobs


def ensure(op: str, *, B: int, L: int, D: int = 0, N: int = 0, H: int = 0,
           dh: int = 0, dtype="float32", reset_density=None,
           objective: str = "fwd", cache: Optional[TuneCache] = None,
           rounds: int = 3, include_pallas: Optional[bool] = None,
           force: bool = False, verbose: bool = False, obs=None) -> bool:
    """Tune ``op`` at this shape unless its exact bucketed key is already
    cached. Returns True iff a new measurement was taken."""
    c = cache if cache is not None else get_cache()
    key = shape_key(op, dtype=dtype, B=B, L=L, D=D, N=N, H=H, dh=dh,
                    reset_density=reset_density, objective=objective)
    if not force and c.get(key) is not None:
        return False
    tune_key(key, cache=c, rounds=rounds, include_pallas=include_pallas,
             verbose=verbose, obs=obs)
    return True


# ---------------------------------------------------------------------------
# bounded default sweeps (make bench-tune)
# ---------------------------------------------------------------------------

def sweep_grid(grid: str) -> List[ShapeKey]:
    """The named bounded sweeps. ``fig2`` mirrors the benchmark matrix —
    including the wide-head (dh ≫ T) cell that gives the dual-form
    evaluator a real shot at winning."""
    keys = []
    if grid == "small":
        keys.append(shape_key("selective_scan", B=1, L=128, D=64, N=8))
        keys.append(shape_key("selective_scan_heads", B=1, L=128, H=4,
                              dh=16, N=8))
        return keys
    if grid != "fig2":
        raise ValueError(f"unknown grid {grid!r}")
    for L in (256, 512, 1024, 2048, 4096):
        keys.append(shape_key("selective_scan", B=1, L=L, D=256, N=16))
        keys.append(shape_key("selective_scan_heads", B=1, L=L, H=4,
                              dh=64, N=16))
        # wide heads at matched channels: dh ≫ the small blocked chunks —
        # the shape family where the C·Bᵀ dual form beats the quad form
        keys.append(shape_key("selective_scan_heads", B=1, L=L, H=2,
                              dh=128, N=16))
    return keys


def main(argv=None):
    ap = argparse.ArgumentParser(description="scan-schedule autotune sweep")
    ap.add_argument("--out", default=None,
                    help="cache path (default: $REPRO_TUNE_CACHE or "
                         "TUNE_CACHE.json)")
    ap.add_argument("--grid", default="fig2", choices=["small", "fig2"])
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--force", action="store_true",
                    help="re-measure keys already in the cache")
    ap.add_argument("--include-pallas", action="store_true",
                    help="force pallas candidates into the space (default: "
                         "only on real TPU)")
    ap.add_argument("--objective", default="fwd",
                    choices=["fwd", "fwdbwd", "both"],
                    help="time forward only (serving), forward+backward "
                         "(training), or sweep both")
    args = ap.parse_args(argv)
    cache = get_cache(args.out)
    objectives = ("fwd", "fwdbwd") if args.objective == "both" \
        else (args.objective,)
    n_new = 0
    for base in sweep_grid(args.grid):
        for obj in objectives:
            key = dataclasses.replace(base, objective=obj)
            if not args.force and cache.get(key) is not None:
                continue
            tune_key(key, cache=cache, rounds=args.rounds,
                     include_pallas=True if args.include_pallas else None,
                     verbose=True)
            n_new += 1
    path = cache.save(args.out)
    print(f"# tuned {n_new} new key(s); {len(cache.entries)} total -> {path}")


if __name__ == "__main__":
    main()
