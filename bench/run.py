"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by its name in BENCHMARK.json; its configuration,
traffic mix and per-layer metrics are files under bench/ named in it
(bench/lib/harness.py). The run loads, warms up, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, ``compiles_in_window`` (programs built inside the measured
window, which warm-up should leave at 0), and last ``checks``: each
number compared with its limit.
Set-up pieces and other readings go to standard error before it; the
checks are the last lines there. Without the chips the cell asks for it
exits nonzero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _env():
    """Caches and logs inside the checkout, at fixed paths."""
    from bench.lib.harness import COMPILE_CACHE, OUT
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    (OUT / "tpu_logs").mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))


def per_layer_metrics(cell, res, trace_summary) -> dict:
    from bench.lib import harness as H
    data = dict(res.layer_data, trace=trace_summary)
    out = {}
    for m in cell.per_layer:
        reader = H.load_module(H.BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(data)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None, require_chip: bool = True, fault=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _env()
    from bench.lib import harness as H
    cell = H.find_cell(args.workload)
    clock = H.SetupClock(T0)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_chip:
        try:
            devs = H.require_devices(cell.chips)
        except H.NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 3
    else:
        devs = jax.devices()[:cell.chips]
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    clock.mark("import")
    H.note(f"cell {cell.name} seed {args.seed} on {devs[0].device_kind} "
           f"x{len(devs)}")

    driver = H.load_module(H.BENCH / "drivers" / f"{cell.kind}.py")
    res = driver.run(cell, args.seed, args.seconds, bool(args.trace), clock,
                     devs, fault=fault)
    H.note("setup pieces (s): " + " ".join(
        f"{k}={v:.3f}" for k, v in clock.pieces.items()))

    out = {"correct": all(c.ok for c in res.checks),
           "attempted": res.attempted, "failed": res.failed}
    if args.trace:
        from bench.lib import xtrace
        summary = xtrace.summarize(res.trace_dir)
        H.note("device modules: " + "; ".join(
            f"{n} x{len(d)} {1e3 * sum(d) / len(d):.3f} ms" for n, d in
            sorted(summary.modules.items(), key=lambda kv: -sum(kv[1]))[:8]))
        H.note("idle by host span (s): " + " ".join(
            f"{n}={t:.4f}" for n, t in sorted(summary.idle_by_host.items())))
        out["metrics"] = per_layer_metrics(cell, res, summary)
        out["device"] = dict(res.device, busy_s=summary.busy_s,
                             window_s=summary.window_s)
        out["breakdown"] = summary.breakdown()
        with open(res.trace_dir / "breakdown.json", "w") as f:
            json.dump({"metrics": out["metrics"], "device": out["device"],
                       "breakdown": out["breakdown"]}, f, indent=1)
        shutil.rmtree(res.trace_dir / "plugins")   # the raw trace, ~100 MB
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in res.end_to_end.items()}
        out["device"] = res.device
    out["compiles_in_window"] = res.compiles_in_window
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in res.checks}
    for c in res.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
