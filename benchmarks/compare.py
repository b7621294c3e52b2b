"""Diff benchmark JSON files (BENCH_scan.json / BENCH_serve.json) and report
every regression in one run.

    PYTHONPATH=src python benchmarks/compare.py OLD.json NEW.json [--pct 10]
    PYTHONPATH=src python benchmarks/compare.py \
        --pair BENCH_scan.json BENCH_scan.new.json \
        --optional-pair BENCH_serve.json BENCH_serve.new.json

Rows are joined on (op, shape, schedule) and printed as an aligned delta
table — every pair, every row, never stopping at the first offender — then
a summary block lists ALL rows slower by more than ``--pct`` percent across
all pairs. The exit code is nonzero iff that list is non-empty (so
`make bench-compare` gates a PR on the scan-schedule AND serve-throughput
trajectories while still showing a multi-row regression in full).

Rows present in only one file are listed as added/removed, never flagged —
new schedules (e.g. the dual rows) must be able to land, and retired
ones to leave.
``--pair`` files are required (missing → nonzero exit: the primary gate
cannot pass vacuously); ``--optional-pair`` skips a pair whose files are
absent, so one gate can also cover benchmark files a given run didn't
regenerate.

``--accept`` promotes each pair's candidate over its baseline (copy NEW →
OLD, delete the staging file) after printing the delta table — the
human-in-the-loop step that keeps ``*.new.json`` staging files out of the
repo (`make bench-accept`). Accepting never fails on regressions: the
table shows them, the operator is choosing to take them.

``--schema FILE...`` is a structural check used by the CI bench smoke:
each file must be a JSON list of records with string op/shape/schedule,
positive numeric us_per_call/tok_per_s, numeric ttft_* fields when
present, and no duplicate (op, shape, schedule) keys. Timings are NOT
judged — CI machines are too noisy to gate on; the schema check catches a
benchmark that silently stopped emitting rows.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys


def _key(rec):
    return (rec["op"], rec["shape"], rec["schedule"])


def load(path):
    with open(path) as f:
        recs = json.load(f)
    return {_key(r): r for r in recs}


def compare(old_path: str, new_path: str, pct: float = 10.0):
    """One pair → (table lines, [(row, old_us, new_us, delta_pct), ...])."""
    old, new = load(old_path), load(new_path)
    keys = sorted(old.keys() | new.keys())
    width = max([len("/".join(k)) for k in keys] + [4])
    lines = [f"  {'status':<10} {'row':<{width}} {'old_us':>10} "
             f"{'new_us':>10} {'delta':>8}"]
    offenders = []
    for k in keys:
        name = "/".join(k)
        if k not in new:
            lines.append(f"  {'removed':<10} {name:<{width}}")
            continue
        n = new[k]["us_per_call"]
        if k not in old:
            lines.append(f"  {'added':<10} {name:<{width}} {'—':>10} "
                         f"{n:>10.1f}")
            continue
        o = old[k]["us_per_call"]
        delta = (n - o) / o * 100 if o else 0.0
        tag = "ok"
        if delta > pct:
            tag = "REGRESSION"
            offenders.append((name, o, n, delta))
        elif delta < -pct:
            tag = "improved"
        lines.append(f"  {tag:<10} {name:<{width}} {o:>10.1f} {n:>10.1f} "
                     f"{delta:>+7.1f}%")
    return lines, offenders


REQUIRED_STR = ("op", "shape", "schedule")
REQUIRED_NUM = ("us_per_call", "tok_per_s")
# scheduler-v2 serve rows carry arrival-process parameters (arrival_*),
# queue pressure (queue_*), and the engine-phase wall-time split
# (prefill_/chunk_/decode_/host_ms) next to the ttft percentiles; train
# rows split tok/s into real_/buffer_tok_per_s and carry the padding_rate
# connecting them (dtype lives in the schedule string, e.g. "pack_bf16") —
# all non-negative numbers when present
OPTIONAL_NUM_PREFIXES = ("ttft_", "arrival_", "queue_", "prefill_",
                         "chunk_", "decode_", "host_", "real_", "buffer_",
                         "padding_",
                         # serve_cached rows: StateCache hit ratio and
                         # insert/evict pressure (hit_/cache_), speculative
                         # decode accept rate and round counts (spec_)
                         "hit_", "cache_", "spec_")
# observability-cost fields (obs_overhead_pct on the serve packed_obs row)
# are deltas vs a baseline mode — legitimately negative under CPU timing
# noise, so they only need to be numeric
OPTIONAL_SIGNED_PREFIXES = ("obs_",)


def schema_errors(path):
    """Structural violations in one benchmark JSON file (see module doc)."""
    errs = []
    try:
        with open(path) as f:
            recs = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    if not isinstance(recs, list) or not recs:
        return [f"{path}: expected a non-empty JSON list of records"]
    seen = set()
    for i, r in enumerate(recs):
        if not isinstance(r, dict):
            errs.append(f"{path}[{i}]: not an object")
            continue
        for k in REQUIRED_STR:
            if not isinstance(r.get(k), str) or not r.get(k):
                errs.append(f"{path}[{i}]: missing/empty string field {k!r}")
        for k in REQUIRED_NUM:
            v = r.get(k)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v <= 0:
                errs.append(f"{path}[{i}]: field {k!r} must be a positive "
                            f"number, got {v!r}")
        for k, v in r.items():
            if any(k.startswith(p) for p in OPTIONAL_NUM_PREFIXES) and (
                    not isinstance(v, (int, float)) or isinstance(v, bool)
                    or v < 0):
                errs.append(f"{path}[{i}]: field {k!r} must be a "
                            f"non-negative number, got {v!r}")
            if any(k.startswith(p) for p in OPTIONAL_SIGNED_PREFIXES) and (
                    not isinstance(v, (int, float))
                    or isinstance(v, bool)):
                errs.append(f"{path}[{i}]: field {k!r} must be a number, "
                            f"got {v!r}")
        if all(isinstance(r.get(k), str) for k in REQUIRED_STR):
            key = _key(r)
            if key in seen:
                errs.append(f"{path}[{i}]: duplicate row {'/'.join(key)}")
            seen.add(key)
    return errs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("old", nargs="?")
    ap.add_argument("new", nargs="?")
    ap.add_argument("--pair", nargs=2, action="append", default=[],
                    metavar=("OLD", "NEW"),
                    help="a required baseline/candidate file pair "
                         "(repeatable; missing files fail the gate)")
    ap.add_argument("--optional-pair", nargs=2, action="append", default=[],
                    metavar=("OLD", "NEW"),
                    help="like --pair but skipped when a file is absent "
                         "(for benchmark files a run didn't regenerate)")
    ap.add_argument("--pct", type=float, default=10.0,
                    help="regression threshold in percent (default 10)")
    ap.add_argument("--allow-missing", action="store_true",
                    help="treat EVERY pair as optional")
    ap.add_argument("--accept", action="store_true",
                    help="promote each pair's NEW file over its baseline "
                         "(copy NEW -> OLD, delete the staging file) after "
                         "showing the delta table; never fails on "
                         "regressions")
    ap.add_argument("--schema", nargs="+", metavar="FILE", default=None,
                    help="structural check of benchmark JSON files "
                         "(required fields/types, no duplicate rows); "
                         "timings are not judged")
    args = ap.parse_args()

    if args.schema is not None:
        errs = []
        for path in args.schema:
            e = schema_errors(path)
            errs += e
            if e:
                print(f"# schema {path}: {len(e)} error(s)")
            else:
                with open(path) as f:
                    print(f"# schema {path}: OK, {len(json.load(f))} "
                          f"records")
        for msg in errs:
            print(f"#   {msg}")
        sys.exit(1 if errs else 0)

    pairs = [(o, n, False) for o, n in args.pair] + \
            [(o, n, True) for o, n in args.optional_pair]
    if args.old or args.new:
        if not (args.old and args.new):
            ap.error("positional usage needs both OLD and NEW")
        pairs.insert(0, (args.old, args.new, False))
    if not pairs:
        ap.error("nothing to compare: pass OLD NEW or --pair")

    all_offenders = []
    missing_required = []
    promoted = []
    for old, new, optional in pairs:
        if args.accept:
            # accepting only needs the candidate; a first-ever baseline is
            # a plain promotion (nothing to diff against)
            if not os.path.exists(new):
                if optional or args.allow_missing:
                    print(f"# skipping accept: no staging file {new}")
                else:
                    print(f"# MISSING staging file {new}")
                    missing_required.append(new)
                continue
            if os.path.exists(old):
                lines, _ = compare(old, new, args.pct)
                print(f"# {old} -> {new} (threshold {args.pct:.0f}%)")
                for ln in lines:
                    print(ln)
            shutil.copyfile(new, old)
            os.remove(new)
            promoted.append((new, old))
            continue
        missing = [p for p in (old, new) if not os.path.exists(p)]
        if missing:
            if optional or args.allow_missing:
                print(f"# skipping compare: missing {', '.join(missing)}")
            else:
                # fail the gate, but keep comparing the remaining pairs so
                # ONE run still surfaces every offender
                print(f"# MISSING required {', '.join(missing)}")
                missing_required += missing
            continue
        lines, offenders = compare(old, new, args.pct)
        print(f"# {old} -> {new} (threshold {args.pct:.0f}%)")
        for ln in lines:
            print(ln)
        all_offenders += [(f"{old}->{new}",) + o for o in offenders]
    for new, old in promoted:
        print(f"# accepted: {new} promoted to {old} (staging file removed)")
    if all_offenders:
        print(f"# {len(all_offenders)} regression(s) > {args.pct:.0f}%:")
        for pair, name, o, n, delta in all_offenders:
            print(f"#   {name}  {o:.1f} -> {n:.1f}us ({delta:+.1f}%)  "
                  f"[{pair}]")
    if missing_required:
        print(f"# missing required file(s): {', '.join(missing_required)}")
    if all_offenders or missing_required:
        sys.exit(1)
    if not promoted:
        print("# no regressions")


if __name__ == "__main__":
    main()
