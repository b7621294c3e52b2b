"""Performance hillclimbing driver (§Perf): re-lower + re-analyze chosen
cells under named optimization variants; print before/after per roofline
term.

Usage:
  PYTHONPATH=src python -m repro.launch.perf --cell recurrentgemma-2b:train_4k \
      --variants baseline act_sp
  PYTHONPATH=src python -m repro.launch.perf --all-hillclimb
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import json

from repro.launch.dryrun import run_cell

# named optimization variants → ArchConfig overrides
# (__accum__ is a builder knob: gradient-accumulation microbatches)
VARIANTS = {
    "baseline": {},
    # It-1: Megatron-SP-style sequence sharding of the residual carried (and
    # saved-for-backward) between layer units
    "act_sp": {"act_pspec": ("auto",)},
    # It-1b: channel (d_model) sharding of the carry — for recurrent stacks
    # whose scans are channel-parallel but sequential in L
    "act_dp": {"act_pspec": ("auto_d",)},
    # It-2: fold y=C·h into a single sequential scan — never materialize the
    # (B,L,D,N) trajectories (pure-XLA analogue of the Pallas kernel).
    # REFUTED: scan autodiff stores per-step residuals (see EXPERIMENTS.md)
    "fused_scan": {"scan_impl": "fused_seq"},
    "act_sp+fused_scan": {"act_pspec": ("auto",), "scan_impl": "fused_seq"},
    # It-8: scan schedules. The SSD-style block-parallel schedule
    # (scan_impl="blocked", see core/scan.py) is now the BASELINE hot path:
    # no (B,L,D,N) materialization, y=C·h fused per chunk, checkpointed
    # chunk bodies. "scan_chunked" re-lowers the pre-It-8 default for
    # before/after regression tracking.
    "scan_chunked": {"scan_impl": "chunked"},
    "act_dp+scan_chunked": {"act_pspec": ("auto_d",), "scan_impl": "chunked"},
    "scan_blocked+bf16": {"scan_impl": "blocked", "scan_dtype": "bfloat16"},
    # It-3: bf16 recurrence compute — halves the scan's HBM traffic
    "scan_bf16": {"scan_dtype": "bfloat16"},
    "act_dp+scan_bf16": {"act_pspec": ("auto_d",),
                         "scan_dtype": "bfloat16"},
    # It-4: smaller scan chunks — fewer associative-scan levels in flight
    "chunk128": {"scan_chunk": 128},
    "act_sp+chunk128": {"act_pspec": ("auto",), "scan_chunk": 128},
    # It-5: gradient-accumulation microbatching — divides live activations
    "act_sp+accum4": {"act_pspec": ("auto",), "__accum__": 4},
    "act_sp+accum8": {"act_pspec": ("auto",), "__accum__": 8},
    # It-7: save dot outputs in remat — spend reclaimed HBM on less
    # recompute traffic
    "act_sp+accum4+remat_dots": {"act_pspec": ("auto",), "__accum__": 4,
                                 "remat": "dots"},
    "act_dp+accum2+remat_dots": {"act_pspec": ("auto_d",), "__accum__": 2,
                                 "remat": "dots"},
    # It-6: token-chunked MoE dispatch — bounds (E, C, d) buffer memory
    "act_sp+accum4+moe8k": {"act_pspec": ("auto",), "__accum__": 4,
                            "moe_token_chunk": 8192},
    "act_sp+accum2+moe8k": {"act_pspec": ("auto",), "__accum__": 2,
                            "moe_token_chunk": 8192},
    "act_sp+moe8k": {"act_pspec": ("auto",), "moe_token_chunk": 8192},
}


# the three hillclimbed cells (DESIGN.md §Perf) + the paper-faithful extra
HILLCLIMB = [
    ("recurrentgemma-2b", "train_4k",
     ["act_dp", "act_dp+scan_bf16"]),
    ("deepseek-67b", "train_4k", ["act_sp+accum4", "act_sp+accum8"]),
    ("gemma-7b", "prefill_32k", ["act_sp"]),
    ("mamba-2.8b", "train_4k",
     ["act_dp", "scan_bf16", "act_dp+scan_bf16", "scan_chunked",
      "scan_blocked+bf16"]),
    # It-9: head-structured (Mamba-2/SSD) variant at matched packed shapes —
    # tracks the per-head vs per-channel schedule gap across PRs
    ("mamba2-370m", "train_4k",
     ["baseline", "act_dp", "scan_bf16", "act_dp+scan_bf16"]),
]


def run_variant(arch, shape, variant, out="experiments/perf",
                multi_pod=False):
    overrides = VARIANTS[variant]
    rec = run_cell(arch, shape, multi_pod, out_dir=None, overrides=overrides)
    rec["variant"] = variant
    rec["overrides"] = {k: v for k, v in overrides.items()
                        if k != "__accum__"}      # audit what was applied
    os.makedirs(out, exist_ok=True)
    fn = f"{arch}__{shape}__{variant.replace('+', '_')}.json"
    with open(os.path.join(out, fn), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def _report(rec):
    if rec["status"] != "ok":
        print(f"  {rec.get('variant')}: {rec['status']} "
              f"{rec.get('error', '')[:160]}")
        return
    rl = rec["roofline"]
    mem = rec["memory"].get("temp_size_in_bytes", 0) / 2 ** 30
    print(f"  {rec['variant']:>20}: comp {rl['t_compute_s'] * 1e3:9.1f}ms | "
          f"mem {rl['t_memory_s'] * 1e3:9.1f}ms | "
          f"coll {rl['t_collective_s'] * 1e3:9.1f}ms | "
          f"dom={rl['dominant']:<10} frac={rl['roofline_fraction']:.4f} | "
          f"tempHBM {mem:6.2f}GiB")


RECURRENT = {"mamba-110m", "mamba-1.4b", "mamba-2.8b", "mamba2-370m",
             "recurrentgemma-2b", "xlstm-125m"}
BIG = {"deepseek-67b", "deepseek-coder-33b", "mixtral-8x22b"}


def opt_variant(arch: str, shape: str) -> str:
    """Per-family best-known settings (EXPERIMENTS.md §Perf iterations)."""
    from repro.launch.shapes import SHAPES as _S
    kind = _S[shape]["kind"]
    rec = arch in RECURRENT
    moe = arch in ("mixtral-8x22b", "moonshot-v1-16b-a3b")
    if kind == "decode":
        return "baseline"                      # no carries; caches dominate
    act = "act_dp" if rec else "act_sp"
    if kind == "train":
        if arch == "mixtral-8x22b":
            return "act_sp+accum8"             # fits w/o expert re-reads
        if arch == "moonshot-v1-16b-a3b":
            return "act_sp+accum2+moe8k"       # 64-expert dispatch chunked
        if arch in BIG:
            return f"{act}+accum4"
        return f"{act}+accum2"
    if moe:
        return "act_sp+moe8k"                  # prefill
    if arch == "hubert-xlarge":
        return "baseline"       # encoder prefill: act_sp measured slightly
        # worse (0.0079→0.0067) and baseline already fits — keep baseline
    return act                                 # prefill


def opt_sweep(out="experiments/dryrun_opt", multi_pod=False):
    from repro.launch.dryrun import ASSIGNED, PAPER
    from repro.launch.shapes import SHAPES as _S
    for arch in ASSIGNED + PAPER:
        for shape in _S:
            v = opt_variant(arch, shape)
            if v not in VARIANTS:
                VARIANTS[v] = {}
                base = "act_dp" if "act_dp" in v else "act_sp"
                VARIANTS[v].update(VARIANTS[base])
                if "accum4" in v:
                    VARIANTS[v]["__accum__"] = 4
                elif "accum2" in v:
                    VARIANTS[v]["__accum__"] = 2
            rec = run_variant(arch, shape, v, out=out, multi_pod=multi_pod)
            print(f"{arch} {shape} [{v}]", end=" ")
            _report(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape")
    ap.add_argument("--variants", nargs="*", default=["baseline", "act_sp"])
    ap.add_argument("--all-hillclimb", action="store_true")
    ap.add_argument("--opt-sweep", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    if args.opt_sweep:
        opt_sweep(multi_pod=args.multi_pod)
        return
    plan = []
    if args.all_hillclimb:
        plan = HILLCLIMB
    elif args.cell:
        arch, shape = args.cell.split(":")
        plan = [(arch, shape, args.variants)]
    for arch, shape, variants in plan:
        print(f"== {arch} × {shape} ==")
        for v in variants:
            rec = run_variant(arch, shape, v, out=args.out)
            _report(rec)


if __name__ == "__main__":
    main()
