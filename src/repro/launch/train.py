"""Production training launcher: sharded train step on the production mesh
(or whatever devices exist), checkpoint/resume, SIGTERM-safe.

With more than one device it builds a ("data", "model") mesh over them
(``--model-axis`` sets the model axis) and the Trainer creates the state
sharded by distributed/sharding.py and places every batch over the data
axis. On one device it runs the same step unsharded. The persistent
compilation cache is on (repro/compile_cache.py).

  PYTHONPATH=src python -m repro.launch.train --arch mamba-110m \
      --rows 8 --seq-len 4096 --steps 100 --ckpt-dir /tmp/ckpt
  # dry-run the full production mesh instead of executing:
  PYTHONPATH=src python -m repro.launch.train --arch mamba-2.8b --dry-run

Recommended real-TPU XLA flags (latency-hiding overlap of the FSDP
all-gathers / grad reduce-scatters with compute; bf16 collective payload):
  --xla_tpu_enable_latency_hiding_scheduler=true
  --xla_tpu_megacore_fusion_allow_ags=true
  --xla_enable_async_collective_permute=true
  --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
"""
import argparse
import dataclasses
import os

import jax

from repro.compile_cache import use_compile_cache
from repro.configs.base import get_config
from repro.data.dataset import SyntheticCorpus, CorpusConfig
from repro.data.packing_loader import PackingLoader, LoaderConfig
from repro.launch.mesh import make_local_mesh
from repro.models.lm import build_model
from repro.obs import Obs, profiler_session
from repro.optim.adamw import AdamW, AdamWConfig, cosine_schedule
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba-110m")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the model for a CPU demo / smoke run")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--mode", default="pack",
                    choices=["pack", "pad", "single"])
    ap.add_argument("--policy", default="sequential",
                    choices=["sequential", "sorted_greedy", "first_fit",
                             "first_fit_decreasing"])
    ap.add_argument("--dtype", default=None,
                    help="activation/compute dtype override (e.g. bfloat16 "
                         "for the mixed-precision lane; scan carries and "
                         "the loss reduction stay f32 regardless)")
    ap.add_argument("--param-dtype", default=None,
                    help="parameter storage dtype (bfloat16 keeps f32 "
                         "master weights in the optimizer)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host-side batches packed ahead of the device "
                         "step (0 = synchronous loader)")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-axis", type=int, default=1,
                    help="TP size on the local mesh")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile on the 16x16 production mesh")
    ap.add_argument("--obs-trace", default=None, metavar="PATH",
                    help="record per-step train spans (data wait / fused "
                         "step / compile marks) and export a Chrome "
                         "trace-event JSON here")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="also capture an XLA profile (jax.profiler, "
                         "TensorBoard format) into this directory")
    args = ap.parse_args()

    if args.dry_run:
        from repro.launch.dryrun import run_cell     # sets 512 devices? no —
        # dryrun sets XLA_FLAGS at import; for a clean dry-run use the
        # dedicated module entry instead:
        raise SystemExit(
            "use: python -m repro.launch.dryrun --arch "
            f"{args.arch} --shape train_4k --mesh both")

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = dataclasses.replace(cfg, d_model=128, n_layers=4, vocab=512,
                                  dtype="float32", scan_chunk=64)
    if args.dtype or args.param_dtype:
        cfg = dataclasses.replace(
            cfg, dtype=args.dtype or cfg.dtype,
            param_dtype=args.param_dtype or cfg.param_dtype)
    model = build_model(cfg)
    obs = Obs.on() if args.obs_trace else Obs.off()
    corpus = SyntheticCorpus(CorpusConfig(vocab=cfg.vocab, seed=0))
    loader = PackingLoader(corpus, LoaderConfig(
        rows=args.rows, seq_len=args.seq_len, mode=args.mode,
        policy=args.policy))
    if args.prefetch > 0:
        from repro.data.prefetch import PrefetchLoader
        loader = PrefetchLoader(loader, depth=args.prefetch, obs=obs)
    opt = AdamW(cosine_schedule(args.lr, warmup=max(1, args.steps // 20),
                                total=args.steps),
                AdamWConfig(weight_decay=0.1, clip_norm=1.0))

    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        mesh = make_local_mesh(model_axis=args.model_axis)
        print(f"mesh {dict(mesh.shape)}; sharded train step")
    trainer = Trainer(model, opt, loader, TrainerConfig(
        steps=args.steps, accum=args.accum, log_every=10,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir), obs=obs, mesh=mesh)
    print(f"training {cfg.name}: {args.steps} steps, mode={args.mode}, "
          f"rows={args.rows}x{args.seq_len}, devices={n_dev}")
    with profiler_session(args.profile_dir) as profiling:
        state, hist = trainer.train(jax.random.PRNGKey(0))
    print(f"done; final loss {hist[-1]['loss']:.4f}")
    if args.obs_trace:
        obs.export(args.obs_trace)
        print(f"obs: wrote {len(obs.tracer.chrome_events())} trace events "
              f"to {args.obs_trace} (open in chrome://tracing or "
              f"ui.perfetto.dev)")
    obs.close()
    if args.profile_dir and profiling:
        print(f"obs: XLA profile captured under {args.profile_dir}")


if __name__ == "__main__":
    main()
