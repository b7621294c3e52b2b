"""Packed-training lane end to end: PackingLoader (mode/policy knobs) +
background prefetch + bf16 mixed precision with f32 scan carries.

    PYTHONPATH=src python examples/train_packed.py
    PYTHONPATH=src python examples/train_packed.py --mode pad --dtype float32
    PYTHONPATH=src python examples/train_packed.py --policy sequential

This is the example-sized version of `python -m repro.launch.train`; the
launcher adds checkpoint/resume, SIGTERM safety, and mesh sharding on top
of exactly this wiring. The gated full-size numbers (single vs pad vs pack
x f32 vs bf16) live in BENCH_train.json (`make bench-train`).
"""
import argparse
import dataclasses
import sys

import jax

sys.path.insert(0, "src")

from repro.configs.base import get_config
from repro.data.dataset import SyntheticCorpus, CorpusConfig
from repro.data.packing_loader import PackingLoader, LoaderConfig
from repro.data.prefetch import PrefetchLoader
from repro.models.lm import build_model
from repro.optim.adamw import AdamW, AdamWConfig, cosine_schedule
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--mode", default="pack",
                    choices=["pack", "pad", "single"])
    ap.add_argument("--policy", default="first_fit_decreasing",
                    choices=["sequential", "sorted_greedy", "first_fit",
                             "first_fit_decreasing"])
    ap.add_argument("--dtype", default="bfloat16",
                    help="activation/compute dtype; scan carries and the "
                         "loss reduction stay f32 regardless")
    ap.add_argument("--param-dtype", default="float32",
                    help="parameter storage dtype (bfloat16 keeps f32 "
                         "master weights inside AdamW)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="batches packed ahead on a background thread "
                         "(0 = synchronous)")
    args = ap.parse_args()

    # a small model from the paper's family; dtype knobs are config fields
    cfg = dataclasses.replace(
        get_config("mamba-110m"), d_model=128, n_layers=4, vocab=512,
        scan_chunk=64, dtype=args.dtype, param_dtype=args.param_dtype)
    model = build_model(cfg)

    # lognormal variable-length stream -> packed (rows, seq_len) buffers;
    # batch(step) is a pure function of step, so the prefetch wrapper is a
    # memoizer and restart replay stays exact
    corpus = SyntheticCorpus(CorpusConfig(
        vocab=cfg.vocab, seed=0, len_min=16, len_max=args.seq_len,
        mu=float(__import__("math").log(args.seq_len / 4.0)), sigma=0.6))
    loader = PackingLoader(corpus, LoaderConfig(
        rows=args.rows, seq_len=args.seq_len, mode=args.mode,
        policy=args.policy))
    print(f"loader: mode={args.mode}, policy={args.policy}, "
          f"padding_rate={loader.stats(0)['padding_rate']:.1%}")
    if args.prefetch > 0:
        loader = PrefetchLoader(loader, depth=args.prefetch)

    opt = AdamW(cosine_schedule(1e-3, warmup=5, total=args.steps),
                AdamWConfig(weight_decay=0.1, clip_norm=1.0))
    trainer = Trainer(model, opt, loader,
                      TrainerConfig(steps=args.steps, log_every=10))
    state, hist = trainer.train(jax.random.PRNGKey(0))

    real = sum(h["real_tokens"] for h in hist)
    buf = sum(h["buffer_tokens"] for h in hist)
    print(f"done: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"over {len(hist)} steps; {real:.0f} real / {buf:.0f} buffer "
          f"tokens ({real / buf:.0%} real)")
    if args.prefetch > 0:
        st = loader.stats(args.steps - 1)
        print(f"prefetch: {st['prefetch_hits']} hits / "
              f"{st['prefetch_misses']} misses")
        loader.close()


if __name__ == "__main__":
    main()
