"""Fix a train cell's row count once: the largest of 8, 4 and 2 rows whose
compiled packed train step plans within 85% of the chip's ``bytes_limit``
(the rule chip_smoke.py uses). Run on the chip:

    python bench/tools/plan_rows.py mamba-110m mamba-1.4b-s12

Prints one line per (configuration, rows) tried, largest first, up to
the first that fits; the chosen number is written by hand into the cell's file under
``bench/workloads/``, so the tokens per step stay fixed.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

HEADROOM = 0.85
CANDIDATES = (8, 4, 2)


def planned_bytes(conf: dict, traffic: dict, rows: int) -> int:
    import jax
    import jax.numpy as jnp
    from bench.drivers.train import build_trainer
    trainer, _ = build_trainer(conf, traffic, loader=None)
    state = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((rows, traffic["seq_len"]), jnp.int32)
             for k in ("tokens", "positions", "segment_ids")}
    m = trainer.step_fn.lower(state, batch).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes +
            m.temp_size_in_bytes - m.alias_size_in_bytes)


def main():
    import jax
    from bench.lib.harness import load_json
    dev = jax.devices()[0]
    limit = dev.memory_stats()["bytes_limit"]
    traffic = load_json("traffic", "pack4k")
    for name in sys.argv[1:]:
        conf = load_json("configs", name)
        for rows in CANDIDATES:
            t0 = time.perf_counter()
            b = planned_bytes(conf, traffic, rows)
            fits = b <= HEADROOM * limit
            print(json.dumps({"config": name, "rows": rows, "planned": b,
                              "limit": limit, "share": b / limit,
                              "fits": fits,
                              "compile_s": time.perf_counter() - t0}),
                  flush=True)
            if fits:
                break


if __name__ == "__main__":
    main()
