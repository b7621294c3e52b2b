"""Model side of the harness: a configuration file -> the program's model,
and the weights the benchmark makes from the seed.

The weights are made here, not by the program, so that the plain
reference (bench/reference/mamba.py) and the system under test start from
the same numbers without the reference taking anything the program made.
They follow the published Mamba initialisation (mamba_ssm
``Mamba.__init__`` and ``MambaLMHeadModel._init_weights``) and are laid
out per layer, stacked over layers, with matrices as ``x @ W``:

    embedding (V, d)   norm_f (d,)   lm_head (d, V)
    layers/norm (L, d)        layers/in_proj (L, d, 2di)
    layers/conv1d_weight (L, K, di)  (tap K-1 multiplies the current token)
    layers/conv1d_bias (L, di)       layers/x_proj (L, di, R + 2N)
    layers/dt_proj_weight (L, R, di) layers/dt_proj_bias (L, di)
    layers/A_log (L, di, N)   layers/D (L, di)   layers/out_proj (L, di, d)

``to_program`` renames that tree into the program's parameter tree and
checks every shape against ``LM.init``'s, so a change of the program's
layout fails loudly here instead of training on misplaced weights.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int
    layers: int
    vocab: int          # padded rows of the embedding and head
    vocab_real: int     # token ids are drawn below this
    di: int
    N: int
    K: int
    R: int
    eps: float


def sizes(conf: dict) -> Sizes:
    ssm = conf["ssm_cfg"]
    d = conf["d_model"]
    mult = conf.get("pad_vocab_size_multiple", 1)
    v = conf["vocab_size"]
    dt_rank = ssm.get("dt_rank", "auto")
    return Sizes(d=d, layers=conf["n_layer"],
                 vocab=-(-v // mult) * mult, vocab_real=v,
                 di=ssm["expand"] * d, N=ssm["d_state"], K=ssm["d_conv"],
                 R=math.ceil(d / 16) if dt_rank == "auto" else dt_rank,
                 eps=conf["norm_epsilon"])


def program_config(conf: dict):
    """The program's ArchConfig for this file: its registry entry with the
    file's sizes. Execution options stay at the program's defaults."""
    from repro.configs.base import get_config
    if conf["family"] != "mamba" or conf["tie_embeddings"]:
        raise ValueError(f"{conf['name']}: only untied mamba configurations "
                         f"are supported by this harness")
    s = sizes(conf)
    return dataclasses.replace(
        get_config(conf["registry"]), n_layers=s.layers, d_model=s.d,
        vocab=s.vocab, d_state=s.N, d_conv=s.K,
        expand=conf["ssm_cfg"]["expand"], dt_rank=s.R, norm_eps=s.eps,
        tie_embeddings=False)


def init_weights(s: Sizes, key) -> Dict[str, Any]:
    """Published Mamba initialisation in float32 (jit this)."""
    L, d, di, N, K, R, V = s.layers, s.d, s.di, s.N, s.K, s.R, s.vocab
    ks = jax.random.split(key, 9)

    def unif(k, shape, bound):
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

    # dt bias: softplus^-1 of dt ~ logU(1e-3, 1e-1), floored at 1e-4
    dt = jnp.exp(jax.random.uniform(ks[6], (L, di)) *
                 (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (L, di, N))
    return {
        "embedding": 0.02 * jax.random.normal(ks[0], (V, d), jnp.float32),
        "layers": {
            "norm": jnp.ones((L, d), jnp.float32),
            "in_proj": unif(ks[1], (L, d, 2 * di), d ** -0.5),
            "conv1d_weight": unif(ks[2], (L, K, di), K ** -0.5),
            "conv1d_bias": unif(ks[3], (L, di), K ** -0.5),
            "x_proj": unif(ks[4], (L, di, R + 2 * N), di ** -0.5),
            "dt_proj_weight": unif(ks[5], (L, R, di), R ** -0.5),
            "dt_proj_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(A),
            "D": jnp.ones((L, di), jnp.float32),
            # rescale_prenorm_residual: out_proj / sqrt(n_layer)
            "out_proj": unif(ks[7], (L, di, d), di ** -0.5) / math.sqrt(L),
        },
        "norm_f": jnp.ones((d,), jnp.float32),
        "lm_head": unif(ks[8], (d, V), d ** -0.5),
    }


_PROGRAM_NAMES = {"norm": "norm", "in_proj": "in_proj",
                  "conv1d_weight": "conv_w", "conv1d_bias": "conv_b",
                  "x_proj": "x_proj", "dt_proj_weight": "dt_w",
                  "dt_proj_bias": "dt_b", "A_log": "A_log", "D": "D",
                  "out_proj": "out_proj"}


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """The same arrays under the program's names (no copy)."""
    return {"embed": w["embedding"],
            "units": {"0_mamba": {_PROGRAM_NAMES[k]: v
                                  for k, v in w["layers"].items()}},
            "final_norm": w["norm_f"], "head": w["lm_head"]}


def from_program(p: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of ``to_program`` (no copy)."""
    inv = {v: k for k, v in _PROGRAM_NAMES.items()}
    return {"embedding": p["embed"],
            "layers": {inv[k]: v for k, v in p["units"]["0_mamba"].items()},
            "norm_f": p["final_norm"], "lm_head": p["head"]}


def check_layout(model, s: Sizes) -> None:
    """Raise unless ``to_program`` yields exactly the program's tree."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: to_program(init_weights(s, k)),
                         jax.random.PRNGKey(0))
    wl = jax.tree_util.tree_flatten_with_path(want)[0]
    gl = jax.tree_util.tree_flatten_with_path(got)[0]
    w = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in wl}
    g = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in gl}
    if w != g:
        raise RuntimeError(f"program parameter layout differs from the "
                           f"benchmark's weights: program {w}, benchmark {g}")


def jax_key(seed: int, salt: int):
    """A PRNG key from a seed of any size (seeds may pass 2**32)."""
    import numpy as np
    word = np.random.SeedSequence([seed, salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) >> 1)


def bench_name(program_path: str) -> str:
    """The benchmark's name of a program leaf, e.g.
    "['units']['0_mamba']['conv_w']" -> "['layers']['conv1d_weight']"."""
    top = {"['embed']": "['embedding']", "['final_norm']": "['norm_f']",
           "['head']": "['lm_head']"}
    if program_path in top:
        return top[program_path]
    leaf = program_path.rsplit("['", 1)[1][:-2]
    inv = {v: k for k, v in _PROGRAM_NAMES.items()}
    return f"['layers']['{inv[leaf]}']"
