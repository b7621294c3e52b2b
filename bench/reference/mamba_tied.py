"""Plain float32 reference of a Mamba language model whose head is its
embedding (state-spaces/mamba ``tie_embeddings``): its loss, gradients and
three AdamW steps, run over the chips of a mesh.

The layers, the scan and the blocking of documents are those of
bench/reference/mamba.py, imported and not copied; each document still
runs alone from a zero state. Its loss is repeated here (``_nll_sum_rows``)
because each layer's weights are gathered inside the loop over layers,
which mamba.py's ``_nll_sum`` keeps to itself. What differs:

  * the head is ``embedding.T``, set inside the differentiated function,
    so the embedding's gradient is the sum of the lookup's and the head's
    (published ``MambaLMHeadModel.tie_weights``); the embedding is not
    scaled;
  * the weights, their gradient and AdamW's moments of a 1.4B model are
    about 22 GB in float32, more than one chip holds, so the weights come
    placed on a mesh (bench/drivers/train_mesh.py places them by the
    program's rules, distributed/sharding.py) and the gradient and moments
    keep those placements. Each chip runs its share of a block's rows and
    gathers each layer's weights whole as the layer runs; the gradient of
    the gathered weights is summed back onto their shards. Nothing else
    crosses chips, so no step of the scan waits on another chip.
    Placement changes where the numbers are computed, not which.

Departures from the published model are those of bench/reference/mamba.py
but its untied head: a float32 residual stream and ``highest`` matmuls
(float8_e4m3fn operands for the control), and weight decay on every tensor
of rank >= 2 in the stacked layout.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference import mamba as ref

F32 = ref.F32


def _gather(x, spec):
    """The whole array of which this chip holds the block ``spec`` names."""
    for dim, axes in enumerate(spec):
        if axes is not None:
            x = jax.lax.all_gather(x, axes, axis=dim, tiled=True)
    return x


def _nll_sum_rows(w, tokens, lens, eps, prec, specs, axes):
    """bench/reference/mamba.py's ``_nll_sum`` over this chip's rows, with
    the head the (whole) embedding and each layer gathered as it runs;
    the sums are over every chip's rows."""
    emb = _gather(w["embedding"], specs["embedding"])
    x = emb[tokens].astype(F32)
    if prec == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(F32)

    def layer(x, p):
        p = {k: _gather(v, specs["layers"][k][1:]) for k, v in p.items()}
        return ref._layer(x, p, eps, prec), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    x = ref._rmsnorm(x, _gather(w["norm_f"], specs["norm_f"]), eps)

    # the loss as bench/reference/mamba.py's _nll_sum computes it
    b, L, d = x.shape
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1),
                                                       tokens.dtype)], 1)
    valid = jnp.arange(L)[None, :] < (lens[:, None] - 1)
    C = ref.LOSS_CHUNK

    @jax.checkpoint
    def chunk(carry, xs):
        xc, lc, vc = xs
        logits = ref._mm(xc, emb.T, prec)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        return carry + jnp.sum(jnp.where(vc, nll, 0.0)), None

    def split(a):
        return jnp.moveaxis(a, 1, 0).reshape((L // C, C) + a.shape[:1] +
                                             a.shape[2:])

    total, _ = jax.lax.scan(chunk, jnp.zeros((), F32),
                            (split(x), split(labels), split(valid)))
    return jax.lax.psum(total, axes), jax.lax.psum(jnp.sum(valid), axes)


def _nll_and_grad(mesh, specs):
    """The loss over a block whose rows are spread over every chip of
    ``mesh``, and its gradient, on the weights' own placements
    (``specs``, the PartitionSpec of each weight). Replication is not
    typed: mamba.py's scan starts each row from one zero state; the
    transpose then sums a replicated weight's gradient over the chips."""
    axes = tuple(mesh.axis_names)

    def nll(w, tokens, lens, eps, prec):
        f = jax.shard_map(
            lambda w, t, n: _nll_sum_rows(w, t, n, eps, prec, specs, axes),
            mesh=mesh, in_specs=(specs, P(axes), P(axes)),
            out_specs=(P(), P()), check_vma=False)
        return f(w, tokens, lens)

    return jax.jit(jax.value_and_grad(nll, has_aux=True),
                   static_argnames=("eps", "prec"))


def _pad_rows(tok: np.ndarray, lens: np.ndarray, k: int):
    """A block with empty rows added up to a multiple of ``k``: a row of
    length 0 predicts nothing and adds nothing to the loss or gradient."""
    extra = -tok.shape[0] % k
    return (np.pad(tok, ((0, extra), (0, 0))), np.pad(lens, (0, extra)))


def loss_and_grad(w, docs, eps, prec, max_tokens, mesh, grad_fn):
    """Mean next-token NLL over all documents, and its gradient."""
    rows = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    tot, cnt, g = 0.0, 0, None
    for tok, lens in ref.blocks(docs, max_tokens):
        tok, lens = _pad_rows(tok, lens, mesh.devices.size)
        (s, n), gb = grad_fn(w, jax.device_put(tok, rows),
                             jax.device_put(lens, rows), eps=eps, prec=prec)
        tot += float(s)
        cnt += int(n)
        g = gb if g is None else ref._acc(g, gb)
        del gb
    return tot / cnt, ref._scale(g, 1.0 / cnt)


def specs_of(w):
    """The PartitionSpec of each weight, from its placement."""
    return jax.tree.map(lambda x: x.sharding.spec, w)


def train(w, docs_per_step: List[List[np.ndarray]], opt: dict, eps: float,
          mesh, prec: str = "f32", max_tokens: int = 16384) -> Dict:
    """bench/reference/mamba.py's ``train`` for the tied model, with
    ``w`` (bench/lib/model.py's layout without ``lm_head``) already placed
    on ``mesh`` by NamedShardings; ``max_tokens`` counts the tokens of a
    block over all the chips. Returns the same readings. ``w`` is
    consumed."""
    w0 = jax.device_get(w)
    shardings = jax.tree.map(lambda x: x.sharding, w)
    grad_fn = _nll_and_grad(mesh, specs_of(w))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=shardings)
    m, v = zeros(w), zeros(w)
    losses, first = [], None
    for k, docs in enumerate(docs_per_step, start=1):
        loss, g = loss_and_grad(w, docs, eps, prec, max_tokens, mesh,
                                grad_fn)
        gn = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
        scale = min(1.0, opt["clip_norm"] / max(gn, 1e-9))
        g = ref._scale(g, scale)
        if first is None:
            first = {"clipped": ref.leaf_norms(g),
                     "raw": {n: x / scale for n, x in
                             ref.leaf_norms(g).items()}}
        losses.append(loss)
        w, m, v = ref._adamw(w, m, v, g, ref.lr_at(opt, k), float(k),
                             opt["b1"], opt["b2"], opt["eps"],
                             opt["weight_decay"])
        del g
    change = {}
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(w)[0],
                         jax.tree.leaves(w0)):
        change[jax.tree_util.keystr(p)] = float(
            jnp.sqrt(jnp.sum(jnp.square(a - jax.device_put(b, a.sharding)))))
    return {"losses": losses, "grad": first["clipped"],
            "grad_raw": first["raw"], "change": change}
