"""Plain float32 reference of a Mamba language model: its loss, gradients,
three AdamW steps, and the logits of served tokens.

It follows the published Mamba (Gu & Dao 2023; mamba_ssm
``mamba_simple.Mamba`` and ``MambaLMHeadModel``) and runs every document
alone, from a zero state, so it owes nothing to packing: agreement with
the packed program is the PackMamba property. It imports nothing of the
program and takes only the weights the benchmark made
(bench/lib/model.py's layout) and the documents of the traffic.

Per layer, with x the residual stream of one document:
    h = RMSNorm(x);  [u, z] = h W_in;  u = SiLU(causal depthwise conv(u))
    [dt, B, C] = u W_x;  delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(delta_t A) s_{t-1} + delta_t u_t B_t;  y_t = C_t s_t + D u_t
    x = x + (y * SiLU(z)) W_out
then RMSNorm and the head; the loss is the mean next-token cross-entropy
over every token of every document that has a successor in it.

Departures from the published model, all forced by what is compared:
  * the residual stream is float32 throughout (published: fp32 residual,
    bf16 or fp32 elsewhere); every matmul runs at ``highest`` precision,
    or, for the control, with both operands rounded to float8_e4m3fn;
  * the head is untied (see each configuration file);
  * weight decay applies to every tensor of rank >= 2 in the stacked
    layout, the rule of the job in bench/traffic/pack4k.json.
The scan walks time one step at a time; chunks of 64 steps are
rematerialised in the backward pass so that the states of a 2048-token
document are never all held.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SCAN_CHUNK = 64
LOSS_CHUNK = 128
LENGTH_QUANTUM = 512


def _mm(a, b, prec: str):
    if prec == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(F32)
        b = b.astype(jnp.float8_e4m3fn).astype(F32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _scan(u, delta, A, Bm, Cm):
    """y_t = C_t s_t with s_t = exp(delta_t A) s_{t-1} + delta_t u_t B_t."""
    b, L, di = u.shape
    T = SCAN_CHUNK

    def step(s, xs):
        u_t, d_t, B_t, C_t = xs
        s = jnp.exp(d_t[:, :, None] * A) * s + \
            (d_t * u_t)[:, :, None] * B_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, C_t,
                             precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def chunk(s, xs):
        return jax.lax.scan(step, s, xs)

    def split(a):                       # (b, L, k) -> (L/T, T, b, k)
        return jnp.moveaxis(a, 1, 0).reshape(L // T, T, b, a.shape[-1])

    s0 = jnp.zeros((b, di, A.shape[1]), F32)
    _, y = jax.lax.scan(chunk, s0, tuple(map(split, (u, delta, Bm, Cm))))
    return jnp.moveaxis(y.reshape(L, b, di), 0, 1)


def _layer(x, p, eps, prec):
    K, di = p["conv1d_weight"].shape
    N = p["A_log"].shape[1]
    R = p["dt_proj_weight"].shape[0]
    h = _rmsnorm(x, p["norm"], eps)
    u, z = jnp.split(_mm(h, p["in_proj"], prec), 2, axis=-1)
    L = u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(up[:, k:k + L] * p["conv1d_weight"][k] for k in range(K)) + \
        p["conv1d_bias"]
    u = jax.nn.silu(u)
    dt, Bm, Cm = jnp.split(_mm(u, p["x_proj"], prec), [R, R + N], axis=-1)
    delta = jax.nn.softplus(_mm(dt, p["dt_proj_weight"], prec) +
                            p["dt_proj_bias"])
    y = _scan(u, delta, -jnp.exp(p["A_log"]), Bm, Cm) + p["D"] * u
    return x + _mm(y * jax.nn.silu(z), p["out_proj"], prec)


def hidden(w, tokens, eps: float, prec: str):
    """Final normed hidden states (b, L, d) of right-padded documents."""
    x = w["embedding"][tokens].astype(F32)
    if prec == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(F32)
    body = jax.checkpoint(lambda x, p: (_layer(x, p, eps, prec), None))
    x, _ = jax.lax.scan(body, x, w["layers"])
    return _rmsnorm(x, w["norm_f"], eps)


def _nll_sum(w, tokens, lens, eps: float, prec: str):
    """(sum of next-token NLL, number of predicted tokens) over documents
    tokens (b, L) of lengths lens (b,), each right-padded."""
    x = hidden(w, tokens, eps, prec)
    b, L, d = x.shape
    labels = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1),
                                                       tokens.dtype)], 1)
    valid = jnp.arange(L)[None, :] < (lens[:, None] - 1)
    C = LOSS_CHUNK

    @jax.checkpoint
    def chunk(carry, xs):
        xc, lc, vc = xs
        logits = _mm(xc, w["lm_head"], prec)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        return carry + jnp.sum(jnp.where(vc, nll, 0.0)), None

    def split(a):
        return jnp.moveaxis(a, 1, 0).reshape((L // C, C) + a.shape[:1] +
                                             a.shape[2:])

    total, _ = jax.lax.scan(chunk, jnp.zeros((), F32),
                            (split(x), split(labels), split(valid)))
    return total, jnp.sum(valid)


_nll_and_grad = jax.jit(jax.value_and_grad(_nll_sum, has_aux=True),
                        static_argnames=("eps", "prec"))


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def blocks(docs: Sequence[np.ndarray], max_tokens: int):
    """Documents sorted by length, in blocks of right-padded rows. A block
    whose longest document rounds up to L (a multiple of 512) holds at
    most pow2_floor(max_tokens / L) rows, and its row count is a power of
    two, so there are few shapes."""
    order = sorted(range(len(docs)), key=lambda i: len(docs[i]))
    quant = lambda n: -(-n // LENGTH_QUANTUM) * LENGTH_QUANTUM
    groups, cur = [], []
    for i in order:
        if cur and len(cur) >= _pow2_floor(max_tokens // quant(len(docs[i]))):
            groups.append(cur)
            cur = []
        cur.append(i)
    if cur:
        groups.append(cur)
    out = []
    for g in groups:
        L = quant(len(docs[g[-1]]))
        rows = min(_pow2_floor(max_tokens // L), 1 << (len(g) - 1).bit_length())
        tok = np.zeros((rows, L), np.int32)
        lens = np.zeros((tok.shape[0],), np.int32)
        for r, j in enumerate(g):
            tok[r, :len(docs[j])] = docs[j]
            lens[r] = len(docs[j])
        out.append((tok, lens))
    return out


_acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_scale = jax.jit(lambda a, c: jax.tree.map(lambda x: x * c, a),
                 donate_argnums=0)


def loss_and_grad(w, docs, eps, prec, max_tokens):
    """Mean next-token NLL over all documents, and its gradient."""
    tot, cnt, g = 0.0, 0, None
    for tok, lens in blocks(docs, max_tokens):
        (s, n), gb = _nll_and_grad(w, tok, lens, eps=eps, prec=prec)
        tot += float(s)
        cnt += int(n)
        g = gb if g is None else _acc(g, gb)
        del gb
    return tot / cnt, _scale(g, 1.0 / cnt)


def block_tokens(layers: int, d: int, di: int, budget: float = 6e9) -> int:
    """Tokens per block that keep the reference's activations (each
    layer's input, saved for the backward pass, and about sixteen
    (tokens, d_inner) float32 temporaries) within ``budget`` bytes."""
    return int(budget / (4 * (layers * d + 16 * di)))


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                            for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine to a tenth (the job's schedule)."""
    base, warm, total = opt["lr"], opt["warmup"], opt["total"]
    if step < warm:
        return base * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * t)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 1, 2))
def _adamw(w, m, v, g, lr, k, b1, b2, eps, wd):
    def one(w, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** k)) / (jnp.sqrt(v / (1 - b2 ** k)) + eps)
        if w.ndim >= 2:
            upd = upd + wd * w
        return w - lr * upd, m, v
    out = jax.tree.map(one, w, m, v, g)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def train(w, docs_per_step: List[List[np.ndarray]], opt: dict, eps: float,
          prec: str = "f32", max_tokens: int = 16384) -> dict:
    """Three (or len(docs_per_step)) AdamW steps from weights ``w``.

    Returns the loss of each step, the norms of the first gradient after
    global-norm clipping, the norms of the gradient before it, and the
    norms of each leaf's change over all the steps. ``w`` is consumed.
    """
    w0 = jax.device_get(w)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first = [], None
    for k, docs in enumerate(docs_per_step, start=1):
        loss, g = loss_and_grad(w, docs, eps, prec, max_tokens)
        gn = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
        scale = min(1.0, opt["clip_norm"] / max(gn, 1e-9))
        g = _scale(g, scale)
        if first is None:
            first = {"clipped": leaf_norms(g),
                     "raw": {n: x / scale for n, x in leaf_norms(g).items()}}
        losses.append(loss)
        w, m, v = _adamw(w, m, v, g, lr_at(opt, k), float(k), opt["b1"],
                         opt["b2"], opt["eps"], opt["weight_decay"])
        del g
    change = {}
    for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(w)[0],
                         jax.tree.leaves(w0)):
        change[jax.tree_util.keystr(p)] = float(
            jnp.sqrt(jnp.sum(jnp.square(a - jnp.asarray(b)))))
    return {"losses": losses, "grad": first["clipped"],
            "grad_raw": first["raw"], "change": change}


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _logits_at(w, tokens, rows, cols, eps, prec):
    x = hidden(w, tokens, eps, prec)
    return _mm(x[rows, cols], w["lm_head"], prec)


def served_logits(w, seqs: Sequence[np.ndarray], starts: Sequence[int],
                  eps: float, prec: str) -> List[np.ndarray]:
    """Logits that predict each served token: for a request whose prompt
    and served tokens are ``seqs[i]`` (the last served token dropped) and
    whose first served token is predicted at ``starts[i]``, the rows of
    positions starts[i] .. len(seqs[i]) - 1."""
    L = -(-max(len(s) for s in seqs) // LENGTH_QUANTUM) * LENGTH_QUANTUM
    tok = np.zeros((len(seqs), L), np.int32)
    rows, cols = [], []
    for i, (s, a) in enumerate(zip(seqs, starts)):
        tok[i, :len(s)] = s
        rows += [i] * (len(s) - a)
        cols += list(range(a, len(s)))
    out = np.asarray(_logits_at(w, tok, np.asarray(rows), np.asarray(cols),
                                eps=eps, prec=prec))
    bounds = np.cumsum([0] + [len(s) - a for s, a in zip(seqs, starts)])
    return [out[bounds[i]:bounds[i + 1]] for i in range(len(seqs))]
