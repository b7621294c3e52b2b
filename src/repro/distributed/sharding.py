"""Sharding rules: param/optimizer/batch/cache PartitionSpecs for the
production mesh.

Scheme (DESIGN.md §5): mesh axes ("data", "model") per pod, optional leading
"pod". Batch shards over ("pod","data") — the pod axis is pure DP. Params
shard Megatron-TP over "model" on the heads/ffn/vocab/d_inner dim and
FSDP/ZeRO-3 over "data" on a second dim; XLA inserts the all-gathers.
Optimizer moments inherit the param spec (sharded Adam). Decode KV caches
shard batch over DP axes and *sequence over "model"* (decode-time sequence
parallelism: partial-softmax reductions become model-axis all-reduces).

Every rule is guarded by divisibility — an axis that does not divide the dim
is dropped (falls back to replication), which is what makes the same rules
serve the 1-device smoke tests, the 16×16 pod and the 2×16×16 multi-pod
mesh, and any elastic restart size.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return mesh.shape[name] if name in mesh.shape else 0


def _fit(mesh: Mesh, dim: int, axis):
    """axis if it exists in mesh and divides dim, else None."""
    s = _axis_size(mesh, axis)
    return axis if s and dim % s == 0 else None


def dp_axes(mesh: Mesh):
    """Data-parallel axes: ("pod","data") when pod exists, else ("data",)."""
    names = [n for n in ("pod", "data") if n in mesh.shape]
    return tuple(names)


def batch_axis(mesh: Mesh, batch_size: int):
    """Largest DP prefix whose product divides the batch."""
    axes = dp_axes(mesh)
    if not axes:
        return None
    prod = int(np.prod([mesh.shape[a] for a in axes]))
    if batch_size % prod == 0:
        return axes if len(axes) > 1 else axes[0]
    # try data only
    if "data" in mesh.shape and batch_size % mesh.shape["data"] == 0:
        return "data"
    return None


def model_axis(mesh: Mesh, dim: int):
    """"model" where the mesh has it and it divides ``dim``, else None."""
    return _fit(mesh, dim, "model")


# ---------------------------------------------------------------------------
# parameter rules — keyed by leaf name (last path component)
# ---------------------------------------------------------------------------

def _param_rule(name: str, shape: Tuple[int, ...], mesh: Mesh,
                in_mlstm: bool = False) -> P:
    d = len(shape)
    m, dta = "model", "data"

    def spec2(row, col):                     # helper with divisibility guard
        return (_fit(mesh, shape[-2], row), _fit(mesh, shape[-1], col))

    if in_mlstm and name in ("wq", "wk", "wv"):
        # mlstm square projections consume the model-sharded conv output:
        # row-TP (contraction sharded, output replicated)
        return P(*spec2(m, None))
    if name == "embed":                       # (V, d): vocab-TP + FSDP
        body = spec2(m, dta)
    elif name == "head":                      # (d, V)
        body = spec2(dta, m)
    elif name in ("wq", "w_gate", "w_up", "w_upx", "w_upz", "in_proj",
                  "dt_w", "w_x", "w_y", "w_pre", "shared_gate", "shared_up"):
        body = spec2(dta, m)                  # column-TP (output sharded)
    elif name in ("wo", "w_down", "out_proj", "x_proj", "wo_rec",
                  "shared_down", "w_out", "bc_proj"):
        body = spec2(m, dta)                  # row-TP (contraction sharded)
    elif name == "dt_proj":                   # (d_inner, H): H heads are few
        body = spec2(m, None)                 # — shard the contraction only
    elif name == "wkv":                       # GQA KV: small — replicate cols
        body = spec2(dta, None)
    elif name == "router":                    # (d, E)
        body = spec2(dta, None)
    elif name.startswith("experts_"):         # (E, d_in, d_out)
        ep = _fit(mesh, shape[0], m)
        if ep:                                # expert parallelism
            body = (ep, _fit(mesh, shape[1], dta), None)
        elif name.endswith("down"):           # TP inside expert: (E, ff, d)
            body = (None, _fit(mesh, shape[1], m),
                    _fit(mesh, shape[2], dta))
        else:                                 # (E, d, ff)
            body = (None, _fit(mesh, shape[1], dta),
                    _fit(mesh, shape[2], m))
    elif name in ("conv_w",):                 # (W, channels)
        body = (None, _fit(mesh, shape[-1], m))
    elif name in ("conv_b", "dt_b", "D", "a_param",
                  "ssm_norm_w"):                       # (channels,)
        body = (_fit(mesh, shape[-1], m),)
    elif name == "A_log":
        if d == 1:                            # mamba2: (H,) per-head decay
            body = (_fit(mesh, shape[-1], m),)
        else:                                 # mamba1: (d_inner, N)
            body = (_fit(mesh, shape[-2], m), None)
    elif name in ("w_r", "w_i"):              # (nb, c, c) block-diag gates
        body = (_fit(mesh, shape[-3], m), None, None)
    elif name in ("w_if",):                   # (pf, 2H)
        body = (_fit(mesh, shape[-2], dta), None)
    elif name == "input_proj":
        body = spec2(dta, None)
    elif name == "R":                         # slstm (4, H, dh, dh)
        body = (None, None, None, None)
    else:                                     # norms, biases, scales
        body = tuple(None for _ in shape)
    body = tuple(body[-d:]) if d <= len(body) else \
        (None,) * (d - len(body)) + tuple(body)
    return P(*body)


def param_pspecs(params_shape, mesh: Mesh):
    """Pytree of PartitionSpecs matching a params (shape) tree. Stacked-unit
    leading dims (path contains 'units') get a leading None."""
    def one(path, leaf):
        name = None
        stacked = False
        in_mlstm = False
        for pth in path:
            k = getattr(pth, "key", None)
            if k == "units":
                stacked = True
            if k is not None:
                if "mlstm" in str(k):
                    in_mlstm = True
                name = k
        shape = leaf.shape
        if stacked:
            spec = _param_rule(name, shape[1:], mesh, in_mlstm)
            return P(None, *spec)
        return _param_rule(name, shape, mesh, in_mlstm)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def named(mesh: Mesh, pspecs):
    """PartitionSpec tree → NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def train_state_pspecs(state_shape, mesh: Mesh):
    """PartitionSpecs for a Trainer state ``{"params", "opt": AdamWState}``:
    the optimizer moments (and the f32 master copy, when there is one)
    inherit the param spec; the step counter is replicated."""
    pspec = param_pspecs(state_shape["params"], mesh)
    opt = state_shape["opt"]
    return {"params": pspec,
            "opt": opt._replace(step=P(), m=pspec, v=pspec,
                                master=None if opt.master is None else pspec)}


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------

def batch_pspecs(batch_shape: Dict[str, Any], mesh: Mesh):
    def one(leaf):
        b = batch_axis(mesh, leaf.shape[0])
        return P(b, *([None] * (len(leaf.shape) - 1)))
    return jax.tree.map(one, batch_shape)


def _cache_leaf_spec(name, core, mesh: Mesh):
    """Slot-major cache-leaf body spec: (B, …) → axis tuple (no P)."""
    if name in ("k", "v") and len(core) == 4:      # (B, S, Hkv, hd)
        return (batch_axis(mesh, core[0]), _fit(mesh, core[1], "model"),
                None, None)
    if name == "conv" and len(core) == 3:          # (B, W-1, ch)
        return (batch_axis(mesh, core[0]), None,
                _fit(mesh, core[2], "model"))
    if name == "ssm" and len(core) == 3:           # (B, d_inner, N)
        return (batch_axis(mesh, core[0]),
                _fit(mesh, core[1], "model"), None)
    if name == "ssm" and len(core) == 4:     # (B, H, dh, N) head-struct.
        return (batch_axis(mesh, core[0]),
                _fit(mesh, core[1], "model"), None, None)
    if name == "h" and len(core) == 2:             # (B, lru)
        return (batch_axis(mesh, core[0]), _fit(mesh, core[1], "model"))
    return (batch_axis(mesh, core[0]),) + (None,) * (len(core) - 1)


_CACHE_LEAF_NAMES = ("k", "v", "conv", "ssm", "h", "C", "n", "m", "c")


def _cache_path_info(path):
    name, stacked = None, False
    for pth in path:
        k = getattr(pth, "key", None)
        if k == "units":
            stacked = True
        if k in _CACHE_LEAF_NAMES:
            name = k
    return name, stacked


def cache_pspecs(cache_shape, mesh: Mesh, batch_size: int):
    """Decode caches: batch over DP axes (when divisible), attention K/V
    sequence dim over 'model' (decode sequence parallelism); recurrent
    channel states over 'model'."""
    del batch_size

    def one(path, leaf):
        name, stacked = _cache_path_info(path)
        shp = leaf.shape
        # stacked over units: leading n_units dim
        lead = (None,) if stacked else ()
        core = shp[1:] if stacked else shp
        return P(*(lead + _cache_leaf_spec(name, core, mesh)))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def packed_state_pspecs(state_shape, mesh: Mesh):
    """PartitionSpecs for the packed-prefill handoff states
    (``model.prefill_packed``): same layout as the decode cache but every
    leaf carries a (B, S) leading pair — prefill rows shard like cache
    batch, the per-row segment axis is replicated (segments are scattered
    to arbitrary slots right after harvest, so sharding it would only buy
    an all-to-all). Unit-stacked leaves keep their leading None."""
    def one(path, leaf):
        name, stacked = _cache_path_info(path)
        shp = leaf.shape
        lead = (None,) if stacked else ()
        core = shp[1:] if stacked else shp          # (B, S, …)
        body = _cache_leaf_spec(name, (core[0],) + core[2:], mesh)
        spec = (body[0], None) + body[1:]           # reinsert segment axis
        return P(*(lead + spec))

    return jax.tree_util.tree_map_with_path(one, state_shape)
