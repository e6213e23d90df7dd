"""Logical-axis sharding rules: param/optimizer/cache/batch PartitionSpecs.

Strategy (DESIGN.md §6): 2-D (data, model) mesh per pod, plus an outer
"pod" axis for cross-pod data parallelism.  Parameters are *fully
sharded* — TP dims over "model" (Megatron-style: column-parallel in,
row-parallel out; experts over "model" = EP) and the remaining large dim
over "data" (FSDP / ZeRO-3).  Every rule is divisibility-guarded: a dim
that doesn't divide its axis falls back to replication rather than
failing, so one rule-set serves all ten architectures.

KV caches shard batch over "data" and heads over "model" when the head
count divides, otherwise the *sequence* dim over "model" (context-
parallel decode: GSPMD inserts the softmax partial-reduce collectives).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


class MeshView:
    """A mesh facade hiding some axes from the sharding rules — used
    inside shard_map regions that are *manual* over those axes (sharding
    constraints there may only reference the auto axes).  ``base`` is
    the physical mesh handed to NamedSharding."""

    def __init__(self, base, hidden=()):
        self.base = base
        self._hidden = set(hidden)

    @property
    def axis_names(self):
        return tuple(a for a in self.base.axis_names
                     if a not in self._hidden)

    @property
    def shape(self):
        return {k: v for k, v in self.base.shape.items()
                if k not in self._hidden}


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def shard_map_compat(fn, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off: our regions end
    in all_gather/psum, so outputs *are* replicated even where the
    checker cannot prove it.  ``axis_names`` (optional): the mesh axes
    the region is *manual* over (the rest stay GSPMD-auto)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def make_mesh_auto(sizes: Sequence[int], names: Sequence[str]):
    """``jax.make_mesh`` with all-Auto axis types: regions that mix
    sharding constraints with shard_map need Auto axes, and
    ``jax.make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(tuple(sizes), tuple(names),
                         axis_types=(AxisType.Auto,) * len(tuple(names)))


def maybe(axis, dim: int, mesh: Mesh):
    """Shard `dim` over `axis` only if it divides evenly."""
    if axis is None:
        return None
    sizes = [axis_size(mesh, a) for a in (axis if isinstance(axis, tuple) else (axis,))]
    total = 1
    for s in sizes:
        total *= s
    return axis if total > 1 and dim % total == 0 else None


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


# ------------------------------------------------------------------ params

def fsdp_axes(mesh: Mesh, fsdp_over_pod: bool = True):
    """The FSDP axis set: in-pod 'data', plus 'pod' when present — at
    405B scale the parameters/optimizer must shard over *all* data-
    parallel devices (ZeRO-3 across pods) to fit 16 GB/chip.

    ``fsdp_over_pod=False`` keeps params replicated across pods (pure
    cross-pod DP): required by the compressed gradient-exchange variant,
    where pods only communicate int8 gradient shards."""
    if "pod" in mesh.axis_names and fsdp_over_pod:
        return ("pod", "data")
    return "data"


def param_pspec(path, leaf, mesh: Mesh, fsdp_over_pod: bool = True) -> P:
    """Rule table keyed on the trailing param name; specs cover trailing
    dims and are left-padded with None (stacked layer axes unsharded).
    'data' in the table means the FSDP axis set (pod+data on the
    multi-pod mesh)."""
    name = _path_str(path)
    last = name.rsplit("/", 1)[-1]
    shape = leaf.shape
    nd = len(shape)

    fsdp = fsdp_axes(mesh, fsdp_over_pod)

    def spec(*trailing):
        trailing = ["data" if t == "data" else t for t in trailing]
        trailing = [fsdp if t == "data" else t for t in trailing]
        assert len(trailing) <= nd, (name, shape, trailing)
        full = [None] * (nd - len(trailing)) + trailing
        full = [maybe(a, shape[i], mesh) for i, a in enumerate(full)]
        return P(*full)

    if nd == 0 or last in ("A_log", "dt_bias", "lambda"):
        return P()
    # --- embeddings / heads ---
    if last in ("embed",):
        return spec("model", "data")                 # (V, d)
    if last == "head":
        return spec("data", "model")                 # (d, V)
    if last in ("enc_pos", "dec_pos"):
        return spec(None, "data")
    if last == "vis_proj":
        return spec(None, "model")
    # --- attention ---
    if last in ("wq", "wk", "wv"):
        return spec("data", "model")
    if last == "wo":
        return spec("model", "data")
    # --- MLA ---
    if last in ("w_dq", "w_dkv"):
        return spec("data", None)
    if last in ("w_uq", "w_uk", "w_uv"):
        return spec("data", "model")
    # --- MoE experts (E, d, f) / (E, f, d); router replicated ---
    if last == "router":
        return P(*([None] * nd))
    if last in ("we_gate", "we_up"):
        return spec("model", "data", None)           # E -> model (EP)
    if last == "we_down":
        return spec("model", None, "data")
    if last in ("w_gate", "w_up"):
        return spec("data", "model")
    if last == "w_down":
        return spec("model", "data")
    # --- SSM ---
    if last == "w_in":
        return spec("data", "model")
    if last == "conv_w":
        return spec(None, "model")
    if last in ("conv_b", "D"):
        return spec("model")
    if last == "w_out":
        return spec("model", "data")
    # --- RG-LRU ---
    if last in ("w_x", "w_gate_branch"):
        return spec("data", "model")
    if last == "w" and ("rg" in name or "ig" in name):
        return spec("model", None, None)             # (nb, bw, bw)
    if last == "b" and ("rg" in name or "ig" in name):
        return spec("model", None)
    # --- plain MLP biases ---
    if last == "b_up":
        return spec("model")
    if last == "b_down":
        return spec("data")
    if last == "w_up" or last == "w_gate":
        return spec("data", "model")
    # norms / everything small: replicated
    return P(*([None] * nd))


def param_shardings(params_shape, mesh: Mesh, fsdp_over_pod: bool = True):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_pspec(p, l, mesh,
                                                     fsdp_over_pod)),
        params_shape)


# ------------------------------------------------------------------ caches

def cache_pspec(path, leaf, cfg, mesh: Mesh) -> P:
    name = _path_str(path)
    last = name.rsplit("/", 1)[-1]
    shape = leaf.shape
    nd = len(shape)
    msize = axis_size(mesh, "model")

    def pad(*trailing):
        trailing = list(trailing)
        full = [None] * (nd - len(trailing)) + trailing
        full = [maybe(a, shape[i], mesh) for i, a in enumerate(full)]
        return P(*full)

    if last == "pos" or nd == 0:
        return P()
    if last in ("k", "v"):                           # (..., b, S, kvh, dh)
        if cfg.num_kv_heads % max(msize, 1) == 0 and cfg.num_kv_heads >= msize:
            return pad("data", None, "model", None)
        return pad("data", "model", None, None)      # context-parallel S
    if last == "k_pos":                              # (..., b, S)
        if cfg.num_kv_heads % max(msize, 1) == 0 and cfg.num_kv_heads >= msize:
            return pad("data", None)
        return pad("data", "model")
    if last in ("ck", "cv"):                         # (..., b, Senc, kvh, dh)
        return pad("data", None, None, "model")      # dh -> model
    if last in ("latent", "k_rope"):                 # (..., b, S, r)
        return pad("data", "model", None)
    if last == "state":                              # ssm (..., b, h, p, n)
        return pad("data", "model", None, None)
    if last == "h":                                  # rglru (..., b, w)
        return pad("data", "model")
    if last == "conv":                               # (..., b, w-1, c)
        return pad("data", None, "model")
    return P(*([None] * nd))


def cache_shardings(cache_shape, cfg, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_pspec(p, l, cfg, mesh)), cache_shape)


# ------------------------------------------------------------------ batch

def batch_pspec(leaf, mesh: Mesh) -> P:
    shape = leaf.shape
    if len(shape) == 0:
        return P()
    ba = batch_axes(mesh)
    first = maybe(ba if len(ba) > 1 else ba[0], shape[0], mesh)
    return P(first, *([None] * (len(shape) - 1)))


def batch_shardings(batch_shape, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, batch_pspec(l, mesh)), batch_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
