"""Partition specs for parameters, optimizer state, and step inputs.

Strategy (baseline, "tp_fsdp"):
  * tensor-parallel over the ``model`` axis: attention heads, FFN hidden,
    MoE experts (expert-parallel when E divides the axis, otherwise the
    expert hidden dim is tensor-parallel — e.g. Mixtral's 8 experts on a
    16-wide axis), vocab/lm-head;
  * FSDP (ZeRO-3 style) over the ``data`` axis on a second dimension of
    every large tensor — gradients reduce-scatter, params all-gather, as
    produced by GSPMD from these specs;
  * the ``pod`` axis (multi-pod mesh) extends data parallelism.

Every rule is divisibility-guarded: if a dim does not divide the axis, the
next alternative dim is tried, else the axis is dropped (replicated). This
keeps all 10 heterogeneous architectures lowering with one rule set.

A variant registry (``STRATEGIES``) carries the hillclimb alternatives
(§Perf): e.g. "tp_only" (no FSDP), "fsdp_only", "2d_ffn".
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import (AbstractMesh, AxisType, Mesh, NamedSharding,
                          PartitionSpec as P)


def make_abstract_mesh(axis_sizes: Sequence[int],
                       axis_names: Sequence[str]) -> AbstractMesh:
    """An ``AbstractMesh`` with ``Auto`` axes, the axis type the
    activation constraints of :func:`repro.models.common.maybe_shard`
    require."""
    if len(axis_sizes) != len(axis_names):
        raise ValueError(f"{len(axis_sizes)} axis sizes for "
                         f"{len(axis_names)} axis names")
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


def _axis_size(mesh, name: str) -> int:
    # works for Mesh and AbstractMesh alike
    return dict(mesh.shape).get(name, 1)


def _data_axes(mesh: Mesh):
    """data-parallel axes: ('pod','data') on the multi-pod mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# rule table: leaf-name (+ndim) -> list of (dim, axis-role) preferences.
# axis-role: "model" = TP axis, "data" = FSDP axis. dim indices refer to the
# STACKED tensor (leading L axis for block params). Alternatives for the
# same role are tried left to right.
def _rules(name: str, ndim: int, parent: str) -> List[Tuple[str, List[int]]]:
    r: List[Tuple[str, List[int]]] = []
    if name == "embed":
        return [("model", [0]), ("data", [1])]
    if name == "lm_head":
        return [("model", [1, 0]), ("data", [0])]
    if parent in ("attn", "xattn"):
        if name == "wq":
            return [("model", [2]), ("data", [1])]
        if name in ("wk", "wv"):
            return [("model", [2]), ("data", [1])]
        if name == "wo":
            return [("model", [1]), ("data", [3])]
    if parent == "moe":
        if name == "router":
            return [("data", [1])]
        if name in ("w1", "w3"):       # [L, E, d, f]
            return [("model", [1, 3]), ("data", [2])]
        if name == "w2":               # [L, E, f, d]
            return [("model", [1, 2]), ("data", [3])]
        if name in ("shared_w1", "shared_w3"):
            return [("model", [2]), ("data", [1])]
        if name == "shared_w2":
            return [("model", [1]), ("data", [2])]
    if parent == "ffn" or (parent == "cm" and name in ("wk", "wv")):
        if name in ("w1", "w3", "wk"):  # [L, d, f]
            return [("model", [2]), ("data", [1])]
        if name in ("w2", "wv"):        # [L, f, d]
            return [("model", [1]), ("data", [2])]
    if parent == "tm":  # rwkv time mix
        if name in ("wr", "wk", "wv", "wg"):
            return [("model", [2]), ("data", [1])]
        if name == "wo":
            return [("model", [1]), ("data", [2])]
        if name in ("shift_lora_a", "w_lora_a"):
            return [("data", [1])]
        if name == "shift_lora_b":
            return [("data", [3])]
        if name == "w_lora_b":
            return [("data", [2])]
    if parent == "mamba":
        if name in ("in_proj", "w_bc"):
            return [("data", [1])]
        if name in ("out_proj",):
            return [("data", [2])]
    if parent in ("cells",):  # LSTM — replicated
        return []
    return []  # norms, scalars, small vectors: replicated


def leaf_spec(path, leaf, mesh: Mesh, fsdp: bool = True,
              tp: bool = True, fsdp_in_pod: bool = False) -> P:
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    shape = leaf.shape
    assign: Dict[int, object] = {}
    data_axes = _data_axes(mesh)
    if fsdp_in_pod:
        # keep the ZeRO-3 gather inside a pod: params replicated across the
        # (slower, inter-pod) 'pod' axis, sharded over 'data' only
        data_axes = tuple(a for a in data_axes if a != "pod")
    data_sz = int(np.prod([_axis_size(mesh, a) for a in data_axes]))
    model_sz = _axis_size(mesh, "model")
    for role, dims in _rules(name, len(shape), parent):
        if role == "model" and not tp:
            continue
        if role == "data" and not fsdp:
            continue
        size = model_sz if role == "model" else data_sz
        axis_val = "model" if role == "model" else (
            data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None))
        if size <= 1 or axis_val is None:
            continue
        for d in dims:
            if d in assign:
                continue
            if shape[d] % size == 0:
                assign[d] = axis_val
                break
    spec = [assign.get(d) for d in range(len(shape))]
    return P(*spec)


def param_specs(params_struct, mesh: Mesh, fsdp: bool = True, tp: bool = True,
                fsdp_in_pod: bool = False, **_ignored):
    """Pytree of PartitionSpec matching ``params_struct`` (works for params
    and for optimizer state, whose subtrees mirror parameter paths)."""
    flat = jax.tree_util.tree_flatten_with_path(params_struct)[0]
    specs = [leaf_spec(path, leaf, mesh, fsdp, tp, fsdp_in_pod)
             for path, leaf in flat]
    treedef = jax.tree_util.tree_structure(params_struct)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# step-input shardings


def batch_specs(batch_struct, mesh: Mesh):
    """Training batch: shard the leading (global batch) dim over pod+data."""
    data_axes = _data_axes(mesh)
    ax = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)

    def one(path, leaf):
        b = leaf.shape[0] if leaf.ndim else 1
        sz = int(np.prod([_axis_size(mesh, a) for a in data_axes]))
        if leaf.ndim and sz > 1 and b % sz == 0:
            return P(ax, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    flat = jax.tree_util.tree_flatten_with_path(batch_struct)[0]
    specs = [one(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(batch_struct), specs)


def cache_specs(cache_struct, mesh: Mesh, seq_over_model: bool = False):
    """Decode cache: batch dim over pod+data when divisible, else the
    sequence/window dim (long-context batch=1); KV heads replicated.

    ``seq_over_model=True`` additionally shards the cache sequence dim over
    the model axis (flash-decode style partial attention + psum) — the
    hillclimb variant that makes the 1T-param decode shapes fit HBM."""
    data_axes = _data_axes(mesh)
    ax = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    sz = int(np.prod([_axis_size(mesh, a) for a in data_axes]))
    model_sz = _axis_size(mesh, "model")

    def one(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        spec = [None] * leaf.ndim
        if sz <= 1 or ax is None or leaf.ndim < 2:
            return P(*spec)
        # stacked caches: dim0 = L (or scalar length), dim1 = batch
        b_dim = 1
        if leaf.ndim > b_dim and leaf.shape[b_dim] % sz == 0:
            spec[b_dim] = ax
            if (seq_over_model and leaf.ndim >= 3 and model_sz > 1
                    and leaf.shape[2] % model_sz == 0 and leaf.shape[2] >= 1024):
                spec[2] = "model"
        elif leaf.ndim >= 3 and leaf.shape[2] % sz == 0:
            spec[2] = ax  # sequence/window dim
        return P(*spec)

    flat = jax.tree_util.tree_flatten_with_path(cache_struct)[0]
    specs = [one(p, l) for p, l in flat]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache_struct), specs)


def tree_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


STRATEGIES = {
    # baseline
    "tp_fsdp": dict(fsdp=True, tp=True),
    # hillclimb variants (§Perf)
    "tp_only": dict(fsdp=False, tp=True),          # params resident (decode)
    "fsdp_only": dict(fsdp=True, tp=False),
    "tp_fsdp_inpod": dict(fsdp=True, tp=True, fsdp_in_pod=True),
    "tp_fsdp_seqkv": dict(fsdp=True, tp=True, seq_over_model=True),
    "tp_only_seqkv": dict(fsdp=False, tp=True, seq_over_model=True),
    "tp_fsdp_flatkv": dict(fsdp=True, tp=True, seq_over_model=False),
}
