"""Logical-axis partitioning rules: param/batch/cache pytrees → PartitionSpec.

Parallelism map (mesh axes: optional ``pod`` × ``data`` × ``model``):

  DP  — batch over (``pod``, ``data``); gradient psum inserted by GSPMD.
  TP  — Megatron col→row: qkv/up projections column-sharded over ``model``,
        o/down projections row-sharded; vocab/lm-head sharded over ``model``.
  EP  — MoE expert dim over ``model`` (every assigned MoE arch has ≥16
        experts); dispatch gather/scatter lowers to all-to-alls.
  SP  — long-context decode caches sequence-sharded (over ``model``, plus
        ``data`` when the batch can't use it), giving flash-decode style
        partial-softmax combines via GSPMD.

Rules are name-keyed (leaf names are unique across the zoo) with a
divisibility guard: a dim is only sharded if the mesh axis divides it
(e.g. mamba2's 50280 vocab stays replicated rather than force-padded).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "param_pspecs",
    "batch_pspecs",
    "opt_pspecs",
    "cache_pspecs",
    "named",
    "dp_axes",
]


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _guard(mesh: Mesh, dim: int, axes):
    """Shard ``dim`` over ``axes`` only if divisible; else replicate."""
    return axes if dim % _axis_size(mesh, axes) == 0 else None


# ---------------------------------------------------------------------------
# parameter rules (matched on the final dict key of the path)
# ---------------------------------------------------------------------------

_FSDP_MIN_ELEMS = 1 << 20


def _apply_fsdp(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-3 style: also shard the first free, divisible dim over 'data'.

    Giant models (arctic-480b: 60 GiB/dev params at TP-16 alone) cannot hold
    model-axis-only sharded params+moments in 16 GiB HBM; FSDP sharding over
    'data' brings params/dev to size/256, with GSPMD inserting the per-layer
    all-gathers inside the scan body (bounded working set)."""
    n = 1
    for d in shape:
        n *= d
    if n < _FSDP_MIN_ELEMS:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    dsz = mesh.shape["data"]
    for i, (dim, cur) in enumerate(zip(shape, entries)):
        if cur is None and dim % dsz == 0 and dim >= dsz:
            entries[i] = "data"
            return P(*entries)
    return spec


def _param_rule(name: str, shape: Tuple[int, ...], mesh: Mesh, fsdp: bool = False) -> P:
    nd = len(shape)
    m = "model"

    def spec(*tail):
        """Pad with leading Nones to the leaf's rank (stacked-layer axes)."""
        pad = (None,) * (nd - len(tail))
        out = P(*pad, *tail)
        return _apply_fsdp(out, shape, mesh) if fsdp else out

    if name == "embed":          # (V, D)
        out = P(_guard(mesh, shape[0], m), None)
        return _apply_fsdp(out, shape, mesh) if fsdp else out
    if name == "head":           # (D, V)
        out = P(None, _guard(mesh, shape[1], m))
        return _apply_fsdp(out, shape, mesh) if fsdp else out
    if name in ("we_gate", "we_up", "we_down"):
        # MoE expert weights (…, E, D, F): EP over the expert dim
        out = P(*((None,) * (nd - 3)), _guard(mesh, shape[-3], m), None, None)
        return _apply_fsdp(out, shape, mesh) if fsdp else out
    if name in ("wk", "wv", "bk", "bv"):
        # kv projections: replicated — every assigned GQA arch has fewer kv
        # heads than the model axis, and the TP attention block wants whole
        # kv heads per device (the weights are tiny).
        return spec(*((None,) * min(nd, 2)))
    if name in ("wq", "wz", "wx", "wdt", "w_gate", "w_up"):
        return spec(None, _guard(mesh, shape[-1], m))
    if name in ("bq", "b_up"):
        return spec(_guard(mesh, shape[-1], m))
    if name in ("wo", "w_down"):
        return spec(_guard(mesh, shape[-2], m), None)
    if name in ("b_down",):
        return spec(None)
    if name == "router":         # (…, D, E) — replicated (tiny, all-reduce-free)
        return spec(None, None)
    if name in ("dt_bias", "a_log", "d_skip"):
        return spec(_guard(mesh, shape[-1], m))
    # conv weights, norms, biases, everything else: replicated
    return P(*((None,) * nd))


def _path_leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return str(entry.name)
    return ""


def param_pspecs(param_shapes, mesh: Mesh, *, fsdp: bool = False):
    """PartitionSpec tree matching a params (or eval_shape thereof) tree."""

    def rule(path, leaf):
        name = _path_leaf_name(path)
        return _param_rule(name, tuple(leaf.shape), mesh, fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(rule, param_shapes)


# ---------------------------------------------------------------------------
# optimizer state
# ---------------------------------------------------------------------------

def opt_pspecs(opt_shapes, mesh: Mesh, *, fsdp: bool = False):
    """OptState: step replicated; mu/nu follow the param rules (QTensor
    int8 payloads keep the param spec; their 1-D scales are replicated)."""

    def rule(path, leaf):
        name = _path_leaf_name(path)
        nd = len(leaf.shape)
        if name == "step" or nd == 0:
            return P()
        # QTensor fields: path ends (…, 'wq', GetAttr('q'|'scale')).  Both
        # follow the parent param's rule — the int8 payload has the param's
        # shape and the scales are axis-aligned (last dim divided by the
        # quantization block), so leading sharded dims coincide.
        tail = path[-1]
        if isinstance(tail, (jax.tree_util.GetAttrKey,)) and str(
            getattr(tail, "name", "")
        ) in ("q", "scale"):
            name = _path_leaf_name(path[:-1])
        elif isinstance(tail, jax.tree_util.SequenceKey):
            name = _path_leaf_name(path[:-1])
        return _param_rule(name, tuple(leaf.shape), mesh, fsdp=fsdp)

    return jax.tree_util.tree_map_with_path(rule, opt_shapes)


# ---------------------------------------------------------------------------
# batch / cache
# ---------------------------------------------------------------------------

def batch_pspecs(batch_shapes, mesh: Mesh):
    dp = dp_axes(mesh)

    def rule(path, leaf):
        name = _path_leaf_name(path)
        shape = leaf.shape
        if name == "positions" and len(shape) == 3:  # (3, B, S)
            return P(None, _guard(mesh, shape[1], dp), None)
        if len(shape) >= 1:
            b_ax = _guard(mesh, shape[0], dp)
            return P(b_ax, *((None,) * (len(shape) - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(rule, batch_shapes)


def cache_pspecs(cache_shapes, mesh: Mesh):
    """Decode caches. KV: (L, B, Hkv, S, hd) — batch over DP when divisible;
    KV heads over ``model`` when it divides them, which matches the
    column-split ``wq`` (each device attends with its own query heads, no
    exchange); else sequence over ``model`` (SP; partial-softmax decode),
    and over (``data``+``model``) when the batch is too small to use DP
    (long_500k).  SSM state (L, B, H, N, P): heads over ``model``."""
    dp = dp_axes(mesh)

    def rule(path, leaf):
        name = _path_leaf_name(path)
        shape = leaf.shape
        if name in ("k", "v") and len(shape) == 5:
            b_ax = _guard(mesh, shape[1], dp)
            if b_ax is not None and _guard(mesh, shape[2], "model"):
                return P(None, b_ax, "model", None, None)
            seq_axes = "model" if b_ax is not None else tuple(dp) + ("model",)
            return P(None, b_ax, None, _guard(mesh, shape[3], seq_axes), None)
        if name == "ssm" and len(shape) >= 5:
            # (L, [sub,] B, H, N, P): batch over DP, heads over model
            nd = len(shape)
            out = [None] * nd
            h_idx, b_idx = nd - 3, nd - 4
            out[b_idx] = _guard(mesh, shape[b_idx], dp)
            out[h_idx] = _guard(mesh, shape[h_idx], "model")
            return P(*out)
        if name == "conv" and len(shape) >= 3:
            return P(*((None,) * len(shape)))
        return P(*((None,) * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)


def named(mesh: Mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
