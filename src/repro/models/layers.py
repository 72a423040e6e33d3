"""Shared model layers. Every contraction goes through the BLAS seam."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import blas
# Re-export: the TP psum dtype policy lives with the seam now (attention.py
# and ssm.py import it from here).
from repro.core.blas import psum_cast_dtype  # noqa: F401

__all__ = [
    "SCOPES",
    "rms_norm",
    "layer_norm",
    "rope",
    "mrope",
    "mlp_apply",
    "psum_cast_dtype",
    "init_dense",
    "init_norm",
]


# The ``jax.named_scope`` names of a model's parts, the same in the train,
# prefill and decode programs.  They reach each compiled op's
# ``metadata={op_name=...}``, so a profile's ops map to a part by name.
EMBED, NORM, ATTENTION, MLP, HEAD = SCOPES = (
    "embed", "norm", "attention", "mlp", "head")


# ---------------------------------------------------------------------------
# init helpers (pure; callers pass split keys)
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, dtype, *, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def init_norm(d: int, dtype, *, kind: str = "rmsnorm"):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# norms (fp32 internals)
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, p, eps: float) -> jax.Array:
    """RMSNorm through the registered ``rmsnorm_scale`` descriptor — the
    norm epilogue every block pays is scored and traced like any other op
    (host-only: it never wins an offload alone, but the auto policy can now
    see it and the graph frontend captures it)."""
    return blas.rmsnorm_scale(x, p["scale"], eps=eps)


def layer_norm(x: jax.Array, p, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(x, p, eps, kind: str):
    return rms_norm(x, p, eps) if kind == "rmsnorm" else layer_norm(x, p, eps)


# ---------------------------------------------------------------------------
# RoPE (theta may be a traced per-layer scalar — gemma3 local/global)
# ---------------------------------------------------------------------------

def _rope_rotate(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope(x: jax.Array, positions: jax.Array, theta) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32; theta: scalar (may be traced)."""
    d = x.shape[-1]
    half = d // 2
    theta = jnp.asarray(theta, jnp.float32)
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (B, S, half)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    return _rope_rotate(x.astype(jnp.float32), sin, cos).astype(x.dtype)


def mrope(x: jax.Array, positions: jax.Array, theta, sections=(2, 3, 3)) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions: (3, B, S) — temporal / height / width position streams.  The
    rotary half-dim is split into ``sections`` (2:3:3 of every 8 dims, per
    the paper), each rotated by its own stream.  Text tokens carry identical
    streams, reducing to standard RoPE.
    """
    d = x.shape[-1]
    half = d // 2
    theta = jnp.asarray(theta, jnp.float32)
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    total = sum(sections)
    # Partition the frequency axis into contiguous section bands.
    bounds = []
    start = 0
    for s in sections:
        size = (half * s) // total
        bounds.append((start, start + size))
        start += size
    bounds[-1] = (bounds[-1][0], half)
    ang_parts = []
    for (lo, hi), stream in zip(bounds, range(3)):
        pos = positions[stream].astype(jnp.float32)[..., None]     # (B, S, 1)
        ang_parts.append(pos * inv_freq[lo:hi])
    ang = jnp.concatenate(ang_parts, axis=-1)                      # (B, S, half)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    return _rope_rotate(x.astype(jnp.float32), sin, cos).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU) — dense FFN through the BLAS seam
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, dtype, kind: str):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "w_gate": init_dense(ks[0], d, d_ff, dtype),
            "w_up": init_dense(ks[1], d, d_ff, dtype),
            "w_down": init_dense(ks[2], d_ff, d, dtype),
        }
    return {
        "w_up": init_dense(ks[0], d, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": init_dense(ks[1], d_ff, d, dtype),
        "b_down": jnp.zeros((d,), dtype),
    }


def mlp_apply(p, x: jax.Array, kind: str) -> jax.Array:
    """Dense FFN through the registered ``mlp_block`` descriptor.

    Previously this hand-rolled the whole-block TP shard_map (raw
    ``lax.dot_general`` launch sites bypassing the seam) plus a bare
    ``engine().launch`` for the cost.  The descriptor now owns all of it:
    TP applicability is its ``plan``, the dense form its host lowering, the
    hand-tiled MXU GEMMs its Pallas lowering — one dispatch, one record,
    placement always threaded."""
    if kind == "swiglu":
        return blas.mlp_block(
            x, p["w_up"], p["w_down"], gate=p["w_gate"], kind="swiglu"
        )
    return blas.mlp_block(
        x, p["w_up"], p["w_down"], b_up=p["b_up"], b_down=p["b_down"],
        kind="gelu",
    )
