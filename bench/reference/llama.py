"""Plain float32 forward pass of a Llama-style decoder with grouped-query
attention, for checking served tokens.

It follows the published description of the architecture (Touvron et al.,
arXiv:2302.13971; Yi: arXiv:2403.04652): token embedding; per layer an
RMSNorm, rotary embedding on queries and keys (rotate-half form, base
``rope_theta``), causal softmax attention with ``num_key_value_heads``
shared key/value heads scaled by ``head_dim ** -0.5``, an output
projection and a residual, then an RMSNorm and a SwiGLU feed-forward
``(silu(x Wg) * (x Wu)) Wd`` and a residual; a final RMSNorm and an untied
output head.  No kernel, cache or batching trick: the whole sequence is one
dense causal pass.  It imports nothing of the system under test.

Weights are read in the layout the benchmark builds them in (stacked over
layers).  Each layer's weights are upcast to float32 inside a scan over
layers, so only one layer at a time is held in float32 beside the bf16
weights, and rows are run in blocks.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 (one scale per tensor, from its largest magnitude),
with float32 accumulation.  It stands for a program that computed in the
next precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3fn


class Spec(NamedTuple):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float


def spec_of(config: dict) -> Spec:
    return Spec(
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
    )


def _round(a, precision: str):
    if precision == "f32":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, T, H, D); rotate-half rotary embedding at positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("spec", "first", "precision"))
def _logits(w, tokens, *, spec: Spec, first: int, precision: str):
    b, t = tokens.shape
    hq, hkv, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    f32 = jnp.float32
    x = jnp.take(w["embed"], tokens, axis=0).astype(f32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(f32), lw)
        h = _rms(x, lw["norm1"]["scale"], spec.eps)
        q = _mm("btd,dk->btk", h, lw["mixer"]["wq"], precision).reshape(b, t, hq, hd)
        k = _mm("btd,dk->btk", h, lw["mixer"]["wk"], precision).reshape(b, t, hkv, hd)
        v = _mm("btd,dk->btk", h, lw["mixer"]["wv"], precision).reshape(b, t, hkv, hd)
        q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, precision).reshape(b, t, hq * hd)
        x = x + _mm("btk,kd->btd", o, lw["mixer"]["wo"], precision)
        h = _rms(x, lw["norm2"]["scale"], spec.eps)
        g = _mm("btd,df->btf", h, lw["ffn"]["w_gate"], precision)
        u = _mm("btd,df->btf", h, lw["ffn"]["w_up"], precision)
        x = x + _mm("btf,fd->btd", jax.nn.silu(g) * u, lw["ffn"]["w_down"], precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["stack"])
    x = _rms(x[:, first:], w["final_norm"]["scale"].astype(f32), spec.eps)
    return _mm("btd,dv->btv", x, w["head"].astype(f32), precision)


def logits(w, tokens: np.ndarray, spec: Spec, *, first: int, rows: int,
           precision: str = "f32"):
    """Float32 logits ``(S, T - first, V)`` of every position from ``first``
    on, for token rows ``tokens`` ``(S, T)``, run ``rows`` rows at a time."""
    out = []
    for i in range(0, len(tokens), rows):
        block = jnp.asarray(tokens[i:i + rows], jnp.int32)
        out.append(_logits(w, block, spec=spec, first=first, precision=precision))
    return jnp.concatenate(out, axis=0)
