"""Plain float32 forward pass of a Qwen2 decoder, for checking served tokens.

It follows the published description (Qwen2 Technical Report,
arXiv:2407.10671; Hugging Face ``Qwen/Qwen2-72B``): a Llama-style layer
(``llama.py``) whose query, key and value projections carry a bias, added
before the rotary embedding; the output projection has none.  Token
embedding; per layer an RMSNorm, ``q = h Wq + bq``, ``k = h Wk + bk``,
``v = h Wv + bv``, rotary embedding on queries and keys (rotate-half form,
base ``rope_theta``), causal softmax attention with ``num_key_value_heads``
shared key/value heads scaled by ``head_dim ** -0.5``, an output projection
and a residual, then an RMSNorm, a SwiGLU feed-forward and a residual; a
final RMSNorm and an untied output head.  It imports nothing of the system
under test.

Departures from the paper: none in the layer.  The published model's
sliding window is off (``use_sliding_window`` false in ``config.json``) and
its long-context rope scaling (YARN, DCA) applies only past 32k positions,
so neither appears at the lengths checked here.

Every matrix product runs at ``Precision.HIGHEST`` on float32 operands, the
whole call under ``jax.default_matmul_precision("highest")``.  Weights are
read as the benchmark builds them (stacked over layers, possibly sharded
over several devices: the compiler splits the plain math, this file holds no
sharding logic); each layer is upcast to float32 inside a scan over layers,
so one layer at a time is held in float32, and rows are run in blocks.

``precision="fp8"`` is the control of ``llama.py``: every matrix product
takes operands rounded to float8 e4m3, biases added in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.llama import Spec, _mm, _rms, _rope, spec_of

__all__ = ["Spec", "spec_of", "logits"]


@functools.partial(jax.jit, static_argnames=("spec", "first", "precision"))
def _logits(w, tokens, *, spec: Spec, first: int, precision: str):
    b, t = tokens.shape
    hq, hkv, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    f32 = jnp.float32
    x = jnp.take(w["embed"], tokens, axis=0).astype(f32)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lw):
        lw = jax.tree_util.tree_map(lambda a: a.astype(f32), lw)
        m = lw["mixer"]
        h = _rms(x, lw["norm1"]["scale"], spec.eps)
        q = (_mm("btd,dk->btk", h, m["wq"], precision) + m["bq"]).reshape(b, t, hq, hd)
        k = (_mm("btd,dk->btk", h, m["wk"], precision) + m["bk"]).reshape(b, t, hkv, hd)
        v = (_mm("btd,dk->btk", h, m["wv"], precision) + m["bv"]).reshape(b, t, hkv, hd)
        q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
        s = _mm("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, precision).reshape(b, t, hq * hd)
        x = x + _mm("btk,kd->btd", o, m["wo"], precision)
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
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(tokens), rows):
            block = jnp.asarray(tokens[i:i + rows], jnp.int32)
            out.append(_logits(w, block, spec=spec, first=first,
                               precision=precision))
    return jnp.concatenate(out, axis=0)
