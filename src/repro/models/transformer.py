"""Stack assembly: pre-norm residual blocks, scanned over layers.

Two stack layouts:

* **uniform** — every layer has the same pytree structure: params are stacked
  on a leading (L, …) axis and applied with one ``lax.scan``.  Per-layer
  *data* (attention window, RoPE theta) rides along as scanned arrays, which
  is how gemma3's 5:1 local:global pattern and danube's SWA share one code
  path (the window is a traced scalar inside the scan body).
* **hybrid (jamba)** — layers repeat with period P (= 8): one scan over
  L/P super-blocks, the P sub-layers unrolled inside the body (attn at
  ``attn_layer_offset``, Mamba elsewhere; MoE FFN every
  ``moe_layer_period``-th sub-layer).

``lax.scan`` keeps the HLO O(1) in depth — essential for compiling 80-layer
models on the dry-run host — and ``jax.checkpoint`` on the body gives
per-layer remat (saved residuals = layer inputs only).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, GLOBAL_WINDOW
from repro.core import accounting
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

__all__ = [
    "init_stack",
    "apply_stack",
    "init_decode_cache",
    "decode_stack",
]


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def _norm(x, p, cfg: ArchConfig):
    with jax.named_scope(L.NORM):
        return L.apply_norm(x, p, cfg.norm_eps, cfg.norm_kind)


def _init_block(key, cfg: ArchConfig, kind: str, is_moe: bool, dtype):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg.d_model, dtype, kind=cfg.norm_kind)}
    if kind == "attn":
        p["mixer"] = A.init_attention(ks[0], cfg, dtype)
    else:
        p["mixer"] = S.init_mamba(ks[0], cfg, dtype)
    if cfg.family != "ssm":
        p["norm2"] = L.init_norm(cfg.d_model, dtype, kind=cfg.norm_kind)
        if is_moe:
            p["ffn"] = M.init_moe(ks[1], cfg, dtype)
        else:
            p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype, cfg.mlp_kind)
    return p


def _apply_block(
    p,
    x,
    cfg: ArchConfig,
    kind: str,
    is_moe: bool,
    *,
    positions,
    window=None,
    rope_theta=None,
):
    if cfg.forward_mode == "graph":
        # Whole-block graph capture: the hnp scheduler fuses elementwise
        # epilogues, batches independent projections and threads residency
        # across the block (models/forward.py).  Same descriptors, same math.
        from repro.models import forward as F

        return F.graph_block(
            p, x, cfg, kind, is_moe,
            positions=positions, window=window, rope_theta=rope_theta,
        )
    h = _norm(x, p["norm1"], cfg)
    if kind == "attn":
        with jax.named_scope(L.ATTENTION):
            x = x + A.attention_block(
                p["mixer"], h, cfg, positions=positions, window=window,
                rope_theta=rope_theta,
            )
    else:
        x = x + S.mamba_block(p["mixer"], h, cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family != "ssm":
        h = _norm(x, p["norm2"], cfg)
        with jax.named_scope(L.MLP):
            if is_moe:
                f, aux = M.moe_ffn(p["ffn"], h, cfg)
            else:
                f = L.mlp_apply(p["ffn"], h, cfg.mlp_kind)
            x = x + f
    return x, aux


# ---------------------------------------------------------------------------
# per-layer static data (windows / rope thetas) as scan arrays
# ---------------------------------------------------------------------------

def _layer_data(cfg: ArchConfig, seq_len: int):
    windows = np.array(
        [min(cfg.layer_window(i, seq_len), GLOBAL_WINDOW) for i in range(cfg.num_layers)],
        np.int32,
    )
    thetas = np.array(
        [cfg.layer_rope_theta(i) for i in range(cfg.num_layers)], np.float32
    )
    return jnp.asarray(windows), jnp.asarray(thetas)


def _uniform_window_static(cfg: ArchConfig) -> Optional[int]:
    """If all layers share one window, return it (enables the Pallas path)."""
    ws = {cfg.layer_window(i, 0) for i in range(cfg.num_layers)}
    if len(ws) == 1:
        w = ws.pop()
        return None if w >= GLOBAL_WINDOW else int(w)
    return None


# ---------------------------------------------------------------------------
# stack init
# ---------------------------------------------------------------------------

def init_stack(key, cfg: ArchConfig, dtype):
    if cfg.uniform_stack:
        kind = cfg.layer_kind(0)
        is_moe = cfg.layer_is_moe(0)
        keys = jax.random.split(key, cfg.num_layers)
        return jax.vmap(
            lambda k: _init_block(k, cfg, kind, is_moe, dtype)
        )(keys)
    # hybrid: stack super-blocks
    period = cfg.attn_layer_period or cfg.moe_layer_period
    n_sb = cfg.num_layers // period
    keys = jax.random.split(key, n_sb)

    def init_sb(k):
        sub_keys = jax.random.split(k, period)
        return {
            f"sub{j}": _init_block(
                sub_keys[j], cfg, cfg.layer_kind(j), cfg.layer_is_moe(j), dtype
            )
            for j in range(period)
        }

    return jax.vmap(init_sb)(keys)


# ---------------------------------------------------------------------------
# stack apply (training / prefill)
# ---------------------------------------------------------------------------

def apply_stack(params, x, cfg: ArchConfig, *, positions):
    seq_len = x.shape[1]
    if cfg.uniform_stack:
        kind = cfg.layer_kind(0)
        is_moe = cfg.layer_is_moe(0)
        windows, thetas = _layer_data(cfg, seq_len)

        def body(carry, xs):
            h, aux = carry
            lp, w, th = xs
            with accounting.scaled(cfg.num_layers):  # scan body runs L times
                h, a = _apply_block(
                    lp, h, cfg, kind, is_moe,
                    positions=positions, window=w, rope_theta=th,
                )
            return (h, aux + a), None

        if cfg.remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params, windows, thetas))
        return x, aux

    period = cfg.attn_layer_period or cfg.moe_layer_period

    def body(carry, lp):
        h, aux = carry
        with accounting.scaled(cfg.num_layers // period):
            for j in range(period):
                h, a = _apply_block(
                    lp[f"sub{j}"], h, cfg, cfg.layer_kind(j), cfg.layer_is_moe(j),
                    positions=positions, window=None, rope_theta=cfg.rope_theta,
                )
                aux = aux + a
        return (h, aux), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params)
    return x, aux


# ---------------------------------------------------------------------------
# decode cache + one-token decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype):
    """Cache pytree with a leading stacked-layer axis (scanned with params)."""
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.uniform_stack:
        n = cfg.num_layers
        if cfg.family == "ssm":
            ssm_shape, conv_shape = S.mamba_state_shapes(cfg, batch)
            return {
                "ssm": jnp.zeros((n, *ssm_shape), jnp.float32),
                "conv": jnp.zeros((n, *conv_shape), dtype),
            }
        eff = cache_len
        if cfg.sliding_window:
            eff = min(cache_len, cfg.sliding_window)  # rolling SWA buffer
        return {
            "k": jnp.zeros((n, batch, hkv, eff, hd), dtype),
            "v": jnp.zeros((n, batch, hkv, eff, hd), dtype),
        }
    # hybrid (jamba): one attn + (period-1) mamba sub-layers per super-block
    period = cfg.attn_layer_period
    n_sb = cfg.num_layers // period
    ssm_shape, conv_shape = S.mamba_state_shapes(cfg, batch)
    return {
        "k": jnp.zeros((n_sb, batch, hkv, cache_len, hd), dtype),
        "v": jnp.zeros((n_sb, batch, hkv, cache_len, hd), dtype),
        "ssm": jnp.zeros((n_sb, period - 1, *ssm_shape), jnp.float32),
        "conv": jnp.zeros((n_sb, period - 1, *conv_shape), dtype),
    }


def _decode_block(p, x, cache_slices, cache_index, cfg, kind, *, window, rope_theta):
    """One layer of single-token decode. Returns (x, new_cache_slices)."""
    h = _norm(x, p["norm1"], cfg)
    if kind == "attn":
        with jax.named_scope(L.ATTENTION):
            mix, (k_new, v_new) = A.decode_attention_block(
                p["mixer"], h, (cache_slices["k"], cache_slices["v"]),
                cache_index, cfg, window=window, rope_theta=rope_theta,
            )
            x = x + mix
        new_cache = {"k": k_new, "v": v_new}
    else:
        mix, (ssm_new, conv_new) = S.decode_mamba_block(
            p["mixer"], h, (cache_slices["ssm"], cache_slices["conv"]), cfg
        )
        x = x + mix
        new_cache = {"ssm": ssm_new, "conv": conv_new}
    if cfg.family != "ssm" and "ffn" in p:
        h = _norm(x, p["norm2"], cfg)
        with jax.named_scope(L.MLP):
            if cfg.layer_is_moe(0) and cfg.uniform_stack:
                x = x + M.moe_ffn(p["ffn"], h, cfg)[0]
            elif cfg.forward_mode == "graph":
                # Decode's graph half: mixers mutate caches eagerly, the
                # dense FFN is captured (residual fused into its launch).
                from repro.models import forward as F

                x = F.graph_ffn(p["ffn"], h, cfg, residual=x)
            else:
                x = x + L.mlp_apply(p["ffn"], h, cfg.mlp_kind)
    return x, new_cache


def decode_stack(params, cache, x, cache_index, cfg: ArchConfig):
    """x: (B, 1, D). Scans layers, threading per-layer cache slices."""
    if cfg.uniform_stack:
        kind = cfg.layer_kind(0)
        windows, thetas = _layer_data(cfg, 0)

        def body(carry, xs):
            h = carry
            lp, csl, w, th = xs
            with accounting.scaled(cfg.num_layers):
                h, new_c = _decode_block(
                    lp, h, csl, cache_index, cfg, kind, window=w, rope_theta=th
                )
            return h, new_c

        x, new_cache = jax.lax.scan(body, x, (params, cache, windows, thetas))
        return x, new_cache

    period = cfg.attn_layer_period

    def body(carry, xs):
        h = carry
        lp, csl = xs
        new_c = dict(csl)
        mi = 0
        _scale = accounting.scaled(cfg.num_layers // period)
        _scale.__enter__()
        for j in range(period):
            kind = cfg.layer_kind(j)
            sub = lp[f"sub{j}"]
            hh = _norm(h, sub["norm1"], cfg)
            if kind == "attn":
                with jax.named_scope(L.ATTENTION):
                    mix, (kn, vn) = A.decode_attention_block(
                        sub["mixer"], hh, (csl["k"], csl["v"]), cache_index,
                        cfg, rope_theta=cfg.rope_theta,
                    )
                    h = h + mix
                new_c["k"], new_c["v"] = kn, vn
            else:
                mix, (sn, cn) = S.decode_mamba_block(
                    sub["mixer"], hh,
                    (csl["ssm"][mi], csl["conv"][mi]), cfg,
                )
                new_c["ssm"] = new_c["ssm"].at[mi].set(sn)
                new_c["conv"] = new_c["conv"].at[mi].set(cn)
                mi += 1
                h = h + mix
            hh = _norm(h, sub["norm2"], cfg)
            with jax.named_scope(L.MLP):
                if cfg.layer_is_moe(j):
                    f, _ = M.moe_ffn(sub["ffn"], hh, cfg)
                else:
                    f = L.mlp_apply(sub["ffn"], hh, cfg.mlp_kind)
                h = h + f
        _scale.__exit__(None, None, None)
        return h, new_c

    x, new_cache = jax.lax.scan(body, x, (params, cache))
    return x, new_cache
