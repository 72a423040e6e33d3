"""Model facade: embeddings + stack + head, losses, decode, input specs.

``build_model(cfg)`` returns a :class:`Model` whose methods are pure
functions of (params, batch) — ready for ``jax.jit``/``pjit`` in the launch
layer.  Input specs are ``ShapeDtypeStruct``s so the multi-pod dry-run can
lower every (arch × shape) cell without allocating anything.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import blas
from repro.models import layers as L
from repro.models import transformer as T

__all__ = ["Model", "build_model", "cross_entropy"]

AUX_LOSS_WEIGHT = 0.01


def _dtype_of(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token CE in fp32. logits: (B, S, V); labels: (B, S) int32.

    The label log-prob is picked with an iota==label mask-and-sum rather
    than take_along_axis: a gather along a vocab-sharded axis forces GSPMD
    to all-gather the logits, while the masked sum partitions cleanly
    (elementwise + reduce with a psum over the model axis)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    v = lf.shape[-1]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    picked = jnp.where(vocab_iota == labels[..., None], lf, 0.0)
    ll = jnp.sum(picked, axis=-1)
    return jnp.mean(lse - ll)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ---- params -----------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = _dtype_of(cfg)
        k_embed, k_stack, k_head = jax.random.split(rng, 3)
        params: Dict[str, Any] = {
            "stack": T.init_stack(k_stack, cfg, dtype),
            "final_norm": L.init_norm(cfg.d_model, dtype, kind=cfg.norm_kind),
        }
        if cfg.embed_inputs:
            params["embed"] = (
                jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model), jnp.float32)
                * cfg.d_model ** -0.5
            ).astype(dtype)
        if not (cfg.tie_embeddings and cfg.embed_inputs):
            params["head"] = L.init_dense(
                k_head, cfg.d_model, cfg.vocab_size, dtype
            )
        return params

    def param_specs(self, rng: jax.Array):
        return jax.eval_shape(self.init_params, rng)

    # ---- forward ------------------------------------------------------------
    def _embed(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.embed_inputs:
            with jax.named_scope(L.EMBED):
                x = jnp.take(params["embed"], batch["tokens"], axis=0)
            bsz, s = batch["tokens"].shape
        else:
            x = batch["embeds"]
            bsz, s = x.shape[0], x.shape[1]
        if "positions" in batch:
            positions = batch["positions"]
        else:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (bsz, s)
            )
        return x, positions

    @jax.named_scope(L.HEAD)
    def _head(self, params, x) -> jax.Array:
        cfg = self.cfg
        x = L.apply_norm(x, params["final_norm"], cfg.norm_eps, cfg.norm_kind)
        if cfg.tie_embeddings and cfg.embed_inputs:
            return blas.matmul(x, params["embed"].T)
        return blas.matmul(x, params["head"])

    def forward(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """(logits (B, S, V), aux_loss) — training / prefill."""
        x, positions = self._embed(params, batch)
        x, aux = T.apply_stack(params["stack"], x, self.cfg, positions=positions)
        return self._head(params, x), aux

    def loss(self, params, batch) -> jax.Array:
        logits, aux = self.forward(params, batch)
        return cross_entropy(logits, batch["labels"]) + AUX_LOSS_WEIGHT * aux

    # ---- decode --------------------------------------------------------------
    def init_decode_cache(self, batch_size: int, cache_len: int):
        return T.init_decode_cache(
            self.cfg, batch_size, cache_len, _dtype_of(self.cfg)
        )

    def decode_step(self, params, cache, tokens, cache_index):
        """One token: tokens (B, 1) int32 (or embeds (B, 1, D) for stub
        frontends); cache_index scalar int32. Returns (logits (B, V), cache)."""
        cfg = self.cfg
        if cfg.embed_inputs:
            with jax.named_scope(L.EMBED):
                x = jnp.take(params["embed"], tokens, axis=0)
        else:
            x = tokens  # already embedded (B, 1, D)
        x, new_cache = T.decode_stack(params["stack"], cache, x, cache_index, cfg)
        logits = self._head(params, x)
        return logits[:, 0, :], new_cache

    # ---- dry-run input specs ---------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = _dtype_of(cfg)
        if shape.kind in ("train", "prefill"):
            specs: Dict[str, Any] = {}
            if cfg.embed_inputs:
                specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
            else:
                specs["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)
            if cfg.mrope:
                specs["positions"] = jax.ShapeDtypeStruct((3, b, s), i32)
            if shape.kind == "train":
                specs["labels"] = jax.ShapeDtypeStruct((b, s), i32)
            return specs
        # decode: one new token against a cache of length s
        if cfg.embed_inputs:
            tok = jax.ShapeDtypeStruct((b, 1), i32)
        else:
            tok = jax.ShapeDtypeStruct((b, 1, cfg.d_model), dt)
        cache = jax.eval_shape(lambda: self.init_decode_cache(b, s))
        return {
            "tokens": tok,
            "cache": cache,
            "cache_index": jax.ShapeDtypeStruct((), i32),
        }


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
