"""Public jit'd wrappers for the Pallas device kernels.

This is the device half of the BLAS seam: ``repro.core.blas`` routes here
when the offload policy selects the Pallas backend.  On a TPU the calls
lower through Mosaic; the CPU tests run the same calls with
``interpret=True``.  The `interpret` flag is plumbed, never hard-coded.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.gemm import DEFAULT_BLOCK, pallas_gemm, pallas_gemm_batched
from repro.kernels.ssd_scan import ssd_chunk_diag as _ssd_chunk

__all__ = [
    "PALLAS_LOWERINGS",
    "gemm",
    "gemm_batched",
    "moe_gemm",
    "flash_attention",
    "flash_decode",
    "ssd_chunk_diag",
    "pallas_lowering",
]


def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    block: Tuple[int, int, int] = DEFAULT_BLOCK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    return pallas_gemm(a, b, block=block, out_dtype=out_dtype, interpret=interpret)


def gemm_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    block: Tuple[int, int, int] = DEFAULT_BLOCK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    return pallas_gemm_batched(
        a, b, block=block, out_dtype=out_dtype, interpret=interpret
    )


def moe_gemm(
    x: jax.Array,
    w: jax.Array,
    *,
    block: Tuple[int, int, int] = DEFAULT_BLOCK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Capacity-grouped expert GEMM: (E, C, d) @ (E, d, f) -> (E, C, f).

    Experts form the outermost *parallel* grid dimension, so each expert's
    tile stream is an independent GEMM pipeline (megablox-style layout with
    a static per-expert capacity, which keeps every tile MXU-dense)."""
    return pallas_gemm_batched(
        x, w, block=block, out_dtype=out_dtype, interpret=interpret
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    return _flash(
        q,
        k,
        v,
        causal=causal,
        window=window,
        sm_scale=sm_scale,
        block_q=block_q,
        block_kv=block_kv,
        interpret=interpret,
    )


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    sm_scale: Optional[float] = None,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention with per-batch valid-slot bounds.

    The TPU serving path calls this directly (the pjit'd serve_step uses
    the shardable masked-math fallback — the dry-run proves that form;
    this kernel is its device-optimal equivalent, one HBM pass over KV)."""
    return _flash_decode(
        q, k, v, lo, hi, sm_scale=sm_scale, block_kv=block_kv,
        interpret=interpret,
    )


def ssd_chunk_diag(
    x: jax.Array,
    dt_a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    return _ssd_chunk(x, dt_a, b, c, interpret=interpret)


# ---------------------------------------------------------------------------
# Lowering table: op name -> Pallas kernel entry point.
#
# The device half of the declarative registry (``repro.core.dispatch``): an
# :class:`OffloadOp` descriptor's ``pallas`` adapter fetches its kernel here
# by name, so the op table and the kernel table stay in one-to-one view and
# a new device kernel is wired up by adding one row.
# ---------------------------------------------------------------------------

PALLAS_LOWERINGS = {
    "gemm": gemm,
    "matmul": gemm,                  # leading dims collapse to GEMM m
    "gemm_batched": gemm_batched,
    "moe_gemm": moe_gemm,
    "attention": flash_attention,
    "decode_attention": flash_decode,
    "ssd_chunk_diag": ssd_chunk_diag,
    # Composite model-zoo descriptors: each fetches its core kernel here by
    # its own name, keeping the op registry and this table in one-to-one
    # view (the glue — bias adds, silu, the inter-chunk scan — lives in the
    # descriptor's pallas adapter in repro.core.blas).
    "qkv_project": gemm,             # concatenated-weight projection GEMM
    "ssd_scan": ssd_chunk_diag,      # within-chunk quadratic term
    "moe_expert_ffn": moe_gemm,      # gate/up/down grouped expert GEMMs
}


def pallas_lowering(name: str):
    try:
        return PALLAS_LOWERINGS[name]
    except KeyError:
        raise KeyError(
            f"no Pallas lowering for op {name!r}; have {sorted(PALLAS_LOWERINGS)}"
        ) from None
