"""The BLAS seam — OpenBLAS analogue (paper Fig. 2, box 3), as a declarative
op registry.

One stable linear-algebra API that *all* higher layers call instead of raw
``jnp`` contractions.  Every op here is an :class:`~repro.core.dispatch.
OffloadOp` descriptor — its cost function, Pallas-eligibility predicate,
host (XLA) lowering and Pallas lowering — registered with
:mod:`repro.core.dispatch` at import time.  The public functions are thin
wrappers over the single :func:`~repro.core.dispatch.dispatch` path, which
scores the call, resolves routing (explicit-TP plan -> Pallas -> host),
threads the chosen ``device_id`` into the trace record, and runs the
winning lowering — exactly the role OpenBLAS plays in the paper, with the
OpenMP ``#pragma omp target`` replaced by the dispatch engine.

Extending the seam is declarative: write the lowerings, build an
``OffloadOp``, ``register`` it (and add the kernel to
``repro.kernels.ops.PALLAS_LOWERINGS`` if it has a device form).  No new
dispatch code — the cost -> plan -> launch -> lower ritual exists once, in
``core/dispatch.py``, so placement, accounting, and scheduling behave
identically for every op.

Host path    : ``lax.dot_general`` (XLA default lowering — the "rv64g host
               kernel").
Device path  : same graph, but accounted as an offload with the three-region
               breakdown (on a real TPU, host/device is a residency and
               lowering distinction, not a different chip).
Pallas path  : hand-tiled MXU kernels from ``repro.kernels`` (the "rv32 PMCA
               kernel"), selected when the policy enables them and the shape
               is tile-eligible.

``syrk`` is host-only (``host_only=True`` on its descriptor), mirroring the
paper compiling ``syrk.c`` only for the host.  Callers holding a
:class:`~repro.core.hero.DeviceHandle` (pinned KV cache, resident weights)
pass ``handle=`` to any op so schedulers route the work to the data.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import cost_model as cm
from repro.core.dispatch import OffloadOp, dispatch, dispatch_placed, register
from repro.core.hero import DeviceHandle, engine  # noqa: F401 (re-export seam)

__all__ = [
    "gemm",
    "matmul",
    "gemm_batched",
    "linear",
    "mlp_block",
    "qkv_project",
    "ssd_scan",
    "moe_expert_ffn",
    "moe_expert_ffn_placed",
    "expert_matmul",
    "attention",
    "attention_math",
    "decode_attention",
    "psum_cast_dtype",
    "syrk",
    "gemv",
    "dot",
    "axpy",
    "scal",
    "nrm2",
    "reduce_sum",
    "reduce_mean",
    "relu",
    "silu",
    "rmsnorm_scale",
]

# Ops never offloaded to the Pallas path (paper keeps syrk host-only).
HOST_ONLY_OPS = frozenset({"syrk"})

_MXU_ALIGN = 128
# Host attention: direct masked einsum up to this kv length, chunked
# online-softmax scan beyond it (keeps memory linear in Skv).
_DIRECT_ATTN_MAX_KV = 8192
_CHUNKED_ATTN_BLOCK = 1024


def _kops():
    from repro.kernels import ops as kops  # lazy: avoid import cycle

    return kops


def _pallas_gemm_eligible(m: int, n: int, k: int, dtype) -> bool:
    """Tile-eligibility for the hand-written MXU GEMM kernel."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    # The kernel pads internally; require dims large enough that a 128-tile
    # working set is meaningful (analogue of "fits the SPM blocking").
    return min(m, n, k) >= 8


def _accum_dot(a, b, dimension_numbers, out_dtype):
    """Contraction with fp32 accumulation (MXU semantics)."""
    acc = lax.dot_general(
        a,
        b,
        dimension_numbers=dimension_numbers,
        preferred_element_type=jnp.float32,
    )
    return acc.astype(out_dtype)


# ---------------------------------------------------------------------------
# Level-3 descriptors
# ---------------------------------------------------------------------------

def _gemm_dims(a, b, transpose_a, transpose_b):
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"gemm takes 2-D operands, got {a.shape} @ {b.shape}")
    m, k = (a.shape[1], a.shape[0]) if transpose_a else a.shape
    kb, n = (b.shape[1], b.shape[0]) if transpose_b else b.shape
    if k != kb:
        raise ValueError(f"gemm contraction mismatch: {a.shape} @ {b.shape}")
    return m, n, k


def _gemm_cost(a, b, *, transpose_a=False, transpose_b=False, out_dtype=None):
    m, n, k = _gemm_dims(a, b, transpose_a, transpose_b)
    return cm.gemm_cost(m, n, k, jnp.dtype(a.dtype).itemsize)


def _gemm_eligible(a, b, *, transpose_a=False, transpose_b=False, out_dtype=None):
    m, n, k = _gemm_dims(a, b, transpose_a, transpose_b)
    return _pallas_gemm_eligible(m, n, k, a.dtype)


def _gemm_host(a, b, *, transpose_a=False, transpose_b=False, out_dtype=None):
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    ca = ((0,) if transpose_a else (1,), (1,) if transpose_b else (0,))
    return _accum_dot(a, b, (ca, ((), ())), out_dtype)


def _gemm_pallas(
    a, b, *, transpose_a=False, transpose_b=False, out_dtype=None,
    interpret=False,
):
    aa = a.T if transpose_a else a
    bb = b.T if transpose_b else b
    return _kops().pallas_lowering("gemm")(
        aa, bb,
        out_dtype=out_dtype or jnp.result_type(a.dtype, b.dtype),
        interpret=interpret,
    )


register(OffloadOp(
    name="gemm",
    cost=_gemm_cost,
    host=_gemm_host,
    pallas=_gemm_pallas,
    eligible=_gemm_eligible,
))


def _tp_mesh_info():
    """Ambient model-parallel topology, or None when no TP plan can apply.

    Returns ``(mesh, n_model, dp_axes, n_dp)`` — the shared applicability
    prologue of every descriptor's TP ``plan`` (pure inspection, safe at
    trace time).  A single-device model axis counts as "no topology".
    """
    from repro.sharding.annotate import _ambient_mesh

    mesh = _ambient_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return None
    n_model = mesh.shape["model"]
    if n_model <= 1:
        return None
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    import numpy as _np

    n_dp = int(_np.prod([mesh.shape[a] for a in dp])) if dp else 1
    return mesh, n_model, dp, n_dp


def _tp_plan(x, w, mode: str):
    """Check whether the explicit-TP shard_map path applies.

    Returns ``(mesh, dp_axes)`` when it does, else None.  Pure inspection —
    no execution — so the dispatcher can resolve routing *before* recording
    a backend (the trace must name the path that actually ran).
    """
    if mode not in ("row", "col"):
        return None
    info = _tp_mesh_info()
    if info is None or x.ndim != 3:
        return None
    mesh, n_model, dp, n_dp = info
    if x.shape[0] % n_dp:
        return None
    if x.shape[-1] != w.shape[0]:
        return None
    if mode == "row" and w.shape[0] % n_model:
        return None
    if mode == "col" and w.shape[1] % n_model:
        return None
    return mesh, dp


def _tp_shard_map_matmul(x, w, mode: str, out_dtype, plan):
    """Explicit tensor-parallel matmul with bf16 cross-device reductions.

    GSPMD places the TP all-reduce on the fp32 dot product (before the bf16
    cast), doubling wire bytes.  Under shard_map the seam does: local matmul
    with fp32 accumulation -> cast -> psum in the output dtype.  ``row``:
    w's first (contracting) dim is model-sharded, psum in forward; ``col``:
    w's last dim is model-sharded, the (autodiff-generated) psum of dX in
    backward is bf16 for free because the local primal is already cast.
    ``plan`` comes from :func:`_tp_plan`.
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh, dp = plan
    out_dtype = out_dtype or jnp.result_type(x.dtype, w.dtype)
    if mode == "row":

        def local(xl, wl):
            y = lax.dot_general(
                xl, wl, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(out_dtype)
            return lax.psum(y, "model")

        return shard_map(
            local,
            mesh=mesh,
            in_specs=(P(dp, None, "model"), P("model", None)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(x, w)
    # col: output dim sharded; bwd dX psum happens in out_dtype

    def local_col(xl, wl):
        return lax.dot_general(
            xl, wl, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_dtype)

    return shard_map(
        local_col,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(None, "model")),
        out_specs=P(dp, None, "model"),
        check_vma=False,
    )(x, w)


def _matmul_dims(x, w):
    if w.ndim != 2:
        raise ValueError(f"matmul expects 2-D rhs, got {w.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"matmul contraction mismatch: {x.shape} @ {w.shape}")
    m = 1
    for d in x.shape[:-1]:
        m *= d
    k, n = w.shape
    return m, k, n


def _matmul_cost(x, w, *, out_dtype=None, tp_mode=None):
    m, k, n = _matmul_dims(x, w)
    return cm.gemm_cost(m, n, k, jnp.dtype(x.dtype).itemsize)


def _matmul_plan(x, w, *, out_dtype=None, tp_mode=None):
    # A tensor-parallel matmul runs the shard_map XLA path, so routing must
    # resolve before the record is written (no phantom Pallas launches).
    return _tp_plan(x, w, tp_mode) if tp_mode in ("row", "col") else None


def _matmul_plan_lower(plan, x, w, *, out_dtype=None, tp_mode=None):
    return _tp_shard_map_matmul(x, w, tp_mode, out_dtype, plan)


def _matmul_eligible(x, w, *, out_dtype=None, tp_mode=None):
    m, k, n = _matmul_dims(x, w)
    return _pallas_gemm_eligible(m, n, k, x.dtype)


def _matmul_host(x, w, *, out_dtype=None, tp_mode=None):
    out_dtype = out_dtype or jnp.result_type(x.dtype, w.dtype)
    return _accum_dot(x, w, (((x.ndim - 1,), (0,)), ((), ())), out_dtype)


def _matmul_pallas(x, w, *, out_dtype=None, tp_mode=None, interpret=False):
    m, k, n = _matmul_dims(x, w)
    out = _kops().pallas_lowering("matmul")(
        x.reshape(m, k), w,
        out_dtype=out_dtype or jnp.result_type(x.dtype, w.dtype),
        interpret=interpret,
    )
    return out.reshape(*x.shape[:-1], n)


register(OffloadOp(
    name="matmul",
    cost=_matmul_cost,
    host=_matmul_host,
    pallas=_matmul_pallas,
    eligible=_matmul_eligible,
    plan=_matmul_plan,
    plan_lower=_matmul_plan_lower,
))


def psum_cast_dtype(dtype):
    """Reduction dtype for TP psums. bf16 on real hardware (halves wire
    bytes); f32 on the XLA:CPU emulation backend, whose AllReducePromotion
    pass crashes cloning bf16 all-reduces produced by partially-manual
    shard_maps (observed: 'Invalid binary instruction opcode copy')."""
    if jax.default_backend() == "cpu" and jnp.dtype(dtype) == jnp.bfloat16:
        return jnp.float32
    return dtype


# ---------------------------------------------------------------------------
# mlp_block — the whole dense FFN behind one descriptor.
#
# The model layers used to hand-roll this: raw `lax.dot_general` calls inside
# a shard_map (bypassing the seam entirely) plus a bare engine().launch for
# the cost.  As a registered op the block takes the same single
# cost -> plan -> launch -> lower path as everything else: the TP shard_map
# form is its `plan` (one bf16 psum per block), the dense fp32-accumulated
# form its host lowering, the hand-tiled MXU GEMMs its Pallas lowering.
# ---------------------------------------------------------------------------

def _mlp_dims(x, w_up, w_down, gate, kind):
    if x.ndim < 2:
        raise ValueError(f"mlp_block needs batched input, got {x.shape}")
    if kind not in ("swiglu", "gelu"):
        raise ValueError(f"mlp_block: unknown kind {kind!r}")
    d = x.shape[-1]
    if w_up.ndim != 2 or w_up.shape[0] != d:
        raise ValueError(f"mlp_block: bad up projection {x.shape} @ {w_up.shape}")
    d_ff = w_up.shape[1]
    if tuple(w_down.shape) != (d_ff, d):
        raise ValueError(
            f"mlp_block: bad down projection {w_down.shape}, want {(d_ff, d)}"
        )
    if kind == "swiglu" and (gate is None or tuple(gate.shape) != (d, d_ff)):
        raise ValueError("mlp_block: swiglu needs a (d, d_ff) gate")
    m = 1
    for dim in x.shape[:-1]:
        m *= dim
    return m, d, d_ff


def _mlp_cost(x, w_up, w_down, gate=None, b_up=None, b_down=None, *,
              kind="swiglu"):
    m, d, d_ff = _mlp_dims(x, w_up, w_down, gate, kind)
    n_mats = 3 if kind == "swiglu" else 2
    return cm.gemm_cost(
        m, d_ff * n_mats, d, jnp.dtype(x.dtype).itemsize, op="mlp_block"
    )


def _mlp_eligible(x, w_up, w_down, gate=None, b_up=None, b_down=None, *,
                  kind="swiglu"):
    m, d, d_ff = _mlp_dims(x, w_up, w_down, gate, kind)
    return _pallas_gemm_eligible(m, d_ff, d, x.dtype)


def _mlp_plan(x, w_up, w_down, gate=None, b_up=None, b_down=None, *,
              kind="swiglu"):
    """Whole-block tensor-parallel applicability (pure inspection).

    Returns ``(mesh, dp_axes)`` when the d_ff column/row slices can stay
    local under an ambient model-parallel mesh, else None."""
    import os

    if os.environ.get("REPRO_DISABLE_TP_MLP"):
        return None
    info = _tp_mesh_info()
    if info is None or x.ndim != 3:
        return None
    mesh, n_model, dp, n_dp = info
    d_ff = w_up.shape[1]
    if x.shape[0] % n_dp or d_ff % n_model:
        return None
    return mesh, dp


def _mlp_plan_lower(plan, x, w_up, w_down, gate=None, b_up=None, b_down=None,
                    *, kind="swiglu"):
    """Whole MLP under one shard_map: d_ff column/row slices stay local,
    ONE bf16 psum forward + one backward (§Perf hillclimb #2).  GSPMD's
    schedule all-reduces the fp32 products and pays per-projection dX
    reductions."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh, dp = plan
    if kind == "swiglu":

        def local(xl, wg, wu, wd):
            g = lax.dot_general(xl, wg, (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            u = lax.dot_general(xl, wu, (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            h = (jax.nn.silu(g) * u).astype(xl.dtype)
            y = lax.dot_general(h, wd, (((2,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            y = lax.psum(y.astype(psum_cast_dtype(xl.dtype)), "model")
            return y.astype(xl.dtype)

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, None, None), P(None, "model"), P(None, "model"),
                      P("model", None)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )
        return fn(x, gate, w_up, w_down)

    def local_gelu(xl, wu, bu, wd, bd):
        h = lax.dot_general(xl, wu, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + bu
        h = jax.nn.gelu(h).astype(xl.dtype)
        y = lax.dot_general(h, wd, (((2,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        y = lax.psum(y.astype(psum_cast_dtype(xl.dtype)), "model")
        return y.astype(xl.dtype) + bd.astype(xl.dtype)

    fn = shard_map(
        local_gelu, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, "model"), P("model"),
                  P("model", None), P(None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )
    return fn(x, w_up, b_up, w_down, b_down)


def _mlp_host(x, w_up, w_down, gate=None, b_up=None, b_down=None, *,
              kind="swiglu"):
    dn = (((x.ndim - 1,), (0,)), ((), ()))
    if kind == "swiglu":
        g = _accum_dot(x, gate, dn, x.dtype)
        u = _accum_dot(x, w_up, dn, x.dtype)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return _accum_dot(h, w_down, dn, x.dtype)
    h = _accum_dot(x, w_up, dn, x.dtype)
    if b_up is not None:
        h = h + b_up.astype(h.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    y = _accum_dot(h, w_down, dn, x.dtype)
    if b_down is not None:
        y = y + b_down.astype(y.dtype)
    return y


def _mlp_pallas(x, w_up, w_down, gate=None, b_up=None, b_down=None, *,
                kind="swiglu", interpret=False):
    m, d, d_ff = _mlp_dims(x, w_up, w_down, gate, kind)
    mm = _kops().pallas_lowering("matmul")
    xm = x.reshape(m, d)
    if kind == "swiglu":
        g = mm(xm, gate, out_dtype=x.dtype, interpret=interpret)
        u = mm(xm, w_up, out_dtype=x.dtype, interpret=interpret)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = mm(h, w_down, out_dtype=x.dtype, interpret=interpret)
    else:
        h = mm(xm, w_up, out_dtype=x.dtype, interpret=interpret)
        if b_up is not None:
            h = h + b_up.astype(h.dtype)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        y = mm(h, w_down, out_dtype=x.dtype, interpret=interpret)
        if b_down is not None:
            y = y + b_down.astype(y.dtype)
    return y.reshape(*x.shape[:-1], d)


register(OffloadOp(
    name="mlp_block",
    cost=_mlp_cost,
    host=_mlp_host,
    pallas=_mlp_pallas,
    eligible=_mlp_eligible,
    plan=_mlp_plan,
    plan_lower=_mlp_plan_lower,
))


# ---------------------------------------------------------------------------
# qkv_project — the fused 3-way attention input projection behind one
# descriptor (mirrors mlp_block).  The attention layer used to hand-roll a
# whole-block shard_map of raw `lax.dot_general` launches plus bare
# engine().launch accounting; as a registered op the projection takes the
# single cost -> plan -> launch -> lower path: the sequence-sharded TP
# shard_map is its `plan` (projection FLOPs divided over the model axis, one
# tiled all-gather of the small qkv activations), the concatenated-weight
# GEMM its host lowering, the hand-tiled MXU matmul its Pallas lowering.
# ---------------------------------------------------------------------------

def _qkv_dims(x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    if x.ndim < 2:
        raise ValueError(f"qkv_project needs batched input, got {x.shape}")
    d = x.shape[-1]
    for name, w in (("wq", wq), ("wk", wk), ("wv", wv)):
        if w.ndim != 2 or w.shape[0] != d:
            raise ValueError(
                f"qkv_project: bad {name} {w.shape} for input {x.shape}"
            )
    for name, w, b in (("bq", wq, bq), ("bk", wk, bk), ("bv", wv, bv)):
        if b is not None and tuple(b.shape) != (w.shape[1],):
            raise ValueError(f"qkv_project: bad bias {name} {b.shape}")
    m = 1
    for dim in x.shape[:-1]:
        m *= dim
    n = wq.shape[1] + wk.shape[1] + wv.shape[1]
    return m, d, n


def _qkv_cost(x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    m, d, n = _qkv_dims(x, wq, wk, wv, bq=bq, bk=bk, bv=bv)
    return cm.gemm_cost(m, n, d, jnp.dtype(x.dtype).itemsize, op="qkv_project")


def _qkv_eligible(x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    m, d, n = _qkv_dims(x, wq, wk, wv, bq=bq, bk=bk, bv=bv)
    return _pallas_gemm_eligible(m, n, d, x.dtype)


def _qkv_concat(x, wq, wk, wv, bq, bk, bv):
    w = jnp.concatenate([wq, wk, wv], axis=1)
    if bq is None and bk is None and bv is None:
        return w, None
    parts = [
        b if b is not None else jnp.zeros((wt.shape[1],), x.dtype)
        for b, wt in ((bq, wq), (bk, wk), (bv, wv))
    ]
    return w, jnp.concatenate(parts)


def _qkv_host(x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    dn = (((x.ndim - 1,), (0,)), ((), ()))
    if _tp_mesh_info() is not None:
        # Under a model-parallel mesh wq may be split by column where
        # wk/wv are not: a concatenated weight would move wq between the
        # devices on every call, so project apart and join the outputs.
        # On one device the concatenated weight is one GEMM, and faster
        # (yi-6b's serve step on a v5e: about 1 ms of 37).
        parts = []
        for w, b in ((wq, bq), (wk, bk), (wv, bv)):
            y = _accum_dot(x, w, dn, x.dtype)
            parts.append(y if b is None else y + b.astype(y.dtype))
        return jnp.concatenate(parts, axis=-1)
    w, b = _qkv_concat(x, wq, wk, wv, bq, bk, bv)
    y = _accum_dot(x, w, dn, x.dtype)
    return y if b is None else y + b.astype(y.dtype)


def _qkv_pallas(x, wq, wk, wv, *, bq=None, bk=None, bv=None, interpret=False):
    m, d, n = _qkv_dims(x, wq, wk, wv, bq=bq, bk=bk, bv=bv)
    w, b = _qkv_concat(x, wq, wk, wv, bq, bk, bv)
    y = _kops().pallas_lowering("qkv_project")(
        x.reshape(m, d), w, out_dtype=x.dtype, interpret=interpret
    )
    if b is not None:
        y = y + b.astype(y.dtype)
    return y.reshape(*x.shape[:-1], n)


def _qkv_plan(x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    """Sequence-sharded TP applicability (pure inspection): each model shard
    projects its sequence slice and the small qkv activations are
    all-gathered — replicated compute would pay n_model x the FLOPs."""
    import os

    if os.environ.get("REPRO_DISABLE_TP_ATTN"):
        return None
    info = _tp_mesh_info()
    if info is None or x.ndim != 3:
        return None
    mesh, n_model, dp, n_dp = info
    if x.shape[0] % n_dp or x.shape[1] % n_model:
        return None
    return mesh, dp


def _qkv_plan_lower(plan, x, wq, wk, wv, *, bq=None, bk=None, bv=None):
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh, dp = plan
    n_model = mesh.shape["model"]
    w, b = _qkv_concat(x, wq, wk, wv, bq, bk, bv)
    if b is None:
        b = jnp.zeros((w.shape[1],), x.dtype)

    def local(xl, wl, bl):
        s = xl.shape[1]
        seg = s // n_model
        idx = lax.axis_index("model")
        xs = lax.dynamic_slice_in_dim(xl, idx * seg, seg, axis=1)
        y = lax.dot_general(
            xs, wl, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(xl.dtype) + bl.astype(xl.dtype)
        return lax.all_gather(y, "model", axis=1, tiled=True)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), P(None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )
    return fn(x, w, b)


register(OffloadOp(
    name="qkv_project",
    cost=_qkv_cost,
    host=_qkv_host,
    pallas=_qkv_pallas,
    eligible=_qkv_eligible,
    plan=_qkv_plan,
    plan_lower=_qkv_plan_lower,
))


def _gemm_batched_cost(a, b, *, out_dtype=None):
    if a.ndim != 3 or b.ndim != 3 or a.shape[0] != b.shape[0]:
        raise ValueError(f"gemm_batched: bad shapes {a.shape} @ {b.shape}")
    bsz, m, k = a.shape
    _, kb, n = b.shape
    if k != kb:
        raise ValueError(
            f"gemm_batched contraction mismatch: {a.shape} @ {b.shape}"
        )
    return cm.gemm_cost(
        m, n, k, jnp.dtype(a.dtype).itemsize, batch=bsz, op="gemm_batched"
    )


def _gemm_batched_eligible(a, b, *, out_dtype=None):
    _, m, k = a.shape
    n = b.shape[2]
    return _pallas_gemm_eligible(m, n, k, a.dtype)


def _gemm_batched_host(a, b, *, out_dtype=None):
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)
    return _accum_dot(a, b, (((2,), (1,)), ((0,), (0,))), out_dtype)


def _gemm_batched_pallas(a, b, *, out_dtype=None, interpret=False):
    return _kops().pallas_lowering("gemm_batched")(
        a, b,
        out_dtype=out_dtype or jnp.result_type(a.dtype, b.dtype),
        interpret=interpret,
    )


register(OffloadOp(
    name="gemm_batched",
    cost=_gemm_batched_cost,
    host=_gemm_batched_host,
    pallas=_gemm_batched_pallas,
    eligible=_gemm_batched_eligible,
))


def _expert_dims(x, w):
    if w.ndim != 3 or x.shape[0] != w.shape[0] or x.shape[-1] != w.shape[1]:
        raise ValueError(f"expert_matmul: bad shapes {x.shape} @ {w.shape}")
    e = x.shape[0]
    m = 1
    for dim in x.shape[1:-1]:
        m *= dim
    k, n = w.shape[1], w.shape[2]
    return e, m, k, n


def _expert_cost(x, w, *, out_dtype=None):
    e, m, k, n = _expert_dims(x, w)
    return cm.gemm_cost(
        m, n, k, jnp.dtype(x.dtype).itemsize, batch=e, op="moe_gemm"
    )


def _expert_eligible(x, w, *, out_dtype=None):
    e, m, k, n = _expert_dims(x, w)
    return _pallas_gemm_eligible(m, n, k, x.dtype)


def _expert_host(x, w, *, out_dtype=None):
    return _accum_dot(
        x, w, (((x.ndim - 1,), (1,)), ((0,), (0,))),
        out_dtype or jnp.result_type(x.dtype, w.dtype),
    )


def _expert_pallas(x, w, *, out_dtype=None, interpret=False):
    e, m, k, n = _expert_dims(x, w)
    out = _kops().pallas_lowering("moe_gemm")(
        x.reshape(e, m, k), w, out_dtype=out_dtype or x.dtype,
        interpret=interpret,
    )
    return out.reshape(*x.shape[:-1], n)


register(OffloadOp(
    name="expert_matmul",
    cost=_expert_cost,
    host=_expert_host,
    pallas=_expert_pallas,
    eligible=_expert_eligible,
))


# ---------------------------------------------------------------------------
# moe_expert_ffn — the whole grouped expert FFN (gate/up/silu/down) behind
# one descriptor.  The MoE layer used to issue three separate expert GEMM
# dispatches (and the explicit-collective path three bare engine().launch
# accounting calls); now the cost model sees the whole expert block at once
# and the expert-parallel shard_map — experts model-sharded, every GEMM
# chip-local, zero collectives — is its `plan`.
# ---------------------------------------------------------------------------

def _moe_ffn_dims(x, wg, wu, wd):
    if x.ndim < 3 or wg.ndim != 3 or wu.ndim != 3 or wd.ndim != 3:
        raise ValueError(
            f"moe_expert_ffn: bad ranks {x.shape} {wg.shape} {wu.shape} {wd.shape}"
        )
    e, d = x.shape[0], x.shape[-1]
    f = wg.shape[2]
    if wg.shape[:2] != (e, d) or wu.shape != wg.shape:
        raise ValueError(f"moe_expert_ffn: bad gate/up {wg.shape} {wu.shape}")
    if tuple(wd.shape) != (e, f, d):
        raise ValueError(f"moe_expert_ffn: bad down {wd.shape}, want {(e, f, d)}")
    m = 1
    for dim in x.shape[1:-1]:
        m *= dim
    return e, m, d, f


def _moe_ffn_cost(x, wg, wu, wd):
    e, m, d, f = _moe_ffn_dims(x, wg, wu, wd)
    return cm.gemm_cost(
        m, 3 * f, d, jnp.dtype(x.dtype).itemsize, batch=e, op="moe_expert_ffn"
    )


def _moe_ffn_eligible(x, wg, wu, wd):
    e, m, d, f = _moe_ffn_dims(x, wg, wu, wd)
    return _pallas_gemm_eligible(m, f, d, x.dtype)


def _moe_ffn_local(x, wg, wu, wd):
    """The expert FFN math itself (fp32 accumulation) — shared by the host
    lowering and the plan's shard_map body."""
    dn = (((x.ndim - 1,), (1,)), ((0,), (0,)))
    g = _accum_dot(x, wg, dn, x.dtype)
    u = _accum_dot(x, wu, dn, x.dtype)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return _accum_dot(h, wd, dn, x.dtype)


def _moe_ffn_host(x, wg, wu, wd):
    return _moe_ffn_local(x, wg, wu, wd)


def _moe_ffn_pallas(x, wg, wu, wd, *, interpret=False):
    e, m, d, f = _moe_ffn_dims(x, wg, wu, wd)
    mm = _kops().pallas_lowering("moe_expert_ffn")
    xe = x.reshape(e, m, d)
    g = mm(xe, wg, out_dtype=x.dtype, interpret=interpret)
    u = mm(xe, wu, out_dtype=x.dtype, interpret=interpret)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = mm(h, wd, out_dtype=x.dtype, interpret=interpret)
    return y.reshape(*x.shape[:-1], d)


def _moe_ffn_plan(x, wg, wu, wd):
    """Expert-parallel applicability: experts shard over the model axis and
    every GEMM stays chip-local (zero collectives inside the plan).  The
    first free dim additionally shards over the data axes when it divides
    (the grouped/EP dispatch layouts both arrange for this)."""
    info = _tp_mesh_info()
    if info is None:
        return None
    mesh, n_model, dp, n_dp = info
    if x.shape[0] % n_model:
        return None
    shard_free = bool(dp) and x.ndim >= 3 and x.shape[1] % n_dp == 0
    return mesh, (dp if shard_free else ())


def _moe_ffn_plan_lower(plan, x, wg, wu, wd):
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh, dp = plan
    free = (dp if dp else None,) + (None,) * (x.ndim - 2)
    spec_x = P("model", *free)
    spec_w = P("model", None, None)
    fn = shard_map(
        _moe_ffn_local,
        mesh=mesh,
        in_specs=(spec_x, spec_w, spec_w, spec_w),
        out_specs=spec_x,
        check_vma=False,
    )
    return fn(x, wg, wu, wd)


register(OffloadOp(
    name="moe_expert_ffn",
    cost=_moe_ffn_cost,
    host=_moe_ffn_host,
    pallas=_moe_ffn_pallas,
    eligible=_moe_ffn_eligible,
    plan=_moe_ffn_plan,
    plan_lower=_moe_ffn_plan_lower,
))


# ---------------------------------------------------------------------------
# ssd_scan — the whole Mamba-2 SSD core (chunked quadratic term + inter-chunk
# state recurrence + D skip) behind one descriptor.  The SSM layer used to
# run this inside a hand-rolled whole-block shard_map with bare
# engine().launch accounting; now the per-head shard_map — SSM heads
# model-sharded, all math chip-local, zero collectives — is its `plan` and
# the ``ssd_chunk_diag`` Pallas kernel its device form.
# ---------------------------------------------------------------------------

def _ssd_dims(xh, dt, a, bh, ch, d_skip, *, chunk):
    if xh.ndim != 4:
        raise ValueError(f"ssd_scan: x must be (B, S, H, P), got {xh.shape}")
    bsz, s, h, pdim = xh.shape
    n = bh.shape[-1]
    if dt.shape != (bsz, s, h):
        raise ValueError(f"ssd_scan: dt {dt.shape} != {(bsz, s, h)}")
    if a.shape != (h,) or d_skip.shape != (h,):
        raise ValueError(f"ssd_scan: a/d_skip must be ({h},)")
    if bh.shape != (bsz, s, h, n) or ch.shape != (bsz, s, h, n):
        raise ValueError(f"ssd_scan: bad B/C {bh.shape} {ch.shape}")
    q = min(int(chunk), s)
    if s % q:
        raise ValueError(f"ssd_scan: seq {s} not divisible by chunk {q}")
    return bsz, s, h, pdim, n, q


def _ssd_cost(xh, dt, a, bh, ch, d_skip, *, chunk):
    bsz, s, h, pdim, n, q = _ssd_dims(xh, dt, a, bh, ch, d_skip, chunk=chunk)
    return cm.gemm_cost(
        bsz * s, 2 * n + pdim, q, jnp.dtype(xh.dtype).itemsize, batch=h,
        op="ssd_scan",
    )


def _ssd_eligible(xh, dt, a, bh, ch, d_skip, *, chunk):
    bsz, s, h, pdim, n, q = _ssd_dims(xh, dt, a, bh, ch, d_skip, chunk=chunk)
    return min(pdim, n, q) >= 8 and xh.dtype in (jnp.float32, jnp.bfloat16)


def _ssd_scan_math(xh, dt, a, bh, ch, d_skip, chunk, diag_fn):
    """Chunked SSD core: (B, S, H, P) -> (B, S, H, P) fp32.  ``diag_fn``
    computes the within-chunk quadratic term (jnp oracle or Pallas kernel);
    the (N, P)-state inter-chunk recurrence stays a ``lax.scan``.  All math
    is per-head — under the plan's shard_map each device runs this on its
    local heads with zero collectives."""
    bsz, s, h, pdim = xh.shape
    n = bh.shape[-1]
    q = min(int(chunk), s)
    nc = s // q
    da = dt * a                                               # (B, S, H)
    xdt = xh * dt[..., None]

    def to_bh(t):
        t = t.reshape(bsz, nc, q, h, -1).transpose(0, 3, 1, 2, 4)
        return t.reshape(bsz * h, nc, q, t.shape[-1])

    da_c = da.reshape(bsz, nc, q, h)
    cum_c = jnp.cumsum(da_c, axis=2)                          # (B, C, Q, H)
    cum_bh = cum_c.transpose(0, 3, 1, 2).reshape(bsz * h, nc, q)

    x_bh = to_bh(xdt).astype(jnp.float32)
    b_bh = to_bh(bh).astype(jnp.float32)
    c_bh = to_bh(ch).astype(jnp.float32)

    y_diag = diag_fn(x_bh, cum_bh, b_bh, c_bh)

    decay_to_end = jnp.exp(cum_bh[:, :, -1:] - cum_bh)
    states = jnp.einsum("zcq,zcqn,zcqp->zcnp", decay_to_end, b_bh, x_bh)
    chunk_decay = jnp.exp(cum_bh[:, :, -1])

    def scan_fn(carry, inp):
        st, dec = inp
        prev = carry
        return dec[:, None, None] * prev + st, prev

    init = jnp.zeros((bsz * h, n, pdim), jnp.float32)
    _, prev_states = jax.lax.scan(
        scan_fn, init, (states.transpose(1, 0, 2, 3), chunk_decay.T)
    )
    prev_states = prev_states.transpose(1, 0, 2, 3)

    y_off = jnp.einsum(
        "zcqn,zcnp,zcq->zcqp", c_bh, prev_states, jnp.exp(cum_bh)
    )
    y = (y_diag.astype(jnp.float32) + y_off)
    y = y.reshape(bsz, h, s, pdim).transpose(0, 2, 1, 3)
    return y + xh.astype(jnp.float32) * d_skip[None, None, :, None]


def _ssd_host(xh, dt, a, bh, ch, d_skip, *, chunk):
    from repro.kernels import ref as kref  # lazy: avoid import cycle

    return _ssd_scan_math(xh, dt, a, bh, ch, d_skip, chunk,
                          kref.ssd_chunk_diag_ref)


def _ssd_pallas(xh, dt, a, bh, ch, d_skip, *, chunk, interpret=False):
    kernel = _kops().pallas_lowering("ssd_scan")

    def diag(x_bh, cum_bh, b_bh, c_bh):
        return kernel(x_bh, cum_bh, b_bh, c_bh, interpret=interpret)

    return _ssd_scan_math(xh, dt, a, bh, ch, d_skip, chunk, diag)


def _ssd_plan(xh, dt, a, bh, ch, d_skip, *, chunk):
    """Head-sharded TP applicability: every piece of the SSD math is
    per-head and therefore chip-local under a model-sharded head axis."""
    info = _tp_mesh_info()
    if info is None or xh.ndim != 4:
        return None
    mesh, n_model, dp, n_dp = info
    bsz, s, h, _ = xh.shape
    if h % n_model or bsz % n_dp or s % min(int(chunk), s):
        return None
    return mesh, dp


def _ssd_plan_lower(plan, xh, dt, a, bh, ch, d_skip, *, chunk):
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    mesh, dp = plan

    def local(xl, dtl, al, bl, cl, dl):
        from repro.kernels import ref as kref

        return _ssd_scan_math(xl, dtl, al, bl, cl, dl, chunk,
                              kref.ssd_chunk_diag_ref)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp, None, "model", None), P(dp, None, "model"), P("model"),
            P(dp, None, "model", None), P(dp, None, "model", None),
            P("model"),
        ),
        out_specs=P(dp, None, "model", None),
        check_vma=False,
    )
    return fn(xh, dt, a, bh, ch, d_skip)


register(OffloadOp(
    name="ssd_scan",
    cost=_ssd_cost,
    host=_ssd_host,
    pallas=_ssd_pallas,
    eligible=_ssd_eligible,
    plan=_ssd_plan,
    plan_lower=_ssd_plan_lower,
))


def _syrk_cost(a, *, out_dtype=None):
    if a.ndim != 2:
        raise ValueError(f"syrk takes a 2-D operand, got {a.shape}")
    n, k = a.shape
    return cm.syrk_cost(n, k, jnp.dtype(a.dtype).itemsize)


def _syrk_host(a, *, out_dtype=None):
    return _accum_dot(a, a, (((1,), (1,)), ((), ())), out_dtype or a.dtype)


register(OffloadOp(
    name="syrk",
    cost=_syrk_cost,
    host=_syrk_host,
    host_only=True,
    note="host-only (syrk.c compiled for host, per paper)",
))


def _attention_cost(
    q, k, v, *, causal=True, window=None, sm_scale=None, kv_mask=None
):
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    static_window = (
        window if (window is None or isinstance(window, int)) else None
    )
    return cm.attention_cost(
        b, sq, skv, hq, d, jnp.dtype(q.dtype).itemsize,
        window=static_window if static_window and static_window < skv else None,
    )


def _attention_eligible(
    q, k, v, *, causal=True, window=None, sm_scale=None, kv_mask=None
):
    # The Pallas flash kernel needs a static window (traced per-layer window
    # patterns fall back to the masked-einsum host path) and no kv_mask.
    static = window is None or isinstance(window, int)
    return (
        static
        and kv_mask is None
        and q.shape[-1] >= 8
        and q.dtype in (jnp.float32, jnp.bfloat16)
    )


def _attention_pallas(
    q, k, v, *, causal=True, window=None, sm_scale=None, kv_mask=None,
    interpret=False,
):
    skv = k.shape[2]
    eff_window = None if (window is None or window >= skv) else window
    return _kops().pallas_lowering("attention")(
        q, k, v,
        causal=causal,
        window=eff_window,
        sm_scale=sm_scale,
        interpret=interpret,
    )


def _attention_host(
    q, k, v, *, causal=True, window=None, sm_scale=None, kv_mask=None
):
    return attention_math(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        kv_mask=kv_mask,
    )


register(OffloadOp(
    name="attention",
    cost=_attention_cost,
    host=_attention_host,
    pallas=_attention_pallas,
    eligible=_attention_eligible,
))


# ---------------------------------------------------------------------------
# decode_attention — one-token attention against a (possibly rolling) KV
# cache with a [lo, hi) valid-slot range.  The decode layer used a bare
# engine().launch + hand-routed backend branch; as a descriptor the masked
# math is the host lowering and the flash-decode kernel (one HBM pass over
# the cache) the Pallas lowering.
# ---------------------------------------------------------------------------

def _decode_attn_cost(q, k, v, lo, hi):
    if q.ndim != 4 or q.shape[2] != 1:
        raise ValueError(f"decode_attention: q must be (B, Hq, 1, D), got {q.shape}")
    b, hq, _, d = q.shape
    if k.ndim != 4 or v.shape != k.shape or k.shape[0] != b or k.shape[3] != d:
        raise ValueError(f"decode_attention: bad cache {k.shape} / {v.shape}")
    skv = k.shape[2]
    return cm.attention_cost(b, 1, skv, hq, d, jnp.dtype(q.dtype).itemsize)


def _decode_attn_eligible(q, k, v, lo, hi):
    return q.shape[-1] >= 8 and q.dtype in (jnp.float32, jnp.bfloat16)


def _decode_attn_host(q, k, v, lo, hi):
    # Grouped-query form: the G = Hq // Hkv query heads of a key/value head
    # form one (G, D) block against that head's cache, so K and V are read
    # as stored and never repeated to every query head (attention_math's
    # repeat keeps prefill's q-head dim shardable; the decode cache is
    # sharded by batch and sequence instead).  Float32 scores, softmax and
    # accumulation, as attention_math computes them.
    b, hq, _, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32)) * d ** -0.5
    slots = jnp.arange(k.shape[2], dtype=jnp.int32)
    kv_valid = jnp.logical_and(slots >= lo, slots < hi)
    s = jnp.where(kv_valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows contribute zeros (matches the kernel semantics)
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= -1e30 * 0.5, 0.0, p)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def _decode_attn_pallas(q, k, v, lo, hi, *, interpret=False):
    b = q.shape[0]
    lo_b = jnp.broadcast_to(lo, (b,)).astype(jnp.int32)
    hi_b = jnp.broadcast_to(hi, (b,)).astype(jnp.int32)
    out = _kops().pallas_lowering("decode_attention")(
        q[:, :, 0, :], k, v, lo_b, hi_b, interpret=interpret
    )
    return out[:, :, None, :]


register(OffloadOp(
    name="decode_attention",
    cost=_decode_attn_cost,
    host=_decode_attn_host,
    pallas=_decode_attn_pallas,
    eligible=_decode_attn_eligible,
))


# ---------------------------------------------------------------------------
# Level-2 / Level-1 descriptors (host lowering only; still scored + routed,
# so traces show whether the decision model would offload them)
# ---------------------------------------------------------------------------

def _gemv_cost(a, x, *, out_dtype=None):
    if a.ndim != 2 or x.ndim != 1 or a.shape[1] != x.shape[0]:
        raise ValueError(f"gemv: bad shapes {a.shape} @ {x.shape}")
    m, n = a.shape
    return cm.gemv_cost(m, n, jnp.dtype(a.dtype).itemsize)


def _gemv_host(a, x, *, out_dtype=None):
    out_dtype = out_dtype or jnp.result_type(a.dtype, x.dtype)
    return _accum_dot(a, x, (((1,), (0,)), ((), ())), out_dtype)


register(OffloadOp(name="gemv", cost=_gemv_cost, host=_gemv_host))


def _dot_cost(x, y):
    if x.shape != y.shape or x.ndim != 1:
        raise ValueError(f"dot: bad shapes {x.shape}, {y.shape}")
    return cm.vector_cost("dot", x.shape[0], jnp.dtype(x.dtype).itemsize)


def _dot_host(x, y):
    return jnp.sum(
        x.astype(jnp.float32) * y.astype(jnp.float32)
    ).astype(x.dtype)


register(OffloadOp(name="dot", cost=_dot_cost, host=_dot_host))


def _axpy_cost(alpha, x, y):
    return cm.vector_cost("axpy", x.size, jnp.dtype(x.dtype).itemsize)


def _axpy_host(alpha, x, y):
    return alpha * x + y


register(OffloadOp(name="axpy", cost=_axpy_cost, host=_axpy_host))


def _scal_cost(alpha, x):
    return cm.vector_cost("scal", x.size, jnp.dtype(x.dtype).itemsize, 1.0)


def _scal_host(alpha, x):
    return alpha * x


register(OffloadOp(name="scal", cost=_scal_cost, host=_scal_host))


def _nrm2_cost(x):
    return cm.vector_cost("nrm2", x.size, jnp.dtype(x.dtype).itemsize)


def _nrm2_host(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))).astype(x.dtype)


register(OffloadOp(name="nrm2", cost=_nrm2_cost, host=_nrm2_host))


# ---------------------------------------------------------------------------
# Light reductions / elementwise ops — host-only descriptors so the auto
# policy can score them and the trace sees them (they never pay to offload
# alone; the graph frontend fuses them into producer launches instead).
# ---------------------------------------------------------------------------

def _light_cost(op_name, flops_per_elem=2.0):
    def cost(x, *rest, **kwargs):
        return cm.vector_cost(
            op_name, x.size, jnp.dtype(x.dtype).itemsize, flops_per_elem
        )

    return cost


def _sum_host(x, *, axis=None, keepdims=False):
    return jnp.sum(x, axis=axis, keepdims=keepdims)


def _mean_host(x, *, axis=None, keepdims=False):
    return jnp.mean(x, axis=axis, keepdims=keepdims)


def _relu_host(x):
    return jax.nn.relu(x)


def _silu_host(x):
    return jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_cost(x, scale, *, eps=1e-6):
    if x.shape[-1] != scale.shape[-1]:
        raise ValueError(
            f"rmsnorm_scale: scale {scale.shape} does not match {x.shape}"
        )
    return cm.vector_cost(
        "rmsnorm_scale", x.size, jnp.dtype(x.dtype).itemsize, 4.0
    )


def _rmsnorm_host(x, scale, *, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


register(OffloadOp(name="sum", cost=_light_cost("sum", 1.0), host=_sum_host,
                   host_only=True, note="light reduction (host-only)"))
register(OffloadOp(name="mean", cost=_light_cost("mean", 1.0), host=_mean_host,
                   host_only=True, note="light reduction (host-only)"))
register(OffloadOp(name="relu", cost=_light_cost("relu", 1.0), host=_relu_host,
                   host_only=True, note="light elementwise (host-only)"))
register(OffloadOp(name="silu", cost=_light_cost("silu", 4.0), host=_silu_host,
                   host_only=True, note="light elementwise (host-only)"))
register(OffloadOp(name="rmsnorm_scale", cost=_rmsnorm_cost,
                   host=_rmsnorm_host, host_only=True,
                   note="norm epilogue (host-only)"))


# ---------------------------------------------------------------------------
# Public API — thin wrappers over dispatch()
# ---------------------------------------------------------------------------

def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    transpose_a: bool = False,
    transpose_b: bool = False,
    out_dtype=None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """C = op(A) @ op(B) for 2-D operands, routed through the offload seam."""
    return dispatch(
        "gemm", a, b, transpose_a=transpose_a, transpose_b=transpose_b,
        out_dtype=out_dtype, handle=handle,
    )


def matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype=None,
    tp_mode: Optional[str] = None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """General (leading-batch, k) @ (k, n) — the framework's workhorse.

    Collapses leading dims into the GEMM ``m`` dimension, exactly how a BLAS
    binding flattens a NumPy ``ndarray @ matrix``.  ``tp_mode`` ("row"/"col")
    opts into the explicit tensor-parallel path with bf16 reductions when an
    ambient mesh allows it (§Perf hillclimb #2).
    """
    return dispatch(
        "matmul", x, w, out_dtype=out_dtype, tp_mode=tp_mode, handle=handle
    )


def gemm_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    out_dtype=None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """(B, m, k) @ (B, k, n) batched GEMM (attention scores/values)."""
    return dispatch("gemm_batched", a, b, out_dtype=out_dtype, handle=handle)


def linear(
    x: jax.Array,
    w: jax.Array,
    b: Optional[jax.Array] = None,
    *,
    out_dtype=None,
    tp_mode: Optional[str] = None,
) -> jax.Array:
    """y = x @ w (+ b) — convenience wrapper used by every model layer."""
    y = matmul(x, w, out_dtype=out_dtype, tp_mode=tp_mode)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def mlp_block(
    x: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    gate: Optional[jax.Array] = None,
    b_up: Optional[jax.Array] = None,
    b_down: Optional[jax.Array] = None,
    kind: str = "swiglu",
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """Whole dense FFN (SwiGLU / GELU) through the offload seam.

    One dispatch for the block: the cost model sees all 2–3 projections at
    once, the TP shard_map form (single bf16 psum) is resolved as a plan
    *before* the record is written, and the Pallas path runs the projections
    on the hand-tiled MXU GEMM kernel.  Replaces the model layers' raw
    ``lax.dot_general``-inside-``shard_map`` launch sites."""
    return dispatch(
        "mlp_block", x, w_up, w_down, gate, b_up, b_down, kind=kind,
        handle=handle,
    )


def qkv_project(
    x: jax.Array,
    wq: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    *,
    bq: Optional[jax.Array] = None,
    bk: Optional[jax.Array] = None,
    bv: Optional[jax.Array] = None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """Fused q/k/v input projection through the offload seam.

    Returns the concatenated ``(..., (Hq + 2·Hkv)·hd)`` projection; callers
    split and reshape into heads.  One dispatch for all three projections:
    the cost model sees the whole input-projection workload, the
    sequence-sharded TP shard_map is resolved as a plan *before* the record
    is written, and the Pallas path runs one hand-tiled MXU GEMM over the
    concatenated weights.  Replaces the attention layer's raw
    ``lax.dot_general``-inside-``shard_map`` launch sites."""
    return dispatch(
        "qkv_project", x, wq, wk, wv, bq=bq, bk=bk, bv=bv, handle=handle
    )


def ssd_scan(
    xh: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    bh: jax.Array,
    ch: jax.Array,
    d_skip: jax.Array,
    *,
    chunk: int,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """Whole Mamba-2 SSD core through the offload seam.

    xh: (B, S, H, P); dt: (B, S, H) fp32; a, d_skip: (H,); bh, ch:
    (B, S, H, N).  Returns the fp32 (B, S, H, P) mixer output (within-chunk
    quadratic term + inter-chunk state recurrence + D skip).  The head-
    sharded TP shard_map is its plan (zero collectives — all SSD math is
    per-head); the ``ssd_chunk_diag`` Pallas kernel its device form."""
    return dispatch(
        "ssd_scan", xh, dt, a, bh, ch, d_skip, chunk=chunk, handle=handle
    )


def moe_expert_ffn(
    x: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    wd: jax.Array,
    *,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """Whole grouped expert FFN (E, ..., d) -> (E, ..., d) through the seam.

    One dispatch for gate/up/silu/down across all experts; the expert-
    parallel shard_map (experts model-sharded, zero collectives) is its
    plan, the grouped MXU GEMM kernel its Pallas lowering.  Keeps all free
    dims intact — merging a sharded dim in a reshape forces GSPMD to
    all-gather, so MoE layouts stay (E, G, C, d) through the block."""
    return dispatch("moe_expert_ffn", x, wg, wu, wd, handle=handle)


def moe_expert_ffn_placed(
    x: jax.Array,
    wg: jax.Array,
    wu: jax.Array,
    wd: jax.Array,
    *,
    placement,
):
    """Grouped expert FFN with per-expert placed accounting.

    Same op, same math, same single dispatch graph as
    :func:`moe_expert_ffn` — but ``placement`` (an
    ``repro.core.placement.ExpertDispatchPlan``) fans the accounting out
    into one handle-affine sub-launch per expert copy, charged on the lane
    its weights live on.  Returns ``(out, launch)`` so callers can read
    the busiest lane back."""
    return dispatch_placed(
        "moe_expert_ffn", x, wg, wu, wd, placement=placement
    )


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    *,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """One-token decode attention against a KV cache through the seam.

    q: (B, Hq, 1, D); caches: (B, Hkv, S_cache, D); ``lo``/``hi`` bound the
    valid cache slots (rolling SWA buffers wrap).  Host form is the
    shardable masked math; the Pallas form streams the cache once
    (``flash_decode``).  ``handle`` pins the call to the device-resident
    cache so affinity scheduling routes decode to the data."""
    return dispatch(
        "decode_attention", q, k_cache, v_cache, lo, hi, handle=handle
    )


def expert_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype=None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """(E, ..., d) @ (E, d, f) -> (E, ..., f) — expert-batched contraction.

    Keeps all free dims intact (no reshape): merging a sharded dim in a
    reshape forces GSPMD to all-gather, so MoE keeps its (E, G, C, d)
    layout 4-D through the expert GEMMs."""
    return dispatch("expert_matmul", x, w, out_dtype=out_dtype, handle=handle)


def syrk(a: jax.Array, *, out_dtype=None) -> jax.Array:
    """C = A @ A.T — host-only, as in the paper's build."""
    return dispatch("syrk", a, out_dtype=out_dtype)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,
    sm_scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    """Fused attention through the offload seam.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  ``window`` may be a static int
    or a *traced scalar* (per-layer local/global patterns scanned as data);
    the Pallas flash kernel requires a static window, so traced windows fall
    back to the masked-einsum host path (still fully shardable).
    Queries align to the end of kv when Sq < Skv (decode / suffix).
    ``handle`` pins the call to a device-resident buffer (e.g. a KV cache).
    """
    return dispatch(
        "attention", q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        kv_mask=kv_mask, handle=handle,
    )


def attention_math(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,
    sm_scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Raw masked/online-softmax attention math (no dispatch/accounting).

    Used by the host path of :func:`attention` and, per-shard, by the
    tensor-parallel attention block (each device runs it on its local q
    heads)."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    group = hq // hkv
    # GQA via kv-repeat to q heads: attention then partitions on the q-head
    # dim (sharded over `model`); a (hkv, group) reshape of a sharded head
    # dim is not expressible as a PartitionSpec and forces an all-gather.
    qf = q.astype(jnp.float32)
    kf = (jnp.repeat(k, group, axis=1) if group > 1 else k).astype(jnp.float32)
    vf = (jnp.repeat(v, group, axis=1) if group > 1 else v).astype(jnp.float32)

    def mask_for(q_pos, kv_pos):
        m = jnp.ones(jnp.broadcast_shapes(q_pos.shape, kv_pos.shape), jnp.bool_)
        if causal:
            m = jnp.logical_and(m, kv_pos <= q_pos)
        if window is not None:
            w = jnp.asarray(window, jnp.int32)
            m = jnp.logical_and(m, (q_pos - kv_pos) < w)
        return m

    if skv <= _DIRECT_ATTN_MAX_KV or sq == 1:
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
        q_pos = (skv - sq) + lax.broadcasted_iota(jnp.int32, (sq, skv), 0)
        kv_pos = lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
        s = jnp.where(mask_for(q_pos, kv_pos)[None, None], s, -1e30)
        if kv_mask is not None:  # (Skv,) or (B, Skv) slot validity (decode)
            km = kv_mask if kv_mask.ndim == 2 else kv_mask[None]
            s = jnp.where(km[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        # fully-masked rows contribute zeros (matches the kernel semantics)
        p = jnp.where(
            jnp.max(s, axis=-1, keepdims=True) <= -1e30 * 0.5, 0.0, p
        )
        out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
        return out.astype(q.dtype)

    if kv_mask is not None:
        raise NotImplementedError("kv_mask only supported on the direct path")
    bkv = min(_CHUNKED_ATTN_BLOCK, skv)
    while skv % bkv:
        bkv //= 2
    assert bkv >= 1
    nkv = skv // bkv
    kc = kf.reshape(b, hq, nkv, bkv, d).transpose(2, 0, 1, 3, 4)
    vc = vf.reshape(b, hq, nkv, bkv, d).transpose(2, 0, 1, 3, 4)
    q_pos = (skv - sq) + lax.broadcasted_iota(jnp.int32, (sq, 1), 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, j = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale  # noqa: F841 traced nkv times (scaled at launch above)
        kv_pos = j * bkv + lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        mask = mask_for(q_pos, kv_pos)  # (sq, bkv)
        s = jnp.where(mask[None, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pj = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(pj, axis=-1, keepdims=True)
        acc = corr * acc + jnp.einsum("bhqk,bhkd->bhqd", pj, vb)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hq, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    (m_f, l_f, acc_f), _ = lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(nkv, dtype=jnp.int32))
    )
    out = acc_f / jnp.maximum(l_f, 1e-30)
    return out.astype(q.dtype)


def gemv(
    a: jax.Array,
    x: jax.Array,
    *,
    out_dtype=None,
    handle: Optional[DeviceHandle] = None,
) -> jax.Array:
    return dispatch("gemv", a, x, out_dtype=out_dtype, handle=handle)


def dot(x: jax.Array, y: jax.Array) -> jax.Array:
    return dispatch("dot", x, y)


def axpy(alpha, x: jax.Array, y: jax.Array) -> jax.Array:
    return dispatch("axpy", alpha, x, y)


def scal(alpha, x: jax.Array) -> jax.Array:
    return dispatch("scal", alpha, x)


def nrm2(x: jax.Array) -> jax.Array:
    return dispatch("nrm2", x)


def reduce_sum(x: jax.Array, *, axis=None, keepdims: bool = False) -> jax.Array:
    """Scored + traced sum reduction (host-only descriptor)."""
    return dispatch("sum", x, axis=axis, keepdims=keepdims)


def reduce_mean(x: jax.Array, *, axis=None, keepdims: bool = False) -> jax.Array:
    """Scored + traced mean reduction (host-only descriptor)."""
    return dispatch("mean", x, axis=axis, keepdims=keepdims)


def relu(x: jax.Array) -> jax.Array:
    return dispatch("relu", x)


def silu(x: jax.Array) -> jax.Array:
    return dispatch("silu", x)


def rmsnorm_scale(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    """RMSNorm (fp32 internals) through the seam — the norm epilogue every
    block pays, visible to the trace and scoreable by the auto policy."""
    return dispatch("rmsnorm_scale", x, scale, eps=eps)
