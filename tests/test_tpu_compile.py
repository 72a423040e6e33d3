"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler ships with jax and compiles for a chip that is described
rather than attached.  It refuses what the chip would refuse: tiles that
break the vector layout, kernels that ask for more VMEM than they may use,
programs that do not fit in HBM.  Interpret-mode tests see none of that.
Each test compiles one program at its real size, and checks that it fits in
one v5e's 16 GB of HBM.  Nothing runs, so these say nothing of speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec, SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops
from repro.launch.serve import _pick_on_device
from repro.launch.steps import make_serve_step
from repro.models import build_model
from repro.sharding import cache_pspecs, named, param_pspecs

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    # Without this, libtpu writes its logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back, so keep the cache off around these compiles.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gemm_4096_compiles(one_chip, dtype):
    a = _spec((4096, 4096), dtype, one_chip)
    compiled = jax.jit(lambda x, y: ops.gemm(x, y)).lower(a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_flash_decode_yi6b_shape_compiles(one_chip):
    cfg = get_arch("yi-6b")
    b, s = 4, 2048
    q = _spec((b, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((b, cfg.num_kv_heads, s, cfg.head_dim), jnp.bfloat16, one_chip)
    bound = _spec((b,), jnp.int32, one_chip)
    compiled = jax.jit(ops.flash_decode).lower(q, kv, kv, bound, bound).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_flash_attention_s2048_compiles(one_chip):
    cfg = get_arch("yi-6b")
    s = 2048
    q = _spec((1, cfg.num_heads, s, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, cfg.num_kv_heads, s, cfg.head_dim), jnp.bfloat16, one_chip)
    compiled = jax.jit(ops.flash_attention).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_yi6b_full_width_serve_step_compiles(one_chip):
    """The greedy decode step ``serve_batch`` runs (the argmax in the step)
    at yi-6b's published widths: 32 layers, d_model 4096, GQA 32/4, vocab
    64000, bf16, batch 4 and a 2048-slot cache."""
    model = build_model(get_arch("yi-6b"))
    b, cache_len = 4, 2048

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _spec(x.shape, x.dtype, one_chip), tree
        )

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_decode_cache(b, cache_len)
    ))
    tokens = _spec((b, 1), jnp.int32, one_chip)
    index = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(
        _pick_on_device(make_serve_step(model)), donate_argnums=(1,)
    ).lower(params, cache, tokens, index).compile()
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
    )
    assert 12.0e9 < param_bytes < 12.3e9   # bf16 weights, ~6.06B params
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_qwen2_72b_tp4_serve_step_fits(topo):
    """One 20-layer stage of Qwen2-72B at its published widths (d_model
    8192, GQA 64/8, head 128, QKV bias, SwiGLU 29568, vocab 152064, bf16),
    tensor-parallel over the four chips of a v5e:2x2 as a greedy
    ``serve_batch(mesh=)`` compiles it: batch 16, 2048 slots.  Each chip's
    arguments and temporaries fit its 16 GB, and no weight moves between
    chips (no all-to-all)."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    model = build_model(dataclasses.replace(get_arch("qwen2-72b"), num_layers=20))
    b, cache_len = 16, 2048

    pshape = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cshape = jax.eval_shape(lambda: model.init_decode_cache(b, cache_len))
    p_shard = named(mesh, param_pspecs(pshape, mesh))
    c_shard = named(mesh, cache_pspecs(cshape, mesh))
    rep = named(mesh, PartitionSpec())

    def placed(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: _spec(x.shape, x.dtype, s), shapes, shardings)

    params = placed(pshape, p_shard)
    with mesh:
        compiled = jax.jit(
            _pick_on_device(make_serve_step(model)), donate_argnums=(1,),
            in_shardings=(p_shard, c_shard, rep, rep),
            out_shardings=(rep, rep, c_shard),
        ).lower(params, placed(cshape, c_shard), _spec((b, 1), jnp.int32, rep),
                _spec((), jnp.int32, rep)).compile()
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
    )
    assert 40.0e9 < param_bytes < 40.2e9   # bf16, 20,045,119,488 params
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    assert "all-to-all" not in compiled.as_text()
