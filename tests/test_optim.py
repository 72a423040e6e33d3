"""Optimizers: AdamW math, 8-bit moment quantization, grad compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.optim import (
    adamw,
    adamw8bit,
    clip_by_global_norm,
    compress_decompress,
    compressed_psum,
    constant,
    init_error_buffer,
    warmup_cosine,
)
from repro.optim.adamw import QBLOCK, _dequantize, _quantize


def _params():
    return {"w": jnp.asarray([[1.0, -2.0], [3.0, 0.5]]), "b": jnp.asarray([0.1, -0.1])}


def test_adamw_first_step_matches_reference():
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.95, 1e-8, 0.0
    init, update = adamw(constant(lr), b1=b1, b2=b2, eps=eps, weight_decay=wd,
                         max_grad_norm=1e9)
    p = _params()
    st_ = init(p)
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    p2, st2 = update(g, st_, p)
    # bias-corrected first step of Adam with unit grads = lr * 1/(1+eps')
    for leaf in jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda a, b_: a - b_, p, p2)
    ):
        np.testing.assert_allclose(np.asarray(leaf), lr, rtol=1e-4)


def test_weight_decay_pulls_to_zero():
    init, update = adamw(constant(0.1), weight_decay=0.5, max_grad_norm=1e9)
    p = {"w": jnp.asarray([10.0])}
    st_ = init(p)
    g = {"w": jnp.asarray([0.0])}
    p2, _ = update(g, st_, p)
    assert float(p2["w"][0]) < 10.0


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(gn), 20.0, rtol=1e-5)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-5
    )


@given(st.integers(1, 2000), st.floats(0.01, 100.0))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_error_bounded(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
    qt = _quantize(x)
    y = _dequantize(qt, x.shape)
    # blockwise int8: per-element error <= its block max / 254 (global max
    # is a valid, looser bound for any block layout)
    bound = float(np.abs(np.asarray(x)).max()) / 127.0 * 0.5 + 1e-6
    assert np.all(np.abs(np.asarray(y - x)) <= bound)
    # shape-preserving payload, axis-aligned scales
    assert qt.q.shape == x.shape
    assert x.shape[-1] % qt.scale.shape[-1] == 0


def test_adamw8bit_tracks_fp32_closely():
    init32, up32 = adamw(constant(0.05), max_grad_norm=1e9)
    init8, up8 = adamw8bit(constant(0.05), max_grad_norm=1e9)
    p32 = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(512,)), jnp.float32)}
    p8 = jax.tree_util.tree_map(jnp.copy, p32)
    s32, s8 = init32(p32), init8(p8)
    for i in range(5):
        g = {"w": jnp.asarray(np.random.default_rng(i).normal(size=(512,)), jnp.float32)}
        p32, s32 = up32(g, s32, p32)
        p8, s8 = up8(g, s8, p8)
    diff = float(jnp.max(jnp.abs(p32["w"] - p8["w"])))
    scale = float(jnp.max(jnp.abs(p32["w"]))) + 1e-9
    assert diff / scale < 0.05, diff


def test_error_feedback_preserves_sum():
    """Error feedback: over many steps, compressed grads sum ≈ true sum."""
    rng = np.random.default_rng(0)
    true_sum = np.zeros(64, np.float32)
    applied_sum = np.zeros(64, np.float32)
    g0 = {"w": jnp.zeros(64)}
    err = init_error_buffer(g0)
    for i in range(50):
        g = rng.normal(size=64).astype(np.float32) * (1 + i % 3)
        true_sum += g
        cg, err = compress_decompress({"w": jnp.asarray(g)}, err)
        applied_sum += np.asarray(cg["w"])
    resid = np.abs(true_sum - applied_sum).max()
    assert resid < np.abs(true_sum).max() * 0.02 + 0.5


def test_compressed_psum_over_real_axis():
    """int8 error-feedback psum under shard_map ≈ exact psum (subprocess
    with 4 forced devices)."""
    import json as _json
    import os as _os
    import subprocess as _sp
    import sys as _sys
    import textwrap as _tw
    from pathlib import Path as _Path

    script = _tw.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.compat import shard_map
        from repro.optim import compressed_psum, init_error_buffer

        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 256))  # per-device rows

        def local(g_loc):
            grads = {"w": g_loc[0]}
            err = init_error_buffer(grads)
            out, err2 = compressed_psum(grads, err, "data")
            return out["w"], err2["w"]

        fn = shard_map(local, mesh=mesh, in_specs=P("data", None),
                           out_specs=(P(None), P("data")),
                           check_vma=False)
        with mesh:
            got, err = fn(g)
        want = jnp.sum(g, axis=0)
        rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
        print(json.dumps({"rel": rel, "err_nonzero": bool(jnp.any(err != 0))}))
        """
    )
    env = dict(_os.environ)
    env["PYTHONPATH"] = str(_Path(__file__).resolve().parents[1] / "src")
    out = _sp.run([_sys.executable, "-c", script], env=env,
                  capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = _json.loads(out.stdout.strip().splitlines()[-1])
    # int8 quantization: ~1% relative error on the reduced sum, error
    # feedback buffers carry the residual
    assert rec["rel"] < 0.05, rec
    assert rec["err_nonzero"]


def test_warmup_cosine_shape():
    fn = warmup_cosine(1.0, 10, 100)
    assert float(fn(jnp.int32(0))) == 0.0
    assert float(fn(jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(fn(jnp.int32(100))) == pytest.approx(0.1, rel=1e-2)
    assert float(fn(jnp.int32(55))) > float(fn(jnp.int32(90)))
