"""Partitioning rules + a miniature end-to-end dry run in a subprocess.

The subprocess is required because forcing a multi-device host platform
(XLA_FLAGS) must happen before JAX initializes — the main pytest process
already owns a single-device runtime.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.models import build_model
from repro.sharding import batch_pspecs, cache_pspecs, param_pspecs

MESH16 = None


def _mesh():
    # a fake Mesh-like for rule evaluation: rules only read .shape / axis_names
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    return FakeMesh()


def test_dense_param_rules():
    cfg = get_arch("qwen2-72b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    specs = param_pspecs(shapes, _mesh())
    st = specs["stack"]
    assert st["mixer"]["wq"] == P(None, None, "model")
    assert st["mixer"]["wo"] == P(None, "model", None)
    assert st["ffn"]["w_gate"] == P(None, None, "model")
    assert st["ffn"]["w_down"] == P(None, "model", None)
    assert specs["embed"] == P("model", None)
    assert specs["head"] == P(None, "model")
    assert st["norm1"]["scale"] == P(None, None)


def test_moe_param_rules_ep():
    cfg = get_arch("qwen3-moe-30b-a3b")
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    specs = param_pspecs(shapes, _mesh())
    ffn = specs["stack"]["ffn"]
    assert ffn["we_gate"] == P(None, "model", None, None)   # EP over experts
    assert ffn["we_down"] == P(None, "model", None, None)
    assert ffn["router"] == P(None, None, None)


def test_indivisible_vocab_replicated():
    cfg = get_arch("mamba2-370m")  # vocab 50280 % 16 != 0
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    specs = param_pspecs(shapes, _mesh())
    assert specs["embed"] == P(None, None)


def test_batch_specs():
    specs = batch_pspecs(
        {
            "tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
            "positions": jax.ShapeDtypeStruct((3, 256, 4096), jnp.int32),
        },
        _mesh(),
    )
    assert specs["tokens"] == P(("data",), None)
    assert specs["positions"] == P(None, ("data",), None)


def test_batch_indivisible_replicates():
    specs = batch_pspecs({"tokens": jax.ShapeDtypeStruct((1, 64), jnp.int32)}, _mesh())
    assert specs["tokens"] == P(None, None)


def test_cache_specs_sp():
    # B=1 long-context: sequence sharded over (data, model)
    specs = cache_pspecs(
        {"k": jax.ShapeDtypeStruct((9, 1, 8, 524288, 128), jnp.bfloat16)}, _mesh()
    )
    assert specs["k"] == P(None, None, None, ("data", "model"), None)
    # B=128 decode: batch over data, seq over model
    specs = cache_pspecs(
        {"k": jax.ShapeDtypeStruct((80, 128, 8, 32768, 128), jnp.bfloat16)}, _mesh()
    )
    assert specs["k"] == P(None, ("data",), None, "model", None)
    # A model axis that divides the KV heads splits them, as it splits wq.
    class TP4:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 4}

    specs = cache_pspecs(
        {"k": jax.ShapeDtypeStruct((20, 16, 8, 2048, 128), jnp.bfloat16)}, TP4())
    assert specs["k"] == P(None, ("data",), "model", None, None)


MINI_DRYRUN = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.launch.steps import TrainOptions, init_train_state, make_train_step
    from repro.sharding import batch_pspecs, named, opt_pspecs, param_pspecs
    from repro.roofline.hlo_parse import analyze_module

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = dataclasses.replace(
        get_arch("yi-6b").reduced(), num_layers=4, num_microbatches=2,
        d_model=128, d_ff=256, vocab_size=512, num_heads=4, num_kv_heads=2,
        head_dim=32,
    )
    m = build_model(cfg)
    ps = jax.eval_shape(lambda: m.init_params(jax.random.PRNGKey(0)))
    specs = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    opts = TrainOptions()
    os_ = jax.eval_shape(lambda p: init_train_state(m, p, opts)[0], ps)
    with mesh:
        fn = jax.jit(
            make_train_step(m, opts),
            in_shardings=(named(mesh, param_pspecs(ps, mesh)),
                          named(mesh, opt_pspecs(os_, mesh)), None,
                          named(mesh, batch_pspecs(specs, mesh))),
        )
        comp = fn.lower(ps, os_, None, specs).compile()
    mc = analyze_module(comp.as_text())
    print(json.dumps({
        "dot_flops": mc.dot_flops,
        "collective_bytes": mc.collective_bytes,
        "num_whiles": mc.num_whiles,
    }))
    """
)


TP_NUMERICS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.configs import get_arch
    from repro.models import build_model, moe as M

    # dense TP blocks: shard_map vs plain path
    cfg = dataclasses.replace(get_arch("qwen2-72b").reduced(),
                              num_layers=2, d_model=64, num_heads=8,
                              num_kv_heads=2, head_dim=16, d_ff=128,
                              vocab_size=256, num_microbatches=1)
    m = build_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 256)}
    ref, _ = m.forward(params, batch)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh:
        tp, _ = jax.jit(m.forward)(params, batch)
        _ = jax.jit(jax.grad(lambda p: m.loss(p, batch)))(params)
    tp_err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - tp.astype(jnp.float32))))

    # MoE: shard_map dispatch vs grouped (no-mesh) dispatch
    mcfg = dataclasses.replace(get_arch("qwen3-moe-30b-a3b").reduced(),
                               capacity_factor=8.0, num_experts=4,
                               experts_per_token=2)
    p = M.init_moe(jax.random.PRNGKey(0), mcfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, mcfg.d_model)) * 0.3
    out_g, _ = M.moe_ffn(p, x, mcfg)
    with mesh:
        out_s, _ = jax.jit(lambda p, x: M.moe_ffn(p, x, mcfg))(p, x)
    moe_err = float(jnp.max(jnp.abs(out_g - out_s)))

    # per-matmul tp_mode paths (iteration-6 knob) still numerically exact
    from repro.core import blas
    xx = jax.random.normal(jax.random.PRNGKey(5), (4, 8, 64))
    ww = jax.random.normal(jax.random.PRNGKey(6), (64, 32))
    want = xx @ ww
    with mesh:
        row = jax.jit(lambda a, b: blas.matmul(a, b, tp_mode="row"))(xx, ww)
        col = jax.jit(lambda a, b: blas.matmul(a, b, tp_mode="col"))(xx, ww)
    tp_mm_err = max(
        float(jnp.max(jnp.abs(row - want))), float(jnp.max(jnp.abs(col - want)))
    )
    print(json.dumps({"tp_err": tp_err, "moe_err": moe_err,
                      "tp_mm_err": tp_mm_err}))
    """
)


TP_SERVE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, numpy as np
    from repro.configs import get_arch
    from repro.launch.serve import serve_batch
    from repro.launch.steps import init_params
    from repro.models import build_model

    import repro.launch.serve as serve

    # The spans' stats, by span name, of the last call.
    stats, span = {}, serve._span

    def recording(name, **kw):
        stats[name] = kw
        return span(name, **kw)

    serve._span = recording
    prompts = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8]]
    kw = dict(smoke=True, max_new_tokens=5, cache_len=16)
    one = serve_batch("yi-6b", prompts, **kw)
    one_stats, stats = stats, {}
    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = init_params(build_model(get_arch("yi-6b").reduced()), 0, mesh)
    wq = params["stack"]["mixer"]["wq"]
    four = serve_batch("yi-6b", prompts, params=params, mesh=mesh, **kw)
    print(json.dumps({
        "wq_shards": [s.data.shape[-1] for s in wq.addressable_shards],
        "wq_cols": wq.shape[-1],
        "same_tokens": bool((one.tokens == four.tokens).all()),
        "logit_err": float(np.abs(one.logits - four.logits).max()),
        "chips": [st["serve.batch"]["chips"] for st in (one_stats, stats)],
        "cache_bytes": [st["serve.compile.cache"]["bytes_per_device"]
                        for st in (one_stats, stats)],
    }))
    """
)


def test_serve_batch_on_model_mesh_matches_one_device():
    """serve_batch with a (data=1, model=4) mesh: weights land sharded and
    the tensor-parallel decode reproduces the one-device run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", TP_SERVE], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["wq_shards"] == [rec["wq_cols"] // 4] * 4, rec
    assert rec["same_tokens"], rec
    assert rec["logit_err"] < 1e-4, rec
    # The spans say how many chips the batch ran on, and one chip's cache.
    assert rec["chips"] == [1, 4], rec
    assert rec["cache_bytes"][0] == 4 * rec["cache_bytes"][1] > 0, rec


def test_tp_and_moe_shard_map_numerics():
    """shard_map TP blocks + explicit-collective MoE must match the plain
    single-device paths (fwd bitwise-ish; grads compile)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", TP_NUMERICS], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["tp_err"] < 3e-2, rec
    assert rec["moe_err"] < 2e-4, rec
    assert rec["tp_mm_err"] < 1e-4, rec


def test_mini_dryrun_subprocess():
    """Machinery check: an 8-device sharded train step lowers, compiles,
    and the per-device dot flops land within 2x of the analytic budget."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    B, S, L, d, dff, hq, hkv, hd, V = 8, 64, 4, 128, 256, 4, 2, 32, 512
    T = B * S
    fwd = (
        2 * T * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * dff) * L
        + 2 * T * d * V
        + 4 * B * hq * S * S * hd * L
    )
    per_dev_total = rec["dot_flops"] * 8  # 8 devices
    assert 2.0 * fwd < per_dev_total < 8.0 * fwd  # fwd+bwd+remat ≈ 3.8x
    assert rec["collective_bytes"] > 0
    assert rec["num_whiles"] >= 3  # mb scan + fwd/bwd layer scans
