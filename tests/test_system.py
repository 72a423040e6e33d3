"""End-to-end behaviour: train loop with checkpointing, serving, offload
accounting through a whole model — the paper's stack assembled."""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.serve as serve
from repro.configs import get_arch
from repro.configs.registry import register
from repro.core import engine, offload_policy, offload_trace
from repro.launch.train import train
from repro.launch.serve import serve_batch
from repro.models import build_model


def test_train_loop_end_to_end(tmp_path):
    losses = train(
        "yi-6b", smoke=True, steps=8, global_batch=4, seq_len=32,
        ckpt_dir=str(tmp_path), ckpt_every=4, log_every=100,
        num_microbatches=1,
    )
    assert len(losses) == 8
    assert losses[-1] < losses[0]
    # checkpoints landed
    assert any(p.name.startswith("step_") for p in tmp_path.iterdir())


def test_train_resumes_from_checkpoint(tmp_path):
    train("yi-6b", smoke=True, steps=6, global_batch=4, seq_len=32,
          ckpt_dir=str(tmp_path), ckpt_every=3, log_every=100,
          num_microbatches=1)
    # second call resumes at step 6 and runs nothing new -> returns []
    losses = train("yi-6b", smoke=True, steps=6, global_batch=4, seq_len=32,
                   ckpt_dir=str(tmp_path), ckpt_every=3, log_every=100,
                   num_microbatches=1)
    assert losses == []


def test_serve_batch_greedy():
    res = serve_batch(
        "yi-6b", [[1, 2, 3, 4], [5, 6]], smoke=True, max_new_tokens=4,
        cache_len=32,
    )
    assert res.tokens.shape == (2, 4)
    assert res.tokens_per_s > 0


def test_serve_batch_logits_match_forward():
    """Each token's logits from the prefill/decode loop equal the model's
    own forward pass over the same tokens (float32 reduced config)."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]]
    res = serve_batch("yi-6b", prompts, smoke=True, max_new_tokens=6,
                      cache_len=32)
    assert res.compile_s > 0
    assert res.logits.shape == (2, 6, 256)
    np.testing.assert_array_equal(res.logits.argmax(-1), res.tokens)
    model = build_model(get_arch("yi-6b").reduced())
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    seq = np.concatenate([np.asarray(prompts), res.tokens[:, :-1]], axis=1)
    ref, _ = model.forward(params, {"tokens": jnp.asarray(seq)})
    np.testing.assert_allclose(res.logits, np.asarray(ref)[:, 7:], atol=1e-4)


def _smoke_arch(dtype: str) -> str:
    """The yi-6b smoke config served in ``dtype``, registered once."""
    cfg = get_arch("yi-6b").reduced()
    name = f"{cfg.name}-{dtype}"
    try:
        get_arch(name)
    except KeyError:
        register(dataclasses.replace(cfg, name=name, dtype=dtype))
    return name


_run_decode = serve._run_decode


def _host_pick(step, params, cache, logits, tokens=None, **kw):
    """The host-pick loop, fed by the picking step with its tokens unread."""
    def plain(*args):
        logits, _, cache = step(*args)
        return logits, cache

    return _run_decode(plain, params, cache, logits, **kw)


def _serve_counted(monkeypatch, arch, prompts, **kw):
    """One ``serve_batch`` call and the number of steps it ran."""
    runs = []

    def counted(run):
        def wrapped(step, *args, **kwargs):
            def step_once(*a):
                runs.append(1)
                return step(*a)
            return run(step_once, *args, **kwargs)
        return wrapped

    with monkeypatch.context() as m:
        m.setattr(serve, "_run_prefill", counted(serve._run_prefill))
        m.setattr(serve, "_run_decode", counted(serve._run_decode))
        res = serve_batch(arch, prompts, smoke=False, **kw)
    return res, len(runs)


@pytest.mark.parametrize("dtype,prompts", [
    ("float32", [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8]]),
    ("bfloat16", [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8]]),
    ("float32", [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7], [1, 8, 2, 8, 1]]),
    ("bfloat16", [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7], [1, 8, 2, 8, 1]]),
])
def test_greedy_device_pick_matches_host_pick(monkeypatch, dtype, prompts):
    """A greedy batch picks on the device; the tokens and logits it serves
    are the host-pick loop's, bit for bit, from as many steps."""
    arch, new = _smoke_arch(dtype), 7
    kw = dict(max_new_tokens=new, cache_len=32)
    dev, dev_steps = _serve_counted(monkeypatch, arch, prompts, **kw)
    monkeypatch.setattr(serve, "_run_decode", _host_pick)
    host, host_steps = _serve_counted(monkeypatch, arch, prompts, **kw)
    assert type(dev.logits) is np.ndarray
    assert dev.logits.dtype == np.float32
    assert dev.logits.shape == (len(prompts), new, 256)
    np.testing.assert_array_equal(dev.tokens, host.tokens)
    np.testing.assert_array_equal(dev.logits, host.logits)
    np.testing.assert_array_equal(dev.logits.argmax(-1), dev.tokens)
    assert dev_steps == host_steps == max(map(len, prompts)) + new


@pytest.mark.parametrize("pick", ["device", "host"])
def test_no_read_back_after_decode_time_stops(monkeypatch, pick):
    """Every NumPy call on a device array in the serve loop, each token's
    logits and tokens, comes before the clock reading that ends
    ``decode_s``: the time to the first token (the call's time less
    ``decode_s``) holds none of the read-back."""
    if pick == "host":
        monkeypatch.setattr(serve, "_run_decode", _host_pick)
    events, new = [], 5

    class RecordingNumpy:
        """NumPy, recording each call given a device array."""

        def __getattr__(self, name):
            f = getattr(np, name)
            if not callable(f) or isinstance(f, type):
                return f

            def call(*args, **kwargs):
                events.extend(("read", a.shape) for a in args
                              if isinstance(a, jax.Array))
                return f(*args, **kwargs)
            return call

    def clock():
        events.append(("clock", None))
        return time_module.perf_counter()

    time_module = serve.time
    monkeypatch.setattr(serve, "np", RecordingNumpy())
    monkeypatch.setattr(serve, "time", SimpleNamespace(perf_counter=clock))
    res = serve_batch("yi-6b", [[1, 2, 3], [4, 5, 6]], smoke=True,
                      max_new_tokens=new, cache_len=16)
    monkeypatch.undo()
    assert [shape for kind, shape in events if kind == "read"].count(
        (2, 256)) == new
    # The call's last clock reading ends decode_s, and nothing follows it.
    assert events[-1] == ("clock", None), events[-4:]
    assert type(res.logits) is np.ndarray and res.decode_s > 0


def test_serve_rejects_encoder():
    with pytest.raises(ValueError):
        serve_batch("hubert-xlarge", [[1, 2]], smoke=True)


def test_whole_model_offload_trace():
    """The paper's instrumentation through a full forward pass: every
    matmul in the model is visible at the BLAS seam with regions."""
    cfg = get_arch("yi-6b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    engine().reset()
    with offload_policy(mode="auto", platform="tpu-v5e", resident_fraction=1.0):
        with offload_trace() as t:
            model.forward(params, batch)
    ops = t.by_op()
    assert "gemm" in ops or "attention" in ops
    # layer-scan records carry the structural multiplier
    assert any(r.count == cfg.num_layers for r in t.records)
    assert t.total_flops() > 0


def test_offload_crossover_matches_paper_story():
    """Small problems stay on host, large ones offload (auto policy)."""
    from repro.core import blas

    engine().reset()
    with offload_policy(mode="auto", platform="hesoc-vcu128"):
        with offload_trace() as t:
            blas.gemm(jnp.ones((16, 16)), jnp.ones((16, 16)))
            blas.gemm(jnp.ones((512, 512)), jnp.ones((512, 512)))
    small, large = t.records
    assert small.backend == "host"
    assert large.backend.startswith("device")


GREEDY_ON_MESH = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, json
    import jax, numpy as np
    import repro.launch.serve as serve
    from repro.configs import get_arch
    from repro.configs.registry import register
    from repro.launch.steps import init_params
    from repro.models import build_model

    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1], [8, 2, 8, 1, 8],
               [2, 8, 4]]
    run_decode = serve._run_decode

    def host_pick(step, params, cache, logits, tokens=None, **kw):
        def plain(*args):
            logits, _, cache = step(*args)
            return logits, cache
        return run_decode(plain, params, cache, logits, **kw)

    out = {}
    for dtype in ("float32", "bfloat16"):
        cfg = get_arch("yi-6b").reduced()
        cfg = register(dataclasses.replace(cfg, name=cfg.name + dtype,
                                           dtype=dtype))
        params = init_params(build_model(cfg), 0, mesh)
        kw = dict(smoke=False, max_new_tokens=6, cache_len=16,
                  params=params, mesh=mesh)
        serve._run_decode = run_decode
        dev = serve.serve_batch(cfg.name, prompts, **kw)
        serve._run_decode = host_pick
        host = serve.serve_batch(cfg.name, prompts, **kw)
        out[dtype] = {
            "same_tokens": bool(np.array_equal(dev.tokens, host.tokens)),
            "same_logits": bool(np.array_equal(dev.logits, host.logits)),
            "logits": [type(dev.logits).__name__, str(dev.logits.dtype),
                       list(dev.logits.shape)],
        }
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def greedy_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", GREEDY_ON_MESH], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_greedy_device_pick_on_model_mesh(greedy_on_mesh, dtype):
    """On a (data=1, model=4) mesh the step's argmax runs over the
    vocabulary-split head: the tokens and logits are the host loop's."""
    rec = greedy_on_mesh[dtype]
    assert rec["same_tokens"] and rec["same_logits"], rec
    assert rec["logits"] == ["ndarray", "float32", [4, 6, 256]], rec
