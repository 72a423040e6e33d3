"""End-to-end behaviour: train loop with checkpointing, serving, offload
accounting through a whole model — the paper's stack assembled."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
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
