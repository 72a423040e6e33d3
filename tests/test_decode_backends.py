"""Decode attention backends: flash-decode kernel == masked-math path.

The decode path dispatches through the seam (``engine().launch``): the
default host form is shardable masked math; with the Pallas policy the
``flash_decode`` kernel runs instead (interpret mode here). Both must
produce the same logits across cache regimes (filled, partially filled,
rolling SWA, per-layer windows). The host form groups the query heads of
each key/value head: it equals the repeat form of ``attention_math`` and
never widens K/V to every query head."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import engine, offload_policy
from repro.models import build_model


@pytest.mark.parametrize("arch", ["yi-6b", "h2o-danube-1.8b", "gemma3-27b"])
def test_decode_pallas_matches_masked(arch):
    cfg = get_arch(arch).reduced()
    m = build_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0, cfg.vocab_size)
    engine().reset()

    def run():
        cache = m.init_decode_cache(2, 16)
        logits = None
        for t in range(10):
            logits, cache = m.decode_step(
                params, cache, tokens[:, t : t + 1], jnp.int32(t)
            )
        return logits

    base = run()
    with offload_policy(mode="device", use_pallas=True, interpret=True):
        pall = run()
    err = float(jnp.max(jnp.abs(base.astype(jnp.float32) - pall.astype(jnp.float32))))
    assert err < 2e-2, err


def test_decode_rolling_wrap_consistent():
    """SWA rolling cache past the wrap point: both backends agree."""
    cfg = get_arch("h2o-danube-1.8b").reduced()  # window 8
    m = build_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 14), 0, cfg.vocab_size)
    engine().reset()

    def run():
        cache = m.init_decode_cache(1, 32)  # rolling buffer = window (8)
        logits = None
        for t in range(14):                 # wraps at t=8
            logits, cache = m.decode_step(
                params, cache, tokens[:, t : t + 1], jnp.int32(t)
            )
        return logits

    base = run()
    with offload_policy(mode="device", use_pallas=True, interpret=True):
        pall = run()
    err = float(jnp.max(jnp.abs(base.astype(jnp.float32) - pall.astype(jnp.float32))))
    assert err < 2e-2, err


@pytest.mark.parametrize(
    "lo,hi", [(0, 5), (3, 11), (6, 6)],
    ids=["partly-filled", "swa-range", "fully-masked"],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group", [1, 2, 8])
def test_grouped_decode_host_matches_repeat_form(group, dtype, lo, hi):
    """The decode host form groups the query heads of each key/value head;
    it equals attention_math, which repeats K/V to every query head."""
    from repro.core import blas

    b, hkv, s, d = 2, 2, 16, 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(group), 3)
    q = jax.random.normal(kq, (b, hkv * group, 1, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.float32).astype(dtype)
    got = blas._decode_attn_host(q, k, v, jnp.int32(lo), jnp.int32(hi))
    slots = jnp.arange(s)
    want = blas.attention_math(
        q, k, v, causal=False, kv_mask=(slots >= lo) & (slots < hi)
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got), np.asarray(want)
    if lo == hi:
        assert not got.any()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    else:
        ulp = np.abs(np.spacing(want)).astype(np.float32)
        err = np.abs(got.astype(np.float32) - want.astype(np.float32))
        assert (err <= ulp).all(), float(err.max())


def _shapes(hlo_line: str):
    """Element counts and dims of each array an HLO instruction produces."""
    out = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) [\w\-]+\(", hlo_line)
    for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", out.group(1) if out else ""):
        shape = [int(x) for x in dims.split(",") if x]
        yield int(np.prod(shape)), shape


def test_grouped_decode_step_never_repeats_kv():
    """The compiled decode step of a GQA model (8 query heads on 2 key/value
    heads) holds no array as large as K or V repeated to every query head:
    attention reads the cache as stored."""
    from repro.launch.steps import make_serve_step

    cfg = dataclasses.replace(get_arch("yi-6b").reduced(), num_heads=8,
                              num_kv_heads=2)
    m = build_model(cfg)
    b, s = 2, 64
    params = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: m.init_decode_cache(b, s))
    hlo = jax.jit(make_serve_step(m)).lower(
        params, cache, jax.ShapeDtypeStruct((b, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    ).compile().as_text()
    attention = [line for line in hlo.splitlines()
                 if re.search(r'op_name="[^"]*/attention/', line)]
    shapes = [sh for line in attention for sh in _shapes(line)]
    cache_size = b * cfg.num_kv_heads * s * cfg.head_dim
    # The step updates and reads the cache under the attention scope ...
    assert any(n == cache_size and s in dims for n, dims in shapes)
    # ... and never widens it to the query heads.
    repeated = cache_size * cfg.num_heads // cfg.num_kv_heads
    assert not [dims for n, dims in shapes if n >= repeated and s in dims]
