"""Check that the main path runs on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: the serving and hnp phases
    python chip_smoke.py --chips 4   # four chips: tensor-parallel decode only

Run it from the repository root on a machine with a TPU.  Without one it
exits non-zero before doing any work; it never falls back to the CPU.

* serving: yi-6b at its published widths (32 layers, d_model 4096, GQA
  32/4, vocab 64000, bf16 weights from a seed) serves a batch of 4 seeded
  16-token prompts through ``serve_batch``: 16 greedy tokens each, from a
  2048-slot cache.  The logits the decode path picked each token from are
  checked against ``Model.forward`` run over the same tokens on the chip.
* hnp: ``tanh(x @ w1 + b1) @ w2`` at n=4096 in bf16, written against
  ``repro.hnp`` and lowered onto the Pallas GEMM kernel, checked against a
  float32 ``jnp.dot`` at HIGHEST precision.  Every GEMM must have run as
  ``device-pallas``.
* ``--chips 4``: the same serving run with the weights and cache sharded
  over a (data=1, model=4) mesh, checked against a one-chip run on the first
  device.  Each device must hold about a quarter of the weight bytes.

Every phase asserts its own checks; any failure ends the script with a
traceback and a non-zero exit.  Times are wall-clock on the chip, with
compilation reported apart; they are a smoke check, not a benchmark.  The
last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-6b"
SEED = 0  # weights, prompts and hnp operands are all drawn from it
BATCH, PROMPT_LEN, MAX_NEW, CACHE_LEN = 4, 16, 16, 2048
# bf16 weights of yi-6b at its published widths: 6.06e9 parameters.
PARAM_BYTES_RANGE = (12.0e9, 12.3e9)
# Two runs of the same bf16 model, in different orders of summation, may
# differ by this much in any logit (logits here are about N(0, 1), and one
# bf16 ulp at 4 is 0.03).  A token two runs pick differently is allowed
# only where the reference's top two logits lie within 2 * LOGIT_ATOL.
LOGIT_ATOL = 0.25
# Frobenius-norm error of the bf16 hnp chain relative to float32: bf16
# rounding of h and of y alone gives about 3e-3.
HNP_REL_ERR = 1e-2
HNP_N = 4096


def _say(msg: str) -> None:
    print(msg, flush=True)


def _require(ok, msg) -> None:
    """A check that ``python -O`` cannot strip, unlike ``assert``."""
    if not ok:
        raise AssertionError(msg)


def _prompts(seed: int, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, size=(BATCH, PROMPT_LEN)).tolist()


def check_agreement(what, ref, got, got_tokens, *, teacher_forced):
    """Compare two runs' logits ``(B, T, V)`` and the tokens ``got`` picked.

    With ``teacher_forced`` both runs saw the same tokens at every position.
    Otherwise each ran greedily on its own picks, so a row is compared only
    up to and including its first differing pick.
    """
    ref_top = ref.argmax(-1)
    differ = ref_top != got_tokens
    b, t, _ = ref.shape
    valid = np.ones((b, t), bool)
    if not teacher_forced:
        first = np.where(differ.any(1), differ.argmax(1), t)
        valid = np.arange(t)[None, :] <= first[:, None]
    err = float(np.abs(ref - got).max(-1)[valid].max())
    gap = ref.max(-1) - np.take_along_axis(ref, got_tokens[..., None], -1)[..., 0]
    mismatched = differ & valid
    agree = 1.0 - mismatched.sum() / valid.sum()
    _say(f"{what}: logits max-abs {err:.6g} over {int(valid.sum())} positions "
         f"(tolerance {LOGIT_ATOL}); top-1 agreement {agree:.6g} "
         f"({int(mismatched.sum())} differing picks, reference top-2 gaps "
         f"{np.round(gap[mismatched], 4).tolist()})")
    _require(err <= LOGIT_ATOL, f"{what}: logits differ by {err}")
    _require(np.all(gap[mismatched] <= 2 * LOGIT_ATOL),
             f"{what}: a differing pick where the reference had a clear top-1")


def _param_bytes(params) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(params))


def _serve(params, prompts, *, mesh=None, seed=0):
    from repro.launch.serve import serve_batch

    res = serve_batch(
        ARCH, prompts, smoke=False, max_new_tokens=MAX_NEW,
        cache_len=CACHE_LEN, seed=seed, params=params, mesh=mesh,
    )
    _say(f"serve_batch wall-clock (s): compile {res.compile_s:.6g}, "
         f"prefill {res.prefill_s:.6g} ({PROMPT_LEN} steps), "
         f"decode {res.decode_s:.6g} ({MAX_NEW} steps)")
    _say(f"generated token ids: {res.tokens.tolist()}")
    _require(res.tokens.shape == (BATCH, MAX_NEW), res.tokens.shape)
    _require(np.isfinite(res.logits).all(), "non-finite logits")
    return res


def serving_phase(device, seed: int) -> None:
    import jax

    from repro.configs import get_arch
    from repro.launch.steps import init_params
    from repro.models import build_model

    cfg = get_arch(ARCH)
    model = build_model(cfg)
    _say(f"[serving] {cfg.name}: {cfg.num_layers} layers, d_model "
         f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, vocab "
         f"{cfg.vocab_size}, {cfg.dtype}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model, seed))
    n_bytes = _param_bytes(params)
    _say(f"parameter bytes: {n_bytes} (built under jit in "
         f"{time.perf_counter() - t0:.6g} s wall-clock, compile included)")
    lo, hi = PARAM_BYTES_RANGE
    _require(lo < n_bytes < hi, f"parameter bytes {n_bytes}")
    prompts = _prompts(seed, cfg.vocab_size)
    res = _serve(params, prompts, seed=seed)
    stats = device.memory_stats() or {}
    _say(f"device peak_bytes_in_use after serving: "
         f"{stats.get('peak_bytes_in_use', 'not reported')}")

    # The model's own forward over the same tokens, on the same chip.
    seq = np.concatenate([np.asarray(prompts), res.tokens[:, :-1]], axis=1)
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    ref = np.asarray(fwd(params, seq)[:, PROMPT_LEN - 1:], np.float32)
    check_agreement("decode vs forward", ref, res.logits, res.tokens,
                    teacher_forced=True)


def hnp_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    import repro.hnp as hnp
    from repro.core import engine, offload_policy, offload_trace

    n = HNP_N
    _say(f"[hnp] tanh(x @ w1 + b1) @ w2, n={n}, bf16, Pallas GEMM")
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf16 = jnp.bfloat16
    x = jax.random.normal(k[0], (n, n), bf16)
    w1 = (jax.random.normal(k[1], (n, n)) * n ** -0.5).astype(bf16)
    b1 = (jax.random.normal(k[2], (n,)) * 0.1).astype(bf16)
    w2 = (jax.random.normal(k[3], (n, n)) * n ** -0.5).astype(bf16)

    def chain():
        return hnp.asnumpy(hnp.tanh(hnp.linear(hnp.array(x), w1, b1)) @ w2)

    engine().reset()
    runs = []
    with offload_policy(mode="device", use_pallas=True):
        with offload_trace() as trace:
            for _ in range(2):
                t0 = time.perf_counter()
                y = chain()
                runs.append(time.perf_counter() - t0)
    _say(f"hnp chain wall-clock (s), to a host array: first {runs[0]:.6g} "
         f"(compile included), second {runs[1]:.6g}")
    gemms = [r for r in trace.records if r.op == "gemm"]
    backends = sorted({r.backend for r in gemms})
    _say(f"hnp GEMM launches: {len(gemms)}, backends {backends}")
    _require(len(gemms) == 4 and backends == ["device-pallas"],
             f"GEMM records: {trace.records}")

    hi = jax.lax.Precision.HIGHEST
    f32 = [a.astype(jnp.float32) for a in (x, w1, b1, w2)]
    h = jnp.tanh(jnp.dot(f32[0], f32[1], precision=hi) + f32[2])
    ref = np.asarray(jnp.dot(h, f32[3], precision=hi))
    y = np.asarray(y, np.float32)
    _require(y.shape == (n, n) and np.isfinite(y).all(), "bad hnp output")
    rel = float(np.linalg.norm(y - ref) / np.linalg.norm(ref))
    rel_max = float(np.abs(y - ref).max() / np.abs(ref).max())
    _say(f"hnp vs float32 HIGHEST: relative error {rel:.6g} (Frobenius, bound "
         f"{HNP_REL_ERR}), max-abs over max-abs {rel_max:.6g}")
    _require(rel <= HNP_REL_ERR, f"hnp relative error {rel}")


def four_chip_phase(seed: int) -> None:
    import jax
    from jax.sharding import AxisType

    from repro.configs import get_arch
    from repro.launch.steps import init_params
    from repro.models import build_model

    devices = jax.devices()
    _require(len(devices) >= 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    cfg = get_arch(ARCH)
    model = build_model(cfg)
    prompts = _prompts(seed, cfg.vocab_size)

    # First on one chip; its weights are freed before the mesh is filled.
    _say(f"[one chip] {cfg.name} on {devices[0]}")
    one = _serve(init_params(model, seed), prompts, seed=seed)

    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices[:4])
    _say(f"[four chips] {cfg.name} over a (data=1, model=4) mesh")
    params = jax.block_until_ready(init_params(model, seed, mesh))
    total = _param_bytes(params)
    held = {d: 0 for d in devices[:4]}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    shares = [held[d] / total for d in devices[:4]]
    _say(f"weight bytes: total {total}, per device "
         f"{[held[d] for d in devices[:4]]} (shares {np.round(shares, 4).tolist()})")
    _require(all(0.24 <= s <= 0.30 for s in shares), f"weight shares {shares}")
    four = _serve(params, prompts, mesh=mesh, seed=seed)
    for d in devices[:4]:
        stats = d.memory_stats() or {}
        _say(f"{d} peak_bytes_in_use: "
             f"{stats.get('peak_bytes_in_use', 'not reported')}")
    same = int((one.tokens == four.tokens).sum())
    _say(f"tokens equal to the one-chip run: {same}/{one.tokens.size}")
    check_agreement("four chips vs one chip", one.logits, four.logits,
                    four.tokens, teacher_forced=False)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {device.platform!r}")

    from repro.launch import use_compile_cache

    use_compile_cache()
    cache_dir = Path(jax.config.jax_compilation_cache_dir)
    warm = cache_dir.is_dir() and any(cache_dir.iterdir())
    _say(f"device: {device.device_kind}, {len(jax.devices())} visible; "
         f"compile cache {cache_dir} ({'warm' if warm else 'cold'})")
    if args.chips == 4:
        four_chip_phase(SEED)
    else:
        serving_phase(device, SEED)
        hnp_phase(SEED)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)


if __name__ == "__main__":
    main()
