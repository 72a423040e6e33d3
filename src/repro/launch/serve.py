"""Batched serving driver: prefill + decode with a KV/state cache.

A deliberately small but real serving loop: requests arrive with prompts,
are padded into a batch, prefilled (full forward building the cache via
teacher-forced decode), then decoded token-by-token with greedy/temperature
sampling.  The same ``serve_step`` is what the decode dry-run cells lower.
A greedy batch picks on the device: its step also returns the argmax of its
logits, the next step is sent with those tokens, and each token's logits come
to the host while the device runs the step after it.

``serve_cluster`` scales the loop to the multi-PMCA engine with placement
as a first-class concept: each batch's prefill is placed by the active
scheduler, and the KV cache it builds is **pinned** there as a
:class:`~repro.core.hero.DeviceHandle` (a device-residency token).  Decode
placement then goes through ``cluster.assign(..., handle=...)`` — the
``cost-aware`` scheduler sees the residency credit and routes the decode
batch to the device holding its cache (skipping the modeled copy region);
placement-oblivious schedulers (``round-robin``) do not, and pay a modeled
``d2d_copy`` migration when decode lands elsewhere.  The un-pinned baseline
(``pin_caches=False``) models today's common deployment: the cache drains
to host DRAM after prefill and decode pays a full host re-stage.  Cluster
throughput is the modeled-parallel makespan — the max device lane, not the
sum.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.core import cost_model as cm
from repro.core.hero import DeviceHandle, engine
from repro.launch import costing, use_compile_cache
from repro.launch.steps import init_params, make_serve_step
from repro.models import build_model
from repro.sharding import cache_pspecs, named


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray          # (B, max_new)
    logits: np.ndarray          # (B, max_new, V) float32: each token's source
    prefill_s: float
    decode_s: float
    tokens_per_s: float
    # Seconds spent compiling the step, timed apart from prefill.  0.0 where
    # the step compiles inside the first prefill call (serve_cluster).
    compile_s: float = 0.0
    # The compiled step the batch ran on (None where no step is compiled
    # apart, as in serve_cluster).  Its ``as_text()`` names each op of a
    # profile and carries the op's ``jax.named_scope`` path (models.SCOPES).
    step: Any = None


# Host spans in the profiler's trace; with no profiler running, a span only
# checks that none is.  Each ``serve.batch`` carries a process-wide call
# number, which the spans nested in it share.
_span = jax.profiler.TraceAnnotation
_batch_ids = itertools.count()


def _bytes_per_device(shapes, shardings=None) -> int:
    """Bytes one device holds of a pytree of ``ShapeDtypeStruct``s laid out
    by ``shardings`` (a matching tree of ``NamedSharding``s; None: whole)."""
    leaves = jax.tree_util.tree_leaves(shapes)
    shards = ([None] * len(leaves) if shardings is None
              else jax.tree_util.tree_leaves(shardings))
    return sum(
        int(np.prod(s.shard_shape(x.shape) if s else x.shape))
        * jnp.dtype(x.dtype).itemsize
        for x, s in zip(leaves, shards)
    )


def _pick_on_device(step):
    """``step`` returning also the greedy pick of its logits: ``(logits,
    tokens, cache)``, ``tokens`` the (B, 1) int32 argmax.  ``jnp.argmax``
    takes the first of equal values, as NumPy's does over the same values in
    float32 (exact for bfloat16), so the tokens are the host pick's."""

    def serve_step(params, cache, tokens, cache_index):
        logits, cache = step(params, cache, tokens, cache_index)
        return (logits, jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32),
                cache)

    return serve_step


def _run_prefill(serve_step, params, cache, prompts: List[List[int]]):
    """Prefill token-by-token through the decode path (correct for rolling
    caches and hybrid state; a fused prefill kernel is a perf option).

    Returns the last step's outputs but the cache, as a list (its logits,
    and its tokens where the step picks), the cache and the seconds."""
    max_prompt = max(len(p) for p in prompts)
    t0 = time.perf_counter()
    head = None
    with _span("serve.prefill", steps=max_prompt):
        for t in range(max_prompt):
            # A fresh host buffer every step: the step may still be reading
            # the last one (on the CPU, jnp.asarray can alias numpy memory).
            tok = np.array([[p[t] if t < len(p) else 0] for p in prompts],
                           np.int32)
            *head, cache = serve_step(
                params, cache, jnp.asarray(tok), jnp.int32(t)
            )
        with _span("serve.prefill.wait"):
            jax.block_until_ready(head)
    return head, cache, time.perf_counter() - t0


def _run_decode(
    serve_step, params, cache, logits, tokens=None, *, start_pos: int,
    max_new_tokens: int, temperature: float, seed: int,
):
    """Greedy/temperature sampling loop from a prefilled cache.

    With ``tokens`` (the device's pick of ``logits``, from a step made by
    :func:`_pick_on_device`) the greedy loop of :func:`_decode_device_pick`
    runs; without, the host copies, picks and sends each token."""
    if tokens is not None:
        return _decode_device_pick(serve_step, params, cache, logits, tokens,
                                   start_pos=start_pos,
                                   max_new_tokens=max_new_tokens)
    bsz = logits.shape[0]
    rng = np.random.default_rng(seed)
    out = np.zeros((bsz, max_new_tokens), np.int32)
    seen = []
    copied = logits.nbytes  # device bytes each token's copy reads
    t0 = time.perf_counter()
    for i in range(max_new_tokens):
        # np.asarray would wait too: the wait is its own span so that the
        # copy's span times the copy alone.
        with _span("serve.decode.wait"):
            jax.block_until_ready(logits)
        with _span("serve.decode.copy", bytes=copied):
            lf = np.asarray(logits, np.float32)
        seen.append(lf)
        with _span("serve.decode.pick"):
            if temperature > 0:
                p = np.exp((lf - lf.max(-1, keepdims=True)) / temperature)
                p /= p.sum(-1, keepdims=True)
                nxt = np.array(
                    [rng.choice(lf.shape[-1], p=p[b]) for b in range(bsz)],
                    np.int32,
                )
            else:
                nxt = lf.argmax(-1).astype(np.int32)
            out[:, i] = nxt
        with _span("serve.decode.dispatch"):
            logits, cache = serve_step(
                params, cache, jnp.asarray(nxt[:, None]),
                jnp.int32(start_pos + i),
            )
    jax.block_until_ready(cache)
    with _span("serve.decode.stack", bytes=sum(lf.nbytes for lf in seen)):
        seen = np.stack(seen, axis=1)
    return out, seen, cache, time.perf_counter() - t0


def _decode_device_pick(serve_step, params, cache, logits, tokens, *,
                        start_pos: int, max_new_tokens: int):
    """Greedy decode with the pick in the step.  Step ``i + 1`` is sent with
    the tokens step ``i`` returned before the host reads step ``i``, so the
    host waits for, copies and records each token while the device runs the
    step after it.  The logits land in one float32 buffer as they come, and
    the time returned ends when the last of them is there and the cache is
    ready: nothing is left to read after it."""
    bsz, vocab = logits.shape
    out = np.zeros((bsz, max_new_tokens), np.int32)
    copied = logits.nbytes  # device bytes each token's copy reads
    t0 = time.perf_counter()
    seen = np.empty((bsz, max_new_tokens, vocab), np.float32)
    # The first step is sent before the loop; each token's dispatch sends
    # the step after the one the device runs (none after the last token).
    # A NumPy position goes to the device with the call, faster than a
    # jnp scalar, which the call must then place.
    nxt = (serve_step(params, cache, tokens, np.int32(start_pos))
           if max_new_tokens else None)
    for i in range(max_new_tokens):
        with _span("serve.decode.wait"):
            jax.block_until_ready((logits, tokens))
        with _span("serve.decode.copy", bytes=copied):
            seen[:, i] = np.asarray(logits)
        with _span("serve.decode.pick"):
            out[:, i] = np.asarray(tokens)[:, 0]
        with _span("serve.decode.dispatch"):
            logits, tokens, cache = nxt
            if i + 1 < max_new_tokens:
                # Both start for the host as soon as their step ends.
                logits.copy_to_host_async()
                tokens.copy_to_host_async()
                nxt = serve_step(params, cache, tokens,
                                 np.int32(start_pos + i + 1))
    # The last step's cache; its logits and tokens are not read.
    with _span("serve.decode.stack", bytes=0):
        jax.block_until_ready(cache)
    return out, seen, cache, time.perf_counter() - t0


def serve_batch(
    arch: str,
    prompts: List[List[int]],
    *,
    smoke: bool = True,
    max_new_tokens: int = 16,
    cache_len: int = 128,
    temperature: float = 0.0,
    seed: int = 0,
    params=None,
    mesh=None,
) -> ServeResult:
    """Prefill and decode one batch of prompts.

    ``params`` defaults to weights built from ``seed`` (:func:`init_params`).
    With a ``mesh`` (axes ``data`` and ``model``) the weights and the cache
    are sharded by ``param_pspecs``/``cache_pspecs``, and the step traces
    under the mesh, so the matmuls take their tensor-parallel plans.

    Under a profiler (``jax.profiler.trace``) the call leaves host spans
    named ``serve.*`` in its trace (README, "Tracing a serving run").
    """
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    if not cfg.embed_inputs:
        raise ValueError("serving driver targets token-input archs")
    if cfg.is_encoder:
        raise ValueError("encoder-only arch has no decode step")
    bsz = len(prompts)
    max_prompt = max(len(p) for p in prompts)
    # Greedy batches pick on the device (_decode_device_pick).
    on_device = temperature == 0
    with _span("serve.batch", batch=next(_batch_ids), requests=bsz,
               prompt_len=max_prompt, new_tokens=max_new_tokens,
               cache_len=cache_len, chips=1 if mesh is None else mesh.size,
               pick="device" if on_device else "host"):
        model = build_model(cfg)
        if params is None:
            params = init_params(model, seed, mesh)

        def new_cache():
            return model.init_decode_cache(bsz, cache_len)

        step_kw = {}
        with _span("serve.compile"):
            cache_shapes = jax.eval_shape(new_cache)
            c_shard = (None if mesh is None else
                       named(mesh, cache_pspecs(cache_shapes, mesh)))
            with _span("serve.compile.cache",
                       bytes_per_device=_bytes_per_device(cache_shapes,
                                                          c_shard)):
                if mesh is None:
                    cache = jax.jit(new_cache)()
                else:
                    cache = jax.jit(new_cache, out_shardings=c_shard)()
                    p_shard = jax.tree_util.tree_map(lambda x: x.sharding,
                                                     params)
                    rep = named(mesh, jax.sharding.PartitionSpec())
                    outs = ((rep, rep, c_shard) if on_device
                            else (rep, c_shard))
                    step_kw = dict(in_shardings=(p_shard, c_shard, rep, rep),
                                   out_shardings=outs)
            t0 = time.perf_counter()
            in_mesh = contextlib.nullcontext() if mesh is None else mesh
            step = make_serve_step(model)
            if on_device:
                step = _pick_on_device(step)
            with _span("serve.compile.step"), in_mesh:
                serve_step = jax.jit(
                    step, donate_argnums=(1,), **step_kw
                ).lower(
                    params, cache, jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                ).compile()
            compile_s = time.perf_counter() - t0

        head, cache, prefill_s = _run_prefill(serve_step, params, cache,
                                              prompts)
        out, seen, cache, decode_s = _run_decode(
            serve_step, params, cache, *head, start_pos=max_prompt,
            max_new_tokens=max_new_tokens, temperature=temperature, seed=seed,
        )
    return ServeResult(
        tokens=out,
        logits=seen,
        prefill_s=prefill_s,
        decode_s=decode_s,
        tokens_per_s=bsz * max_new_tokens / max(decode_s, 1e-9),
        compile_s=compile_s,
        step=serve_step,
    )


@dataclasses.dataclass
class ClusterServeResult:
    """One multi-device serving round."""

    results: List[ServeResult]            # one per request batch
    placements: List[int]                 # batch index -> decode device id
    prefill_placements: List[int]         # batch index -> prefill device id
    # Device holding each cache when its decode batch was *placed*
    # (-1 = unstaged to host); differs from `placements` exactly when the
    # scheduler strayed from the cache and a move was paid.
    cache_devices: List[int]
    per_device_s: Dict[int, float]        # modeled busy seconds per device
    makespan_s: float                     # modeled wall-clock (max lane)
    total_tokens: int
    tokens_per_s: float                   # modeled cluster throughput
    d2d_s: float = 0.0                    # modeled cache-migration seconds
    restage_s: float = 0.0                # modeled host re-stage seconds


def _cache_nbytes(cache) -> float:
    """Total bytes of the KV/state cache pytree (the pinned buffer size)."""
    return float(sum(
        leaf.size * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(cache)
        if hasattr(leaf, "size") and hasattr(leaf, "dtype")
    ))


def _prefill_cost(prompts: List[List[int]], cfg) -> cm.OpCost:
    """Batch-path adapter over the shared costed-step helper
    (:mod:`repro.launch.costing`): every prompt token runs the stack's
    GEMMs, collapsed to one cost the scheduler can weigh."""
    return costing.prefill_cost(sum(len(p) for p in prompts), cfg)


def _decode_cost(
    bsz: int, max_new_tokens: int, cache_bytes: float, cfg
) -> cm.OpCost:
    """Batch-path adapter over :func:`repro.launch.costing.decode_cost` —
    the whole decode phase's tokens with the KV cache riding staged bytes,
    the asymmetry the ``cost-aware`` scheduler keys on to route decode
    batches to the cache-holding device."""
    return costing.decode_cost(bsz * max_new_tokens, cache_bytes, cfg)


def serve_cluster(
    arch: str,
    request_batches: List[List[List[int]]],
    *,
    smoke: bool = True,
    max_new_tokens: int = 16,
    cache_len: int = 128,
    temperature: float = 0.0,
    seed: int = 0,
    pin_caches: bool = True,
    forward_mode: Optional[str] = None,
) -> ClusterServeResult:
    """Serve concurrent request batches across the HeroCluster's devices.

    Two placement rounds per batch, both through the active scheduler:

    1. **Prefill** is placed by workload (prompt tokens x stack GEMMs) and
       executed with the cluster pinned to its lane; the KV cache it builds
       is pinned there as a :class:`DeviceHandle` (``pin_caches=True``) or
       drained back to host DRAM (``pin_caches=False``).
    2. **Decode** is placed with ``assign(..., handle=...)``: a
       placement-affine scheduler routes it to the cache holder (no cache
       movement); landing elsewhere costs a modeled ``d2d_copy`` migration,
       and an unstaged cache costs a full host re-stage — both recorded on
       the decode lane's trace.

    All request batches are modeled as in flight concurrently — every KV
    cache stays live from its prefill to its decode, as on a real server
    holding resident caches per device (chunk ``request_batches`` if host
    memory can't hold them all at once at full model scale).

    Devices run batches sequentially within a lane; lanes run in parallel
    — the modeled makespan is the longest lane.  Lane seconds are model
    units throughout (batch-level cost-model breakdowns plus explicit cache
    moves, never wall clock): the jit cache means fine-grained launches
    only trace once per shape, so per-batch execution traces are not a
    coherent lane measure — the batch cost the scheduler placed is.
    """
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    if forward_mode is not None:
        # "graph": the decode steps run the graph-captured forward — each
        # block's dense FFN is lowered as an hnp expression graph (residual
        # fused into the FFN launch, per-launch residency threaded), through
        # the exact same registered descriptors as the eager path.
        cfg = dataclasses.replace(cfg, forward_mode=forward_mode)
    cluster = engine()
    # one set of weights serves every batch (and one jit cache warms up)
    model = build_model(cfg)
    params = init_params(model, seed)
    serve_step = jax.jit(make_serve_step(model), donate_argnums=(1,))

    per_device_s: Dict[int, float] = {}
    prefill_placements: List[int] = []
    handles: List[DeviceHandle] = []
    sessions = []  # (prefill outputs, cache, prefill_s, max_prompt)

    results: List[ServeResult] = []
    placements: List[int] = []
    cache_devices: List[int] = []
    total_tokens = 0
    d2d_s = 0.0
    restage_s = 0.0
    try:
        # ---- round 1: prefill placement + execution, caches pinned ------
        for i, prompts in enumerate(request_batches):
            cache = model.init_decode_cache(len(prompts), cache_len)
            p_dev, p_bd = cluster.assign(
                _prefill_cost(prompts, cfg), shape_key=f"serve-prefill-{i}"
            )
            prefill_placements.append(p_dev)
            with cluster.pin_device(p_dev):
                head, cache, prefill_s = _run_prefill(
                    serve_step, params, cache, prompts
                )
            per_device_s[p_dev] = per_device_s.get(p_dev, 0.0) + p_bd.offload_s
            handle = cluster.pin_handle(
                f"kv-cache-{i}", _cache_nbytes(cache), device_id=p_dev
            )
            if not pin_caches:
                # baseline: the cache drains to host DRAM between phases
                cluster.unstage_handle(handle)
            handles.append(handle)
            sessions.append(
                (head, cache, prefill_s, max(len(p) for p in prompts))
            )

        cluster.sync()  # prefill barrier: decode starts after prefills retire

        # ---- round 2: handle-affine decode placement + execution --------
        for i, prompts in enumerate(request_batches):
            head, cache, prefill_s, max_prompt = sessions[i]
            handle = handles[i]
            d_cost = _decode_cost(
                len(prompts), max_new_tokens, handle.nbytes, cfg
            )
            d_dev, _ = cluster.assign(
                d_cost,
                shape_key=f"serve-decode-{i}",
                handle=handle if pin_caches else None,
            )
            placements.append(d_dev)
            cache_devices.append(handle.device_id if handle.valid else -1)
            # Bring the cache to the decode lane first, paying the move
            # visibly (recorded on the active trace, charged to the lane):
            move_s = 0.0
            if not handle.valid:
                # unstaged cache: full host->device copy on this lane
                move_s = cluster.restage_handle(
                    handle, device_id=d_dev
                ).offload_s
                restage_s += move_s
            elif handle.device_id != d_dev:
                # pinned elsewhere: migrate over the d2d link
                move_s = cluster.migrate_handle(handle, d_dev).offload_s
                d2d_s += move_s
            with cluster.pin_device(d_dev):
                out, seen, cache, decode_s = _run_decode(
                    serve_step, params, cache, *head, start_pos=max_prompt,
                    max_new_tokens=max_new_tokens, temperature=temperature,
                    seed=seed,
                )
            # Not assign()'s breakdown: that one was scored before the move,
            # so a strayed/unstaged cache still counted in its copy region.
            # Now the cache is resident on the lane — the decode breakdown
            # takes the credit and the movement cost was added explicitly.
            lane_s = move_s + cluster.device(d_dev).breakdown_for(
                d_cost, cluster.policy, handle.name
            ).offload_s
            per_device_s[d_dev] = per_device_s.get(d_dev, 0.0) + lane_s
            results.append(ServeResult(
                tokens=out,
                logits=seen,
                prefill_s=prefill_s,
                decode_s=decode_s,
                tokens_per_s=(
                    len(prompts) * max_new_tokens / max(decode_s, 1e-9)
                ),
            ))
            total_tokens += len(prompts) * max_new_tokens

        cluster.sync()  # retire the batch tickets (modeled barrier)
    finally:
        # never leak handles into the singleton engine, even on failure
        for h in handles:
            cluster.release_handle(h)
    makespan_s = max(per_device_s.values(), default=0.0)
    return ClusterServeResult(
        results=results,
        placements=placements,
        prefill_placements=prefill_placements,
        cache_devices=cache_devices,
        per_device_s=per_device_s,
        makespan_s=makespan_s,
        total_tokens=total_tokens,
        tokens_per_s=total_tokens / max(makespan_s, 1e-9),
        d2d_s=d2d_s,
        restage_s=restage_s,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the published widths (default: the reduced config)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--scheduler", default="least-loaded",
                    choices=["round-robin", "least-loaded", "cost-aware"])
    ap.add_argument("--policy-mode", default="device",
                    choices=["host", "device", "auto"],
                    help="offload routing policy for the cluster run")
    ap.add_argument("--forward-mode", default=None,
                    choices=["eager", "graph"],
                    help="decode step forward path (graph = hnp capture)")
    ap.add_argument("--num-batches", type=int, default=1)
    ap.add_argument("--no-pin-caches", action="store_true",
                    help="baseline: caches drain to host between phases")
    # Streaming mode: the continuous-batching engine over a live arrival
    # process (fully modeled — no model build, so it runs anywhere fast).
    ap.add_argument("--stream", action="store_true",
                    help="run the streaming engine on a bursty trace")
    ap.add_argument("--qps", type=float, default=100.0,
                    help="offered load for --stream (requests/s)")
    ap.add_argument("--duration", type=float, default=1.0,
                    help="trace duration for --stream (modeled seconds)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    if args.stream:
        from repro.launch.streaming import (
            StreamConfig, bursty_trace, serve_stream,
        )

        # 1 prefill lane + >=1 decode lanes; default to the bench's
        # 4-device split unless the user asked for a bigger cluster.
        cfg = StreamConfig(
            num_devices=max(args.devices, 4), scheduler=args.scheduler
        )
        trace = bursty_trace(args.qps, args.duration, seed=args.seed)
        rep = serve_stream(args.arch, trace, config=cfg)
        o = rep.slo.overall
        print(f"streaming {args.arch}: offered {rep.offered_qps:.4g} qps "
              f"-> sustained {rep.sustained_qps:.4g} qps "
              f"(reject {rep.reject_rate:.1%}, "
              f"ttft p99 {o.ttft.p99_s * 1e3:.1f}ms, "
              f"per-token p99 {o.per_token.p99_s * 1e3:.2f}ms, "
              f"meets SLO: {rep.slo.meets_slo})")
        return
    rng = np.random.default_rng(args.seed)
    if args.devices > 1 or args.num_batches > 1:
        from repro.core.hero import offload_policy

        batches = [
            [list(rng.integers(1, 200, size=args.prompt_len))
             for _ in range(args.batch)]
            for _ in range(args.num_batches)
        ]
        with offload_policy(mode=args.policy_mode, num_devices=args.devices,
                            scheduler=args.scheduler):
            res = serve_cluster(
                args.arch, batches, smoke=args.smoke,
                max_new_tokens=args.max_new,
                temperature=args.temperature,
                pin_caches=not args.no_pin_caches,
                forward_mode=args.forward_mode,
            )
        print(f"{len(batches)} batches over {args.devices} devices "
              f"({args.scheduler}): prefill={res.prefill_placements} "
              f"decode={res.placements} "
              f"makespan={res.makespan_s:.6g}s "
              f"d2d={res.d2d_s:.3g}s restage={res.restage_s:.3g}s "
              f"{res.tokens_per_s:.4g} tok/s (modeled)")
        return
    prompts = [list(rng.integers(1, 200, size=args.prompt_len)) for _ in range(args.batch)]
    res = serve_batch(
        args.arch, prompts, smoke=args.smoke, max_new_tokens=args.max_new,
        temperature=args.temperature, seed=args.seed,
    )
    print(f"compile {res.compile_s:.2f}s prefill {res.prefill_s:.2f}s "
          f"decode {res.decode_s:.2f}s {res.tokens_per_s:.1f} tok/s")
    print(res.tokens)


if __name__ == "__main__":
    main()
